"""Sarvam-105B's layer as a layer pattern of latent layers
(``GPTConfig(layer_types=("mla", ...), first_k_dense=1, mla=MLAConfig(
q_lora_rank=None, rope_scaling=YaRN(...)), experts=ExpertShareConfig(
score="sigmoid", shared_size=...))``) against the family's plain reference,
``benchmarks/families/reference_sarvam_mla.py``, in float32 on the CPU at a
small size: the full forward, prefill then decode through the paged latent
pool, the absorbed decode with YaRN on, the sigmoid route, the shares adding
up, the YaRN table's closed-form numbers, the leading dense layer, the
parameter count of the published cut, and what the server refuses."""
import dataclasses
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import reference_sarvam_mla as ref
from benchmarks.families import sarvam_mla as fam
from paddle_tpu.text import fleet, gpt, kv_pool, mla, moe, serving

# config.json keys at the small size: 4 heads of 16 + 8 / 16 on a width of
# 64, latent rank 16, 16 routed experts of 32 (4 held), 4 a token, a shared
# expert of 32; one leading dense layer of 96 and two expert layers
YARN = {"type": "deepseek_yarn", "factor": 40,
        "original_max_position_embeddings": 4096, "beta_fast": 32,
        "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1}
MODEL = dict(
    hidden_size=64, num_attention_heads=4, kv_lora_rank=16,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    rope_theta=10000, rope_scaling=YARN, rms_norm_eps=1e-6,
    routed_scaling_factor=2.5, num_experts_published=16,
    num_experts_per_tok=4, first_k_dense_replace=1, held=[0, 4])
ARCH = ref.arch_of(MODEL)
V, T, L, F, FE = 128, 64, 3, 96, 32


def make_cfg(held=(0, 4)):
    m = MODEL
    y = m["rope_scaling"]
    return gpt.GPTConfig(
        vocab_size=V, hidden_size=m["hidden_size"], num_layers=L,
        num_heads=m["num_attention_heads"], intermediate_size=F,
        max_seq_len=T, dtype=jnp.float32, pos_embed="rope", norm="rmsnorm",
        activation="swiglu", rms_norm_eps=m["rms_norm_eps"],
        tie_embeddings=False, bias=False, layer_types=("mla",) * L,
        first_k_dense=1,
        mla=mla.MLAConfig(None, m["kv_lora_rank"], m["qk_nope_head_dim"],
                          m["qk_rope_head_dim"], m["v_head_dim"],
                          scale_q_lora=False, scale_kv_lora=False,
                          rope_scaling=mla.YaRN(
                              y["factor"],
                              y["original_max_position_embeddings"],
                              y["beta_fast"], y["beta_slow"], y["mscale"],
                              y["mscale_all_dim"])),
        experts=moe.ExpertShareConfig(16, 0, 4, FE, 2.5, tuple(held),
                                      "sigmoid", FE))


def make_params(cfg, seed=0):
    """``gpt.init_params`` with the router drawn wide and a non-zero
    selection bias, the sublayers' outputs large enough that every branch
    moves the logits, and a head that spreads them."""
    shapes = jax.eval_shape(lambda k: gpt.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    rng = np.random.default_rng(seed)
    outs = ("proj_w", "out_w", "down_w", "shared_down_w")

    def draw(path, x):      # init_params's law, drawn on the host
        name = [q.key for q in path if hasattr(q, "key")][-1]
        if name.endswith("_g"):
            return jnp.ones(x.shape, x.dtype)
        std = 0.02 / math.sqrt(2 * L) if name in outs else 0.02
        return jnp.asarray(std * rng.standard_normal(x.shape), x.dtype)

    p = jax.tree_util.tree_map_with_path(draw, shapes)
    b = p["blocks"]
    k = jax.random.PRNGKey(seed + 100)
    b["moe"]["router_w"] = b["moe"]["router_w"] * 15.0
    b["moe"]["router_b"] = 0.3 * jax.random.normal(
        k, b["moe"]["router_b"].shape)
    b["mla"]["proj_w"] = b["mla"]["proj_w"] * 20.0
    b["moe"]["down_w"] = tuple(w * 20.0 for w in b["moe"]["down_w"])
    p["lm_head"] = p["lm_head"] * 10.0
    return p


@pytest.fixture(scope="module")
def cfg():
    return make_cfg()


@pytest.fixture(scope="module")
def params(cfg):
    return make_params(cfg)


def tokens(seed, n):
    return np.random.default_rng(seed).integers(0, V, (n,)).astype(np.int32)


def pool_with_slot(cfg, slot=1, batch=3, block=8, blocks=24):
    cache = kv_pool.init_paged_cache(cfg, batch, T, block_size=block,
                                     num_blocks=blocks)
    nmax = cache["tables"].shape[1]
    tables = np.full((batch, nmax), -1, np.int32)
    tables[slot] = np.arange(3, 3 + nmax)
    return dict(cache, tables=jnp.asarray(tables),
                live=jnp.arange(batch) == slot)


@pytest.fixture(scope="module")
def steps(cfg):
    """(prefill, decode) of slot 1, jitted once for the module: a chunk of
    16 at ``pos0`` holding ``n`` tokens; one token at ``pos``."""
    chunk = jax.jit(lambda p, c, t, pos0, n: kv_pool.paged_prefill_chunk(
        p, c, t, pos0, n, jnp.asarray(1), cfg))
    step = jax.jit(lambda p, c, t, pos: kv_pool.paged_decode_step_batched(
        p, c, t, pos, cfg))

    def prefill(params, cache, seq, pos0, n):
        c = np.zeros((1, 16), np.int32)
        c[0, :n] = seq[pos0:pos0 + n]
        return chunk(params, cache, jnp.asarray(c), jnp.asarray(pos0),
                     jnp.asarray(n))

    def decode(params, cache, tok, pos):
        t, p = np.zeros((3,), np.int32), np.zeros((3,), np.int32)
        t[1], p[1] = tok, pos
        logits, cache = step(params, cache, jnp.asarray(t), jnp.asarray(p))
        return logits[1], cache

    return prefill, decode


SEQ = tokens(1, 27)


@pytest.fixture(scope="module")
def want(params):
    return ref.logits(params, SEQ, arch=ARCH)


@pytest.mark.parametrize("path", ["forward", "prefill_then_decode"])
def test_matches_the_reference(cfg, params, steps, want, path):
    """The full forward, and a prompt of 21 prefilled in chunks of 16 (the
    last one padded) then decoded token by token through the paged latent
    pool: every position's logits are the reference's full forward's."""
    prefill, decode = steps
    assert float(jnp.std(want)) > 0.3
    if path == "forward":
        got = jax.jit(lambda p, t: gpt.forward(p, t, cfg))(
            params, jnp.asarray(SEQ)[None])[0]
        np.testing.assert_allclose(got, want, atol=2e-5)
        return
    cache = pool_with_slot(cfg)
    for pos0 in (0, 16):
        logits, cache = prefill(params, cache, SEQ, pos0,
                                min(16, 21 - pos0))
    np.testing.assert_allclose(logits, want[20], atol=2e-5)
    for i in range(21, 27):
        logits, cache = decode(params, cache, SEQ[i], i)
        np.testing.assert_allclose(logits, want[i], atol=2e-5)
    # one latent row a mla layer: the leaf is as deep as the pattern
    assert cache[kv_pool.LATENT].shape == (L, 24, 8, 128)
    assert "k" not in cache and "v" not in cache
    # 6 steps x 2 expert layers x one live slot x 4 selections
    counts = dict(zip(moe.SHARE_COUNTS, np.asarray(cache[kv_pool.COUNTS])))
    assert counts["pairs_held"] + counts["pairs_absent"] == 6 * 2 * 4
    assert counts["calls"] == 12


def test_absorbed_decode_equals_up_projected_prefill(cfg, params, steps):
    """The last position of a prompt once as the last query of a prefill
    chunk (keys and values up-projected, YaRN on the rope lanes) and once
    as a decode step over the rows the earlier positions left."""
    prefill, decode = steps
    seq = tokens(9, 30)
    _, head = prefill(params, pool_with_slot(cfg), seq, 0, 16)
    up, _ = prefill(params, head, seq, 16, 14)
    _, cache = prefill(params, head, seq, 16, 13)
    absorbed, _ = decode(params, cache, seq[29], 29)
    np.testing.assert_allclose(absorbed, up, atol=2e-5)


def test_sigmoid_route_chooses_by_the_bias_and_weighs_without_it(cfg,
                                                                 params):
    """The selection bias changes which 8 are chosen and not their
    weights: the chosen sigmoids over their sum, 2.5 in all with the
    scaling."""
    m = jax.random.normal(jax.random.PRNGKey(1), (64, cfg.hidden_size))
    p = moe.layer_of(params["blocks"]["moe"], 0)
    idx, w = moe.route_share(m, p, cfg.experts)
    idx0, _ = moe.route_share(m, dict(p, router_b=jnp.zeros_like(
        p["router_b"])), cfg.experts)
    g = jax.nn.sigmoid(m @ p["router_w"])
    chosen = jnp.take_along_axis(g, idx, axis=-1)
    np.testing.assert_allclose(w, chosen / chosen.sum(-1, keepdims=True),
                               rtol=1e-6)
    np.testing.assert_allclose(cfg.experts.scaling * w.sum(-1), 2.5,
                               rtol=1e-6)
    assert (np.sort(np.asarray(idx), -1) != np.sort(np.asarray(idx0), -1)
            ).any(-1).mean() > 0.3
    want = ref.route(m, p["router_w"], p["router_b"], arch=ARCH)
    got = np.zeros((64, 16), np.float32)
    np.put_along_axis(got, np.asarray(idx), 2.5 * np.asarray(w), axis=-1)
    np.testing.assert_allclose(got, want, atol=1e-6)


def test_the_shares_add_up_to_the_uncut_layer():
    """Every share ``held = [4j, 4j + 4)`` of the 16 routed experts: the
    routed parts summed over the shares, plus what every chip computes
    alike (the shared expert; the latent sublayer is not in the expert
    layer) counted once, are the uncut reference's expert layer; a share
    alone is not."""
    whole = moe.ExpertShareConfig(16, 0, 4, FE, 2.5, (0, 16), "sigmoid", FE)
    rng = np.random.default_rng(3)
    p = {name: jnp.asarray(0.3 * rng.standard_normal(shape), jnp.float32)
         for name, shape in (
             ("router_w", (64, 16)), ("router_b", (16,)),
             ("gate_w", (16, 64, FE)), ("up_w", (16, 64, FE)),
             ("down_w", (16, FE, 64)), ("shared_gate_w", (64, FE)),
             ("shared_up_w", (64, FE)), ("shared_down_w", (FE, 64)))}
    m = jnp.asarray(rng.standard_normal((20, 64)), jnp.float32)
    want = ref._moe(m, p, arch=ref.arch_of(dict(MODEL, held=[0, 16])))

    def share_of(j, zero_down=False):
        q = dict(p)
        for name in ("gate_w", "up_w", "down_w"):
            q[name] = q[name][4 * j:4 * j + 4]
        if zero_down:
            q["down_w"] = jnp.zeros_like(q["down_w"])
        ex = dataclasses.replace(whole, held=(4 * j, 4 * j + 4))
        return jax.jit(lambda x: moe.expert_share(m, x, ex,
                                                  jnp.float32)[0])(q)

    alike = share_of(0, zero_down=True)
    total = alike
    for j in range(4):
        total = total + share_of(j) - alike
    np.testing.assert_allclose(total, want, rtol=1e-5, atol=1e-4)
    assert float(jnp.max(jnp.abs(alike - want))) > 1e-1


def test_yarn_table_and_scale_at_the_published_widths():
    """The 64 rope lanes at base 10,000, factor 40 over 4,096: the ramp
    runs from pair 10 to pair 23, mscale(40, 1)^2 = (0.1 ln 40 + 1)^2 =
    1.873854 multiplies the softmax scale (0.135234 at 192 lanes a head),
    cos and sin are not scaled; the program's table is the reference's."""
    y = mla.YaRN(40, 4096, 32, 1, 1, 1)
    assert y.ramp_range(64, 10000) == (10, 23)
    assert y.softmax_factor == pytest.approx((0.1 * math.log(40) + 1) ** 2)
    assert y.softmax_factor == pytest.approx(1.873854, abs=1e-6)
    assert y.cos_scale == 1.0
    m = dict(MODEL, qk_rope_head_dim=64, qk_nope_head_dim=128)
    inv, cos_f, soft_f, low, high = ref.yarn(ref.arch_of(m))
    assert (low, high, cos_f) == (10, 23, 1.0)
    np.testing.assert_allclose(y.inv_freq(64, 10000), inv, rtol=1e-6)
    f = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(inv[:10], f[:10], rtol=1e-12)
    np.testing.assert_allclose(inv[23:], f[23:] / 40, rtol=1e-12)
    c = dataclasses.replace(make_cfg(), num_heads=64, mla=mla.MLAConfig(
        None, 512, 128, 64, 128, False, False, y))
    assert mla._scale(c) == pytest.approx(0.135234, abs=1e-6)


def test_the_leading_dense_layer(cfg, params, want):
    """Layer 0 runs the dense SwiGLU of ``intermediate_size`` under the
    ``mlp`` scope and counts no expert; the expert layers' leaves are as
    deep as the layers past it."""
    b = params["blocks"]
    assert b["dense"]["gate_w"].shape == (1, 64, F)
    assert b["moe"]["router_w"].shape == (2, 64, 16)
    assert len(b["moe"]["gate_w"]) == 2
    p0 = gpt.pattern_layer(b, cfg, 0)
    assert "dense" in p0 and "moe" not in p0
    # 27 rows, as the module's reference logits: its pieces are compiled
    h = jax.random.normal(jax.random.PRNGKey(2), (27, 64))
    mixer = lambda n: gpt._latent_attend_seqs(n, p0, cfg, 1, 27)  # noqa: E731
    block = jax.jit(lambda x: gpt.pattern_block(x, p0, cfg, mixer))
    out, counts = block(h)
    assert counts is None
    want = ref.layer(h, ref.layer_weights(b, 0, ARCH), arch=ARCH)
    np.testing.assert_allclose(out, want, atol=2e-5)
    text = block.lower(h).as_text(debug_info=True)
    assert "mlp" in text and "moe" not in text


def test_the_published_cut_counts_to_the_digit():
    """The cell's configuration: one dense layer and four expert layers of
    32 held experts, a quarter of the vocabulary."""
    with open("benchmarks/configs/sarvam-105b-serve.json") as f:
        config = json.load(f)
    s = fam.sizes(config)
    c = fam.gpt_config(config)
    shapes = jax.eval_shape(lambda k: gpt.init_params(c, k),
                            jax.random.PRNGKey(0))
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))
    assert n == gpt.count_params(c) == fam.total_params(s) == 4_535_401_472
    # the attention's five parts: Wq, Wkva, the latent gain, Wkvb, Wo
    assert fam.mla_params(s) == (50_331_648 + 2_359_296 + 512 + 8_388_608
                                 + 33_554_432) == 94_634_496
    assert fam.layer_params(s, True) == 295_969_280
    assert fam.layer_params(s, False) == 120_332_928
    assert fam.layer_params(s, False) + 32 * fam.expert_params(s) \
        == 925_639_296


def server(params, cfg, **kw):
    kw.setdefault("layout", "paged")
    return serving.DecodeServer(params, cfg, max_batch=2, max_len=T,
                                block_size=8, **kw)


@pytest.mark.parametrize("case,word", [
    ("contiguous", "contiguous"), ("spec_k", "speculation"),
    ("env_spec_tree", "speculation"), ("env_int8", "int8"),
    ("env_spill", "spill"), ("submit_prefilled", "wire form"),
    ("prefill_worker", "wire form"),
])
def test_refuses_as_the_latent_block_does(cfg, params, monkeypatch, case,
                                          word):
    """A pattern with latent layers refuses what latent rows cannot do
    yet, by name, as the latent block does."""
    env = {"env_spec_tree": ("PADDLE_TPU_SPEC_TREE", "4"),
           "env_int8": ("PADDLE_TPU_KV_DTYPE", "int8"),
           "env_spill": ("PADDLE_TPU_KV_SPILL_MB", "4")}
    if case in env:
        monkeypatch.setenv(*env[case])
    with pytest.raises(NotImplementedError, match=word):
        if case == "contiguous":
            server(params, cfg, layout="contiguous")
        elif case == "spec_k":
            server(params, cfg, spec_k=3)
        elif case == "submit_prefilled":
            server(params, cfg).submit_prefilled(
                tokens(1, 8), {}, np.zeros((V,), np.float32))
        elif case == "prefill_worker":
            fleet.PrefillWorker(params, cfg, max_len=T, layout="paged")
        else:
            server(params, cfg)


def test_served_greedy_tokens_and_the_programs_counts(cfg, params):
    """Two prompts served together: each served token is the
    reference's best at its position (margin 0 in float32); the drain
    counts the rows x held experts the decode steps computed from the
    host's step number (every slot through every held expert of both
    expert layers), of which the selections are a small part; the
    reference's route puts some of a token's routed weight on the held
    experts, not all."""
    prompts = [tokens(40 + n, n) for n in (5, 7)]
    srv = serving.DecodeServer(params, cfg, max_batch=4, max_len=T,
                               layout="paged", block_size=8)
    rids = [srv.submit(p, max_new_tokens=4) for p in prompts]
    while srv.pending():
        srv.tick()
    mass = []
    for p, r in zip(prompts, rids):
        m = ref.served_margins(params, p, srv.result(r), arch=ARCH,
                               pad_to=T, held_mass=mass)
        assert float(m.max()) < 1e-4
    st = srv.load_stats()
    assert st["moe_expert_rows"] == srv._step_no * 4 * 4 * 2
    assert srv._step_no >= 3
    assert 0 < st["moe_pairs_held"] < st["moe_expert_rows"]
    assert all(0.0 < x < 1.0 for x in mass) and len(mass) == 2, mass
