"""The decode step's state update in place (``ops/ssm_update``, interpret
mode) against ``ssm.mixer_step`` on the same inputs, at both benchmark
cells' state geometries; its routing gate; and the step of a layer pattern
through the kernel against the same step through XLA."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import ssm_update
from paddle_tpu.text import gpt, kv_pool, moe, ssm

# (layers, slots, heads, head_dim, d_state, groups): the hybrid cell's
# state (two B/C groups) and the layer-pattern cell's (one), 8 slots
GEOMETRIES = {"falconh1": (2, 8, 32, 128, 256, 2),
              "granite4h": (3, 8, 128, 64, 128, 1)}
LIVE = {"all": [1] * 8, "none": [0] * 8, "scattered": [0, 1, 0, 0, 1, 1, 0, 1]}
POS = [3, 0, 5, 0, 0, 7, 1, 2]      # slots 1, 3, 4 feed a first position
HIDDEN = 32


@pytest.fixture()
def interpret(monkeypatch):
    monkeypatch.setattr(ssm_update, "_INTERPRET", True)


def _mixer(geometry):
    L, B, H, P, N, G = GEOMETRIES[geometry]
    cfg = gpt.GPTConfig(
        vocab_size=64, hidden_size=HIDDEN, num_layers=1, num_heads=2,
        max_seq_len=16, dtype=jnp.float32,
        ssm=ssm.SSMConfig(n_heads=H, head_dim=P, d_state=N, n_groups=G))
    p = ssm.init_params(cfg.ssm, HIDDEN, 1, jax.random.PRNGKey(0), std=0.2)
    return cfg, {k: v[0] for k, v in p.items()}


@pytest.mark.parametrize("live", list(LIVE))
@pytest.mark.parametrize("geometry", list(GEOMETRIES))
def test_kernel_equals_mixer_step(interpret, geometry, live):
    """Decoding slots agree with the plain update to float32 rounding in
    state and output; idle slots and every OTHER layer of the leaves are
    bit for bit what they were; a decoding slot at position 0 gets the
    update from a zero state whatever the leaf held (NaNs here)."""
    L, B, H, P, N, G = GEOMETRIES[geometry]
    cfg, p = _mixer(geometry)
    assert ssm_update.available((L, B, H, P, N), jnp.float32, G)
    k = jax.random.split(jax.random.PRNGKey(1), 3)
    pos = jnp.asarray(POS, jnp.int32)
    mask = jnp.asarray(LIVE[live], bool)
    leaves = {"ssm": jax.random.normal(k[0], (L, B, H, P, N), jnp.float32),
              "conv": jax.random.normal(
                  k[1], (L, B, cfg.ssm.d_conv - 1, cfg.ssm.conv_dim))}
    first = (pos == 0)[None, :]
    leaves = {n: jnp.where(first.reshape(first.shape + (1,) * (v.ndim - 2)),
                           jnp.nan, v) for n, v in leaves.items()}
    n = jax.random.normal(k[2], (B, 1, HIDDEN), jnp.float32)
    layer = L - 1
    out, new = jax.jit(lambda lv: ssm.mixer_step_pooled(
        n, p, cfg, lv, layer, mask, pos))(leaves)
    want_out, want = ssm.mixer_step(
        n, p, cfg, ssm.from_zero({m: v[layer] for m, v in leaves.items()},
                                 pos, 0))
    on = np.asarray(mask)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out)[on], np.asarray(want_out)[on],
                               rtol=2e-5, atol=2e-5)
    for m in ssm.STATE_LEAVES:
        got, was = np.asarray(new[m]), np.asarray(leaves[m])
        np.testing.assert_allclose(got[layer][on], np.asarray(want[m])[on],
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_array_equal(got[layer][~on], was[layer][~on])
        np.testing.assert_array_equal(got[:layer], was[:layer])
        if on.any():
            assert np.isfinite(got[layer][on]).all()


def test_decoding_slots_come_first_and_the_rest_repeat_the_last():
    slots, count = ssm_update.decoding_slots(
        jnp.asarray([0, 1, 0, 0, 1, 1, 0, 1], bool))
    assert int(count[0]) == 4
    assert np.asarray(slots).tolist() == [1, 4, 5, 7, 7, 7, 7, 7]
    slots, count = ssm_update.decoding_slots(jnp.zeros((4,), bool))
    assert int(count[0]) == 0 and np.asarray(slots).tolist() == [0] * 4


@pytest.mark.parametrize("leaf,dtype,groups,ok", [
    ((6, 64, 32, 128, 256), jnp.float32, 2, True),
    ((9, 64, 128, 64, 128), jnp.float32, 1, True),
    ((2, 4, 4, 32, 16), jnp.float32, 2, False),      # lanes short of a tile
    ((2, 4, 32, 128, 256), jnp.bfloat16, 2, False),  # another state dtype
    ((2, 4, 32, 128, 8192), jnp.float32, 2, False),  # a head over a block
])
def test_gate_reads_shapes_and_the_platform(monkeypatch, leaf, dtype, groups,
                                            ok):
    assert ssm_update.supported(leaf, dtype, groups) == ok
    # this process runs on the CPU: the step keeps the XLA sequence there
    assert not ssm_update.available(leaf, dtype, groups)
    monkeypatch.setattr(ssm_update, "_INTERPRET", True)
    assert ssm_update.available(leaf, dtype, groups) == ok


def test_pattern_step_through_the_kernel_equals_the_step_through_xla(
        monkeypatch):
    """A layer pattern's decode step (mamba, attention, mamba, each under
    its expert layer) with a state the gate takes: the decoding slots'
    logits and every leaf as the XLA sequence gives them, first positions
    included; the idle slot's state to the bit (the K/V row it writes is
    garbage on both routes: a later step overwrites it)."""
    cfg = gpt.GPTConfig(
        vocab_size=64, hidden_size=HIDDEN, num_layers=3, num_heads=2,
        num_kv_heads=1, max_seq_len=32, dtype=jnp.float32, pos_embed="none",
        norm="rmsnorm", activation="swiglu", bias=False,
        layer_types=("mamba", "attention", "mamba"),
        ssm=ssm.SSMConfig(n_heads=4, head_dim=8, d_state=128, n_groups=2),
        experts=moe.ExpertShareConfig(8, 0, 3, 32, held=(0, 4),
                                      score="topk_softmax", shared_size=64))
    params = gpt.init_params(cfg, jax.random.PRNGKey(2))
    cache = kv_pool.init_paged_cache(cfg, 4, 32, block_size=8)
    nmax = cache["tables"].shape[1]
    junk = {n: jax.random.normal(jax.random.PRNGKey(i), cache[n].shape,
                                 jnp.float32).astype(cache[n].dtype)
            for i, n in enumerate(kv_pool.STATE_LEAVES + ("k", "v"))}
    cache = dict(cache, **junk,
                 tables=jnp.arange(4 * nmax, dtype=jnp.int32).reshape(4, nmax),
                 live=jnp.asarray([True, False, True, True]))
    tok = jnp.asarray([5, 6, 7, 8], jnp.int32)
    pos = jnp.asarray([9, 4, 0, 3], jnp.int32)

    def step():
        return jax.jit(lambda c: kv_pool.paged_decode_step_batched(
            params, c, tok, pos, cfg))(cache)

    want_lg, want = step()
    calls = []
    real = ssm_update.state_update
    monkeypatch.setattr(ssm_update, "_INTERPRET", True)
    monkeypatch.setattr(ssm_update, "state_update",
                        lambda *a: calls.append(1) or real(*a))
    from jax.experimental import pallas as pl

    traces = []
    build = pl.pallas_call
    monkeypatch.setattr(pl, "pallas_call",
                        lambda *a, **k: traces.append(1) or build(*a, **k))
    lg, new = step()
    assert len(calls) == 2          # once a mamba layer
    # and the kernel's unrolled body traced once for both (a trace a layer
    # was 1.3 s each of every launch's set-up on the chip's host)
    assert len(traces) <= 1
    live = np.asarray(cache["live"])
    np.testing.assert_allclose(np.asarray(lg)[live], np.asarray(want_lg)[live],
                               rtol=2e-5, atol=2e-5)
    kept = np.ones(cache["k"].shape[1:3], bool)
    kept[int(cache["tables"][1, 4 // 8]), 4 % 8] = False   # the idle slot's
    for n in ("k", "v"):
        np.testing.assert_allclose(np.asarray(new[n])[:, kept],
                                   np.asarray(want[n])[:, kept],
                                   rtol=2e-5, atol=2e-5)
    for n in kv_pool.STATE_LEAVES:
        np.testing.assert_allclose(np.asarray(new[n]), np.asarray(want[n]),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_array_equal(np.asarray(new[n][:, 1]),
                                      np.asarray(junk[n][:, 1]))


def test_state_walk_share_gauge_reads_host_arrays_only(monkeypatch):
    """``kv_pool.state_walk_share``: the slots whose state the next decode
    step touches over ``max_batch``.  1.0 where a layer's state is cut
    out for every slot (this process's own routing); the decoding slots'
    share where the kernel runs; set from the host's ``live`` array, no
    device read."""
    from paddle_tpu import telemetry
    from paddle_tpu.text import serving

    if not telemetry.enabled():
        pytest.skip("telemetry off")
    cfg = gpt.GPTConfig(
        vocab_size=64, hidden_size=HIDDEN, num_layers=2, num_heads=2,
        num_kv_heads=1, max_seq_len=32, dtype=jnp.float32, pos_embed="none",
        norm="rmsnorm", activation="swiglu", bias=False,
        layer_types=("mamba", "attention"),
        ssm=ssm.SSMConfig(n_heads=4, head_dim=8, d_state=128, n_groups=2),
        experts=moe.ExpertShareConfig(8, 0, 3, 32, held=(0, 4),
                                      score="topk_softmax", shared_size=64))
    params = gpt.init_params(cfg, jax.random.PRNGKey(3))

    def reading(interpret):
        monkeypatch.setattr(ssm_update, "_INTERPRET", interpret)
        srv = serving.DecodeServer(params, cfg, max_batch=4, max_len=32,
                                   layout="paged", block_size=8)
        assert srv._state_in_place == interpret
        srv.submit([1, 2, 3], max_new_tokens=6)
        srv.tick()
        srv.tick()
        assert len(srv._slots) == 1
        with monkeypatch.context() as m:
            m.setattr(jax, "device_get", None)      # a device read raises
            srv._tel_gauges()
        out = telemetry.snapshot()["gauges"]["kv_pool.state_walk_share"]
        srv.close()
        return out

    assert reading(False) == 1.0
    assert reading(True) == 0.25
