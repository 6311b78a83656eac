"""Ask the chip's compiler, without the chip.

Every Pallas kernel on the main path, one decode step of the Engine and one
``hybrid.train_step`` are compiled here for a *described* ``v5e:2x2`` at the
widths of ``gpt.gpt_1p3b()`` (hidden 2048, 16 heads of 128, vocab 50304,
seq 2048).  What the compiler refuses here it refuses on the chip; interpret
mode cannot show that (the two decode kernels passed every interpret test
while both were refused for their block shapes).  Nothing runs, so nothing
here says anything about results or times.

Rules this file keeps (``/opt/skills/guides/on-chip-measurement``): the
topology is described inside a module-scoped fixture that skips when it
cannot be, never at import; shapes and shardings are built in fixtures or
tests; the persistent compile cache is off around the compiles (an entry
written for a described chip cannot be read back without one); everything
compiles in the test's own process; and all such tests live in this one
file, because the TPU library belongs to one process at a time.
"""
import contextlib
import dataclasses
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, SingleDeviceSharding

B, H, HD, T, D, V = 8, 16, 128, 2048, 2048, 50304
BF, F32, I8, I32 = jnp.bfloat16, jnp.float32, jnp.int8, jnp.int32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc

    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    cc.reset_cache()


@pytest.fixture()
def shape(one_chip, no_persistent_cache):
    """``shape((8, 128), dtype)`` -> a ShapeDtypeStruct on the described
    chip (there is no device to hold an array)."""
    return lambda dims, dt: jax.ShapeDtypeStruct(dims, dt, sharding=one_chip)


@pytest.fixture()
def as_on_tpu(monkeypatch):
    """Whole programs ask ``_pallas.on_tpu()`` and would take their CPU
    branch here: steer it in the test, not through an option."""
    from paddle_tpu.ops import _pallas

    monkeypatch.setattr(_pallas, "on_tpu", lambda: True)
    monkeypatch.setenv("PADDLE_TPU_FUSED_LN", "1")
    monkeypatch.setenv("PADDLE_TPU_FUSED_CE", "1")


def kernels_in(fn, *args, names=()) -> int:
    """Compile ``fn`` for the described chip; how many Mosaic kernels the
    executable holds.  Each of ``names`` (a kernel's ``name=``) must name
    one of its custom calls: a device trace tells kernels apart by it."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    for name in names:
        assert _names_a_kernel(name, text), name
    return text.count("tpu_custom_call")


def _kernel_calls(name: str, text: str) -> int:
    """How many custom calls of the compiled text are named after the
    kernel (``%flash_attention_fwd.3``; under a bare ``jax.vjp`` the name
    carries the transformation, ``%jvp_flash_attention_fwd_.1``) with an
    op_name path that holds the kernel's name as a scope of its own."""
    return len(re.findall(
        rf'%\w*{name}_*(?:\.\d+)? = .* custom-call\(.*'
        rf'op_name="[^"]*\b{name}\)*/pallas_call"', text))


def _names_a_kernel(name: str, text: str) -> bool:
    return _kernel_calls(name, text) > 0


# --------------------------------------------------------------------------
# training kernels
# --------------------------------------------------------------------------


@pytest.mark.parametrize("heads,hd,dtype", [
    (H, HD, BF), (12, HD, BF),  # gpt_1p3b's heads; the train cell's
    # every head size the gate takes, in both dtypes: the tiles the rule
    # gives each fit the chip's VMEM (head size 256 in float32 shrinks dq)
    *[(2, d, dt) for d in (64, 128, 256) for dt in (BF, F32)]])
def test_flash_fwd_bwd_causal(shape, heads, hd, dtype):
    """The three kernels at the tiles ``_block_sizes`` commits for a
    2,048-token head compile under their names (no VMEM overflow)."""
    from paddle_tpu.ops import flash_attention as fa

    q = shape((1, T, heads, hd), dtype)
    n = kernels_in(
        lambda q, k, v: jax.vjp(
            lambda a, b, c: fa._flash(a, b, c, True, None), q, k, v)[1](q),
        q, q, q, names=("flash_attention_fwd", "flash_attention_bwd_dq",
                        "flash_attention_bwd_dkv"))
    assert n == 3  # fwd, dq, dk/dv


def test_fused_layer_norm_fwd_bwd(shape):
    from paddle_tpu.ops import fused_norm as fn

    x, g = shape((T, D), BF), shape((D,), BF)
    n = kernels_in(
        lambda x, g, b: jax.vjp(
            lambda a, w, c: fn._fused_ln(a, w, c, 1e-5), x, g, b)[1](x),
        x, g, g, names=("fused_layer_norm_fwd", "fused_layer_norm_bwd"))
    assert n == 2


def test_fused_softmax_ce_fwd_bwd(shape):
    from paddle_tpu.ops import fused_ce as fce

    n = kernels_in(
        lambda l, y: jax.vjp(lambda a: fce._fused_ce(a, y), l)[1](
            jnp.ones((T,), F32)),
        shape((T, V), BF), shape((T,), I32),
        names=("fused_softmax_ce_fwd", "fused_softmax_ce_bwd"))
    assert n == 2


def test_w4_matmul(shape):
    from paddle_tpu.ops import woq_matmul as wm

    n = kernels_in(lambda x, p, s: wm._w4_call(x, p, s, 128),
                   shape((8, D), BF), shape((D // 2, 4 * D), I8),
                   shape((D // 128, 1, 4 * D), F32), names=("w4_matmul",))
    assert n == 1


# --------------------------------------------------------------------------
# decode kernels: refused on the parent commit for their block shapes
# (pos block (1, 1) of (8, 1); KV block (1, bs, 1, 128) of [N, bs, 16, 128]:
# PR 21's pool; since PR 32 a K/V leaf is [L, N, bs, Hkv*hd])
# --------------------------------------------------------------------------


@pytest.mark.parametrize("Tq", [1, 4])
@pytest.mark.parametrize("kv", ["bf16", "int8"])
@pytest.mark.parametrize("Hkv", [16, 4])
def test_decode_kernel(shape, Hkv, kv, Tq):
    from paddle_tpu.ops import decode_attention as da

    q = shape((B, Tq, H, HD), BF)
    k = shape((B, T, Hkv, HD), BF if kv == "bf16" else I8)
    sc = shape((B, T, Hkv), F32) if kv == "int8" else None
    assert da.supported(q.shape, k.shape)
    n = kernels_in(
        lambda q, k, v, p, a, b: da._decode_call(q, k, v, p, a, b, None),
        q, k, k, shape((B,), I32), sc, sc)
    assert n == 1


# (slots, query heads, KV heads, pool blocks, rows a block, table entries):
# this file's widths at both block sizes, then the two chat cells' own
PAGED_SHAPES = {
    "bs16": (B, H, H, B * T // 16, 16, T // 16),
    "bs128": (B, H, H, B * T // 128, 128, T // 128),
    "gpt1p3b-serve-chat": (32, 16, 16, 3072, 16, 128),
    "falconh1-serve-chat": (64, 20, 4, 8192, 16, 128),
}


@pytest.mark.parametrize("Tq", [1, 4])
@pytest.mark.parametrize("pool_shape", list(PAGED_SHAPES))
@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_paged_decode_kernel(shape, kv, pool_shape, Tq):
    from paddle_tpu.ops import decode_attention as da

    slots, Hq, Hkv, N, bs, nmax = PAGED_SHAPES[pool_shape]
    q = shape((slots, Tq, Hq, HD), BF)
    # the pool's leaf as it is stored: two layers, a row's heads side by
    # side, read at a layer the kernel is told at run time
    pool = shape((2, N, bs, Hkv * HD), BF if kv == "bf16" else I8)
    sc = shape((2, N, bs, Hkv), F32) if kv == "int8" else None
    assert da.paged_supported(q.shape, pool.shape)
    n = kernels_in(
        lambda q, k, v, t, p, li, a, b: da._paged_call(q, k, v, t, p, li, a,
                                                       b, None),
        q, pool, pool, shape((slots, nmax), I32), shape((slots,), I32),
        shape((), I32), sc, sc, names=("paged_decode_attention",))
    assert n == 1


# the two state-space cells' leaves as their servers hold them (64 slots):
# (layers, heads, head_dim, d_state, B/C groups)
STATE_SHAPES = {"falconh1-serve-chat": (6, 32, 128, 256, 2),
                "granite4h-serve-docqa": (9, 128, 64, 128, 1)}


@pytest.mark.parametrize("cell", list(STATE_SHAPES))
def test_ssm_state_update_kernel(shape, cell):
    """The decode step's state update at the cell's full geometry: one
    kernel, the donated leaf aliased to its output and nothing of a
    layer's size beside it."""
    from paddle_tpu.ops import ssm_update as su

    L, Hm, P, N, G = STATE_SHAPES[cell]
    slots = 64
    leaf = shape((L, slots, Hm, P, N), F32)
    assert su.supported(leaf.shape, leaf.dtype, G)
    compiled = jax.jit(su.state_update, donate_argnums=(0,)).lower(
        leaf, shape((), I32), shape((slots,), jnp.bool_),
        shape((slots,), I32), shape((slots, Hm, P), F32),
        shape((slots, Hm), F32), shape((slots, G, N), F32),
        shape((slots, G, N), F32)).compile()
    text = compiled.as_text()
    assert _kernel_calls("ssm_state_update", text) == 1
    assert text.count("tpu_custom_call") == 1
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= L * slots * Hm * P * N * 4
    assert mem.temp_size_in_bytes < 1 << 20


# --------------------------------------------------------------------------
# whole programs at reduced depth, through the entry points' own builders
# --------------------------------------------------------------------------


def _abstract(tree, sharding=None, dtype=None):
    def one(x):
        dt = dtype if dtype is not None and x.dtype == F32 else x.dtype
        return jax.ShapeDtypeStruct(x.shape, dt, sharding=sharding)

    return jax.tree_util.tree_map(one, tree)


def _gpt(layers):
    from paddle_tpu.text import gpt

    return dataclasses.replace(gpt.gpt_1p3b(), num_layers=layers)


def _param_shapes(cfg):
    from paddle_tpu.text import gpt

    return jax.eval_shape(lambda k: gpt.init_params(cfg, k),
                          jax.ShapeDtypeStruct((2,), jnp.uint32))


@pytest.fixture()
def purge_engine():
    from paddle_tpu.text import engine

    cfgs = []
    yield cfgs.append
    engine.ENGINE.purge(*cfgs)


def _decode_step(cfg, layout, shard, sharding):
    """(jitted decode step of the Engine, its abstract arguments)."""
    from paddle_tpu.text import engine, generate

    fn = engine.ENGINE.get("step", engine.StepSpec(
        cfg=cfg, paged=(layout == "paged"), shard=shard))
    params = _abstract(_param_shapes(cfg), sharding, dtype=BF)
    cache = _abstract(jax.eval_shape(
        lambda: generate.init_cache(cfg, B, T, layout=layout)), sharding)
    tok = jax.ShapeDtypeStruct((B,), I32, sharding=sharding)
    return fn, (params, cache, tok, tok)


@pytest.mark.parametrize("layout", ["contiguous", "paged"])
def test_engine_decode_step(one_chip, no_persistent_cache, as_on_tpu,
                            purge_engine, layout):
    cfg = _gpt(2)
    purge_engine(cfg)
    fn, args = _decode_step(cfg, layout, None, one_chip)
    text = fn.lower(*args).compile().as_text()
    assert "tpu_custom_call" in text  # the decode-attention kernel
    # the accepted decode metrics find the step by these names
    kernel = "paged_decode_attention" if layout == "paged" \
        else "decode_attention"
    assert _names_a_kernel(kernel, text)
    assert text.startswith("HloModule jit__lambda,")
    # every op's op_name path starts with the step it belongs to
    paths = set(re.findall(r'op_name="([^"]*)"', text))
    assert any(p.startswith("jit(<lambda>)/serving.step/") and "/attn/" in p
               for p in paths)


def test_hybrid_decode_step_and_prefill(one_chip, no_persistent_cache,
                                        as_on_tpu, purge_engine):
    """A Mamba-2 mixer beside grouped-query attention at the published
    widths of the benchmark's hybrid configuration (two layers of it, an
    eighth of the vocabulary): the paged kernel takes 20 query heads
    over 4 KV heads of 128, the state leaves ride the layer scan's carry
    and are aliased to the outputs (donated with the pool), the state is
    advanced by the ``ssm_state_update`` kernel where it is stored (once
    in the layer scan's body: no op's result is a layer's state), and the
    mixer's ops, the kernel among them, carry the ``ssm`` scope the
    per-layer metrics select."""
    import json

    from benchmarks.families import falcon_h1 as fam
    from paddle_tpu.text import engine, generate

    with open("benchmarks/configs/falcon-h1-34b-serve.json") as f:
        config = json.load(f)
    config.update(num_hidden_layers=2, vocab_size=32640)
    cfg = fam.gpt_config(config)
    purge_engine(cfg)
    params = _abstract(_param_shapes(cfg), one_chip, dtype=BF)
    cache = _abstract(jax.eval_shape(lambda: generate.init_cache(
        cfg, B, T, layout="paged")), one_chip)
    assert cache["ssm"].shape == (2, B, 32, 128, 256)
    assert cache["ssm"].dtype == F32 and cache["live"].shape == (B,)
    tok = jax.ShapeDtypeStruct((B,), I32, sharding=one_chip)
    step = engine.ENGINE.get("step", engine.StepSpec(cfg=cfg, paged=True))
    compiled = step.lower(params, cache, tok, tok).compile()
    text = compiled.as_text()
    assert _names_a_kernel("paged_decode_attention", text)
    assert _kernel_calls("ssm_state_update", text) == 1   # the scan's body
    assert _kernel_on_path("ssm_state_update", "/ssm/ssm_update/", text)
    assert _holds_pool_slices(text, B * 32 * 128 * 256, "f32") == []
    paths = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in ("/ssm/ssm_update/", "/ssm/ssm_conv/", "/attn/", "(mlp)/"):
        assert any(p.startswith("jit(<lambda>)/serving.step/")
                   and scope in p for p in paths), scope
    # the state is written in place: no copy of it, or of a layer of it
    # (33.5 MB at these 8 slots), in the step
    mem = compiled.memory_analysis()
    state = sum(int(np.prod(cache[n].shape)) * cache[n].dtype.itemsize
                for n in ("ssm", "conv"))
    assert mem.alias_size_in_bytes >= state
    assert mem.temp_size_in_bytes < 8 << 20
    scalar = jax.ShapeDtypeStruct((), I32, sharding=one_chip)
    pf = engine.ENGINE.get("paged_prefill",
                           engine.StepSpec(cfg=cfg, bucket=256))
    text = pf.lower(params, cache, jax.ShapeDtypeStruct(
        (1, 256), I32, sharding=one_chip), scalar, scalar,
        scalar).compile().as_text()
    assert any("/ssm/ssm_scan/" in p for p in
               re.findall(r'op_name="([^"]*)"', text))


# the two chat cells' servers (slots, pool blocks; 16 rows a block, a
# window of 2048), and ``peak_memory_in_bytes`` of their decode step as the
# parent of PR 32 (122b50b) compiled it here, at full depth
POOL_CELLS = {"gpt1p3b": (32, 3072), "falconh1": (64, 8192)}
PARENT_STEP_PEAK = {"gpt1p3b": 12_906_021_376, "falconh1": 14_352_944_640}
_HLO_BYTES = {"bf16": 2, "f16": 2, "f32": 4, "s8": 1, "u8": 1, "s32": 4,
              "u32": 4, "pred": 1}


def _computations(text) -> dict:
    """{computation's name: its instructions' lines} of a compiled module."""
    out, name = {}, None
    for line in text.splitlines():
        m = re.match(r"^(?:ENTRY )?%([\w.\-]+) \(.*\{$", line)
        if m:
            name = m.group(1)
            out[name] = []
        elif name is not None:
            out[name].append(line)
    return out


def _aliases_its_large_outputs(shape: str, rest: str, slice_elems: int,
                               dtype: str) -> bool:
    """Every output of a custom call's result ``shape`` that holds a
    layer's slice is named in its ``output_to_operand_aliasing``."""
    outs = re.findall(r"\b([a-z]+\d*)\[([\d,]*)\]", shape)
    m = re.search(r"output_to_operand_aliasing=\{(.*?)\}, [a-z_]+=", rest)
    aliased = set(re.findall(r"\{(\d*)\}: \(", m.group(1))) if m else set()
    large = [i for i, (dt, dims) in enumerate(outs)
             if dt == dtype and int(np.prod(
                 [int(d) for d in dims.split(",") if d])) >= slice_elems]
    one = len(outs) == 1
    return all(("" if one else str(i)) in aliased for i in large)


def _kernel_on_path(name: str, scope: str, text: str) -> bool:
    """A custom call named after the kernel sits under ``scope``."""
    return re.search(rf'custom-call\(.*op_name="[^"]*{scope}[^"]*\b{name}\)*'
                     rf'/pallas_call"', text) is not None


def _holds_pool_slices(text, slice_elems: int, dtype: str) -> list:
    """(name, opcode, shape) of every op whose result holds a whole number
    of layer slices of a K/V leaf (``slice_elems`` elements of ``dtype``
    each), one at least, and is not the leaf passed on as it is: a
    parameter, a tuple or its element, the loop, a bitcast, the row
    scatter writing into the leaf it was given, or a kernel whose output
    of that size is one of its operands (``output_to_operand_aliasing``:
    the state update writes the slots it visits where they are)."""
    comps = _computations(text)
    passed_on = {"parameter", "tuple", "get-tuple-element", "while",
                 "bitcast", "scatter"}
    found = []
    for lines in comps.values():
        for line in lines:
            m = re.match(r"^\s+(?:ROOT )?%([\w.\-]+) = (.*?)\s"
                         r"([a-z][a-z\-]*)\((.*)$", line)
            if m is None:
                continue
            name, shape, opcode, rest = m.groups()
            sizes = [int(np.prod([int(d) for d in dims.split(",") if d]))
                     for dt, dims in re.findall(r"\b([a-z]+\d*)\[([\d,]*)\]",
                                                shape) if dt == dtype]
            if not any(n >= slice_elems and n % slice_elems == 0
                       for n in sizes) or opcode in passed_on:
                continue
            if opcode == "custom-call" and _aliases_its_large_outputs(
                    shape, rest, slice_elems, dtype):
                continue
            called = re.search(r"calls=%([\w.\-]+)", rest)
            body = comps.get(called.group(1), ()) if called else ()
            if opcode == "fusion" and any(" scatter(" in ln for ln in body):
                continue
            found.append((name, opcode, shape.split("{")[0]))
    return found


@pytest.mark.parametrize("program", ["gpt1p3b-decode", "gpt1p3b-prefill256",
                                     "falconh1-decode"])
def test_paged_step_holds_no_slice_of_the_pool(one_chip, no_persistent_cache,
                                               as_on_tpu, purge_engine,
                                               program):
    """The pool is addressed by (layer, page): at the chat cells' own
    sizes and depths, no op of the compiled decode step or prefill has a
    layer's slice of a K/V leaf as its result (a copy, a dynamic slice, a
    reshape or bitcast-convert fusion: the parent's step held four a
    layer, ``constant_dynamic-slice_fusion`` and ``squeeze..._reshape``
    twice each, 87% of the 1.3B cell's step on the chip), the row scatter
    writes in place, and the decode step is not larger than the
    parent's."""
    import json

    from benchmarks.families import falcon_h1 as fam
    from paddle_tpu.text import engine, generate

    cell, kind = program.split("-")
    if cell == "gpt1p3b":
        cfg = _gpt(24)
    else:
        with open("benchmarks/configs/falcon-h1-34b-serve.json") as f:
            cfg = fam.gpt_config(json.load(f))
    purge_engine(cfg)
    slots, blocks = POOL_CELLS[cell]
    params = _abstract(_param_shapes(cfg), one_chip, dtype=BF)
    cache = _abstract(jax.eval_shape(lambda: generate.init_cache(
        cfg, slots, T, layout="paged", block_size=16, num_blocks=blocks)),
        one_chip)
    slice_elems = blocks * 16 * cfg.kv_heads * cfg.head_dim
    assert int(np.prod(cache["k"].shape)) == cfg.num_layers * slice_elems
    assert cache["k"].dtype == BF
    if kind == "decode":
        tok = jax.ShapeDtypeStruct((slots,), I32, sharding=one_chip)
        fn = engine.ENGINE.get("step", engine.StepSpec(cfg=cfg, paged=True))
        args = (params, cache, tok, tok)
    else:
        scalar = jax.ShapeDtypeStruct((), I32, sharding=one_chip)
        fn = engine.ENGINE.get("paged_prefill",
                               engine.StepSpec(cfg=cfg, bucket=256))
        args = (params, cache, jax.ShapeDtypeStruct(
            (1, 256), I32, sharding=one_chip), scalar, scalar, scalar)
    compiled = fn.lower(*args).compile()
    text = compiled.as_text()
    if kind == "decode":
        assert _names_a_kernel("paged_decode_attention", text)
    assert _holds_pool_slices(text, slice_elems, "bf16") == []
    mem = compiled.memory_analysis()
    if cell == "falconh1":
        # nor a layer's state: the kernel once in the scan's body, and of
        # the parent's two float32 slices of 268 MB nothing is left
        assert _kernel_calls("ssm_state_update", text) == 1
        assert _holds_pool_slices(text, slots * 32 * 128 * 256, "f32") == []
        assert mem.temp_size_in_bytes < 16 << 20
        assert mem.peak_memory_in_bytes < PARENT_STEP_PEAK[cell] - 4e8
    # both leaves are donated and written where they are
    assert mem.alias_size_in_bytes >= 2 * cfg.num_layers * slice_elems * 2
    if cell == "gpt1p3b":
        # nothing of a slice's size among the temporaries (the parent:
        # 604,726,272 B in the step, 253,104,640 B in this prefill)
        assert mem.temp_size_in_bytes < slice_elems * 2
    if kind == "decode":
        # the hybrid step's peak is its mixer's state temporaries on both
        # sides (two float32 slices of 268 MB): within 1 KB of the parent's
        assert mem.peak_memory_in_bytes <= PARENT_STEP_PEAK[cell] + (1 << 20)
        if cell == "gpt1p3b":
            assert mem.peak_memory_in_bytes < PARENT_STEP_PEAK[cell] - 5e8


def _branches_of(comps) -> list:
    """[(the computation a ``conditional`` sits in, [its branch
    computations, false first])] of ``_computations(text)``."""
    return [(comp, re.findall(r"%([\w.\-]+)", m.group(1)))
            for comp, lines in comps.items() for line in lines
            for m in [re.search(r"\sconditional\(.*branch_computations="
                                r"\{([^}]*)\}", line)] if m]


def _reached_from(comps, comp) -> set:
    """``comp`` and every computation its instructions call, to any
    depth (a fusion's body, a sort's comparator, a nested branch)."""
    seen, todo = set(), [comp]
    while todo:
        c = todo.pop()
        if c in seen or c not in comps:
            continue
        seen.add(c)
        for line in comps[c]:
            todo += re.findall(r"(?:calls|to_apply|body|condition)="
                               r"%([\w.\-]+)", line)
            for group in re.findall(r"branch_computations=\{([^}]*)\}",
                                    line):
                todo += re.findall(r"%([\w.\-]+)", group)
    return seen


def _sampler_work(comps, names) -> list:
    """What only a sampled step needs among the instructions of the
    computations ``names``: ``"sort"`` for a sort of the vocabulary,
    ``"bits"`` for an op that makes random bits (jax's threefry is plain
    integer ops on the chip, told by its op_name path; the hardware
    generator by its opcode)."""
    found = []
    for c in names:
        for line in comps[c]:
            if re.search(r"\ssort\(", line):
                found.append("sort")
            elif re.search(r"\srng[\w\-]*\(|op_name=\"[^\"]*(?:threefry|"
                           r"random_bits|_uniform|_gumbel)", line):
                found.append("bits")
    return found


@pytest.mark.parametrize("cell", ["gpt1p3b", "falconh1"])
def test_async_step_sorts_nothing_for_a_greedy_batch(
        one_chip, no_persistent_cache, as_on_tpu, purge_engine, cell):
    """The paged ``async`` step, the one every serve cell runs, at the chat
    cells' own slots, pools and vocabularies (two layers: the sampler sees
    ``[slots, V]`` logits whatever the depth): the sampler's work is chosen
    on the device.  XLA keeps both ``lax.cond``s as ``conditional`` ops
    (flattened into a ``select`` every batch would pay for the sorted
    branch again); no sort and no random-bit op is in the entry
    computation, outside the conditionals, or in the all-greedy branch;
    the drawn-without-a-filter branch draws and sorts nothing; the whole
    module holds one sort where the parent's held two; and the ops inside
    the branches keep the ``serving.async_step/sample`` path the
    per-layer metrics select them by."""
    import json

    from benchmarks.families import falcon_h1 as fam
    from paddle_tpu import telemetry
    from paddle_tpu.text import engine, generate

    if cell == "gpt1p3b":
        cfg = _gpt(2)
    else:
        with open("benchmarks/configs/falcon-h1-34b-serve.json") as f:
            config = json.load(f)
        config.update(num_hidden_layers=2)
        cfg = fam.gpt_config(config)
    purge_engine(cfg)
    slots, blocks = POOL_CELLS[cell]
    params = _abstract(_param_shapes(cfg), one_chip, dtype=BF)
    cache = _abstract(jax.eval_shape(lambda: generate.init_cache(
        cfg, slots, T, layout="paged", block_size=16, num_blocks=blocks)),
        one_chip)

    def arr(dt, dims=(slots,)):
        return jax.ShapeDtypeStruct(dims, dt, sharding=one_chip)

    fn = engine.ENGINE.get("async", engine.StepSpec(cfg=cfg, paged=True))
    text = fn.lower(params, cache, arr(I32), arr(jnp.bool_), arr(I32),
                    arr(I32), arr(jnp.uint32, (2,)), arr(F32), arr(I32),
                    arr(F32)).compile().as_text()
    comps = _computations(text)
    conds = _branches_of(comps)
    assert len(conds) == 2, conds
    entry = next(c for c in comps if re.search(
        rf"^ENTRY %{re.escape(c)} ", text, re.M))
    (_, (greedy, sampled)), = [c for c in conds if c[0] == entry]
    (inner_in, (drawn, filtered)), = [c for c in conds if c[0] != entry]
    in_greedy, in_sampled, in_drawn, in_filtered = (
        _reached_from(comps, c) for c in (greedy, sampled, drawn, filtered))
    assert inner_in in in_sampled
    assert _sampler_work(comps, set(comps) - in_greedy - in_sampled) == []
    assert _sampler_work(comps, in_greedy) == []
    work = _sampler_work(comps, in_drawn)
    assert "bits" in work and "sort" not in work, set(work)
    work = _sampler_work(comps, in_filtered)
    assert "bits" in work and work.count("sort") == 1, set(work)
    assert len(re.findall(r"\ssort\(", text)) == 1
    scopes = telemetry.hlo_op_scopes(text)
    for branch in (greedy, drawn, filtered):
        paths = [scopes[m.group(1)] for line in comps[branch]
                 for m in [re.match(r"\s+(?:ROOT )?%([\w.\-]+) = ", line)]
                 if m and m.group(1) in scopes]
        # the nucleus' cumsum is expanded by XLA into ops named
        # ``reduce_window_sum`` and nothing else, as in the parent
        lost = [p for p in paths if p != "reduce_window_sum" and not (
            p.startswith("jit(<lambda>)/serving.async_step/")
            and "/sample/" in p)]
        assert paths and not lost, (branch, lost[:3])
        assert branch is filtered or "reduce_window_sum" not in paths


@pytest.mark.parametrize("program", ["decode", "prefill256"])
def test_latent_paged_step_holds_no_slice_and_no_whole_view(
        one_chip, no_persistent_cache, as_on_tpu, purge_engine, program):
    """The latent cell at its own size (256 slots, 20,480 blocks of 16
    rows of 640 lanes, 4 layers of 16 held experts): the decode step takes
    the paged kernel on the shared row (eight calls, a sublayer each), no
    op has a sublayer's slice of the pool as its result, the row scatters
    write in place, and the temporaries hold neither a per-slot whole
    view of a sublayer (256 x 4096 rows: 1.3 GB) nor a copy of a layer's
    experts (0.6 GB: the batched matmuls read each layer's own leaf).
    The prefill gathers one slot's view (5 MB a sublayer), nothing more."""
    import json

    from benchmarks.families import longcat_flash as fam
    from paddle_tpu.text import engine, generate, kv_pool

    with open("benchmarks/configs/longcat-flash-omni-serve.json") as f:
        config = json.load(f)
    cfg = fam.gpt_config(config)
    purge_engine(cfg)
    a = config["entry_point"]["args"]
    slots, blocks = a["max_batch"], a["num_blocks"]
    params = _abstract(_param_shapes(cfg), one_chip, dtype=BF)
    cache = _abstract(jax.eval_shape(lambda: generate.init_cache(
        cfg, slots, a["max_len"], layout="paged", block_size=16,
        num_blocks=blocks)), one_chip)
    leaf = cache[kv_pool.LATENT]
    slice_elems = blocks * 16 * 640
    assert leaf.shape == (8, blocks, 16, 640) and leaf.dtype == BF
    if program == "decode":
        tok = jax.ShapeDtypeStruct((slots,), I32, sharding=one_chip)
        fn = engine.ENGINE.get("step", engine.StepSpec(cfg=cfg, paged=True))
        args = (params, cache, tok, tok)
    else:
        scalar = jax.ShapeDtypeStruct((), I32, sharding=one_chip)
        fn = engine.ENGINE.get("paged_prefill",
                               engine.StepSpec(cfg=cfg, bucket=256))
        args = (params, cache, jax.ShapeDtypeStruct(
            (1, 256), I32, sharding=one_chip), scalar, scalar, scalar)
    compiled = fn.lower(*args).compile()
    text = compiled.as_text()
    assert _holds_pool_slices(text, slice_elems, "bf16") == []
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 8 * slice_elems * 2
    if program == "decode":
        assert _kernel_calls("paged_decode_attention", text) == 8
        assert mem.temp_size_in_bytes < 128 << 20
        # the experts' matmuls keep the program's scope on the chip's
        # compiler: decode_moe_dev_ms finds them by it, not by an op's name
        for dot, n in (("etd,edf->etf", 2), ("etf,efd->td", 1)):
            assert text.count(f"moe/moe_experts/{dot}/dot_general") \
                >= n * cfg.num_layers
    else:
        assert mem.temp_size_in_bytes < 1 << 30
    # fits the chip beside what else the process holds
    assert mem.peak_memory_in_bytes < 15.0e9


@pytest.mark.parametrize("program", ["decode", "prefill1024"])
def test_pattern_step_holds_no_slice_of_either_pool_and_no_experts_copy(
        one_chip, no_persistent_cache, as_on_tpu, purge_engine, program):
    """The layer-pattern cell at its own size (64 slots, 16,384 blocks of
    16 rows; nine mamba layers to one attention layer, 36 held experts a
    layer): the K/V leaves are ONE layer deep and the state leaves nine;
    the decode step takes the paged kernel once (the one layer that
    attends, its place among the attention layers as the ``layer``
    operand), no op has a whole K/V leaf or a copy of a layer's experts
    (679 MB) as its result, every cache leaf is donated and written where
    it is, each mamba layer's state is advanced by one ``ssm_state_update``
    kernel where it is stored, and no op's result is a layer's state (the
    parent's step held one, 268 MB, among its temporaries).  The prefill
    gathers one slot's view of the one layer and cuts the slot's state
    out of the leaf as it is stored."""
    import json

    from benchmarks.families import granite_moe_hybrid as fam
    from paddle_tpu.text import engine, generate, kv_pool

    with open("benchmarks/configs/granite-4.0-h-small-serve.json") as f:
        config = json.load(f)
    cfg = fam.gpt_config(config)
    purge_engine(cfg)
    a = config["entry_point"]["args"]
    slots, blocks = a["max_batch"], a["num_blocks"]
    params = _abstract(_param_shapes(cfg), one_chip, dtype=BF)
    cache = _abstract(jax.eval_shape(lambda: generate.init_cache(
        cfg, slots, a["max_len"], layout="paged", block_size=16,
        num_blocks=blocks)), one_chip)
    assert cache["k"].shape == (1, blocks, 16, 1024)
    assert cache["ssm"].shape == (9, slots, 128, 64, 128)
    pool_elems = blocks * 16 * 1024
    experts_elems = 36 * 4096 * 768
    layer_state = slots * 128 * 64 * 128 * 4
    if program == "decode":
        tok = jax.ShapeDtypeStruct((slots,), I32, sharding=one_chip)
        fn = engine.ENGINE.get("step", engine.StepSpec(cfg=cfg, paged=True))
        args = (params, cache, tok, tok)
    else:
        scalar = jax.ShapeDtypeStruct((), I32, sharding=one_chip)
        fn = engine.ENGINE.get("paged_prefill",
                               engine.StepSpec(cfg=cfg, bucket=1024))
        args = (params, cache, jax.ShapeDtypeStruct(
            (1, 1024), I32, sharding=one_chip), scalar, scalar, scalar)
    compiled = fn.lower(*args).compile()
    text = compiled.as_text()
    assert _holds_pool_slices(text, pool_elems, "bf16") == []
    # a layer's experts are read where they are stored: no op's result is
    # the size of one of a layer's three expert leaves, in either layout
    assert [f for f in _holds_pool_slices(text, experts_elems, "bf16")
            if f[1] in ("copy", "transpose")] == []
    mem = compiled.memory_analysis()
    held = sum(int(np.prod(cache[n].shape)) * cache[n].dtype.itemsize
               for n in ("k", "v") + kv_pool.STATE_LEAVES)
    assert mem.alias_size_in_bytes >= held
    if program == "decode":
        assert _kernel_calls("paged_decode_attention", text) == 1
        assert _kernel_calls("ssm_state_update", text) == 9
        assert _kernel_on_path("ssm_state_update", "/ssm/ssm_update/", text)
        assert _holds_pool_slices(text, layer_state // 4, "f32") == []
        assert mem.temp_size_in_bytes < 32 << 20
        # the experts' and the shared expert's matmuls keep the program's
        # scopes on the chip's compiler: the moe metrics find them by it
        for dot, n in (("etd,edf->etf", 2), ("etf,efd->td", 1)):
            assert text.count(f"moe/moe_experts/{dot}/dot_general") \
                >= n * cfg.num_layers
        assert text.count("moe/moe_shared/") >= 3 * cfg.num_layers
        assert "ssm/ssm_update/" in text and "moe_zero" not in text
    else:
        assert mem.temp_size_in_bytes < 1 << 30
        assert "ssm/ssm_scan/" in text and "moe/moe_shared/" in text
    # fits the chip beside what else the process holds
    assert mem.peak_memory_in_bytes < 15.0e9


def _train_step(cfg, mesh, accum=1):
    from paddle_tpu.optimizer import AdamW
    from paddle_tpu.text import gpt_hybrid

    opt = AdamW(learning_rate=1e-3)
    _, step_fn, _ = gpt_hybrid.build_gpt_train_step(cfg, mesh, opt,
                                                    accum=accum)
    p = _param_shapes(cfg)
    state = gpt_hybrid.GPTTrainState(
        p, jax.eval_shape(opt.init_state, p),
        jax.ShapeDtypeStruct((), I32))
    # one sequence per data shard and micro-batch
    batch = mesh.shape.get("dp", 1) * accum
    return step_fn.lower(
        state, jax.ShapeDtypeStruct((batch, T + 1), I32),
        jax.eval_shape(lambda: jax.random.PRNGKey(0)),
        jax.ShapeDtypeStruct((), F32)).compile()


def test_train_step_one_chip(topo, no_persistent_cache, as_on_tpu):
    compiled = _train_step(_gpt(2), Mesh(np.array(topo.devices[:1]),
                                         ("dp",)))
    # flash fwd/dq/dkv + fused LN fwd/bwd (two sites) + fused CE fwd/bwd
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 7
    assert compiled.memory_analysis().temp_size_in_bytes < 16e9
    assert text.startswith("HloModule jit_step_fn,")
    for kernel in ("flash_attention_fwd", "flash_attention_bwd_dq",
                   "flash_attention_bwd_dkv"):
        assert _names_a_kernel(kernel, text)


def test_train_step_dots_keeps_flash_residuals(topo, no_persistent_cache,
                                               monkeypatch):
    """The benchmark's train cell checkpoints its block under ``"dots"``
    with ``accum`` 8.  That policy keeps the flash forward's ``out`` and
    ``lse`` (ops/remat_policies), so the step holds the forward kernel
    once, not a second time inside the backward pass's recomputation."""
    from paddle_tpu.ops import _pallas

    monkeypatch.setattr(_pallas, "on_tpu", lambda: True)
    cfg = dataclasses.replace(_gpt(2), remat=True, remat_policy="dots")
    compiled = _train_step(cfg, Mesh(np.array(topo.devices[:1]), ("dp",)),
                           accum=8)
    text = compiled.as_text()
    for kernel in ("flash_attention_fwd", "flash_attention_bwd_dq",
                   "flash_attention_bwd_dkv"):
        assert _kernel_calls(kernel, text) == 1, kernel
    assert text.count("tpu_custom_call") == 3
    # 2,580,986,880 B when this was written; 2,563,167,232 B with the
    # forward recomputed: the residuals of two layers are 17.8 MB more
    assert compiled.memory_analysis().temp_size_in_bytes < 2.7e9


def _opcodes(text) -> dict:
    """{opcode: count} over a compiled module's instructions."""
    out: dict = {}
    for m in re.finditer(r"^\s+(?:ROOT )?%[\w.\-]+ = .*?\s([a-z][a-z\-]*)\(",
                         text, re.M):
        out[m.group(1)] = out.get(m.group(1), 0) + 1
    return out


@pytest.mark.parametrize("program", ["decode", "train"])
def test_scopes_change_metadata_only(topo, one_chip, no_persistent_cache,
                                     as_on_tpu, purge_engine, monkeypatch,
                                     program):
    """The named scopes on the model's parts and around every step kind
    are HLO metadata: without them the same program compiles, op for op
    and byte for byte of device memory."""
    def build():
        if program == "train":
            return _train_step(_gpt(2), Mesh(np.array(topo.devices[:1]),
                                             ("dp",)))
        cfg = _gpt(2)
        purge_engine(cfg)
        fn, args = _decode_step(cfg, "paged", None, one_chip)
        compiled = fn.lower(*args).compile()
        from paddle_tpu.text import engine

        engine.ENGINE.purge(cfg)
        return compiled

    with_scopes = build()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    without = build()
    assert "serving.step" not in without.as_text()
    assert _opcodes(with_scopes.as_text()) == _opcodes(without.as_text())
    a, b = with_scopes.memory_analysis(), without.memory_analysis()
    for field in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "alias_size_in_bytes"):
        assert getattr(a, field) == getattr(b, field), field


# --------------------------------------------------------------------------
# four chips: GSPMD cannot partition a Mosaic kernel, so each runs per shard
# (ops/_pallas.partitioned); both sharded programs must still hold kernels
# AND collectives
# --------------------------------------------------------------------------


def test_train_step_dp2_mp2(topo, no_persistent_cache, as_on_tpu):
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("dp", "mp"))
    compiled = _train_step(_gpt(2), mesh)
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 5  # CE stays on XLA under mp
    assert "all-reduce" in text
    one = _train_step(_gpt(2), Mesh(np.array(topo.devices[:1]), ("dp",)))
    # per-device argument bytes shrink: the mp-sharded weights are halved
    assert (compiled.memory_analysis().argument_size_in_bytes
            < 0.75 * one.memory_analysis().argument_size_in_bytes)


@pytest.mark.parametrize("layout", ["contiguous", "paged"])
def test_engine_decode_step_mp4(topo, no_persistent_cache, as_on_tpu,
                                purge_engine, layout):
    from paddle_tpu.text import engine, generate

    cfg = _gpt(2)
    purge_engine(cfg)
    mesh = Mesh(np.array(topo.devices), ("mp",))
    cache = jax.eval_shape(
        lambda: generate.init_cache(cfg, B, T, layout=layout))
    shard = engine._ShardCtx(mesh, cfg, _param_shapes(cfg), cache)
    fn, args = _decode_step(cfg, layout, shard, None)
    text = fn.lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    assert "all-reduce" in text
