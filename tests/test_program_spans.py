"""The program's spans and scopes (telemetry.span, DecodeServer's tick
phases and token stamps, jax.named_scope on the model's parts).

No timing assertions: only structure.  Span ids, parents and ``rid`` nest;
a span is in a ``jax.profiler`` trace under its name with its ``span_id``;
with telemetry off a span is a no-op and served tokens are bit-identical;
every tick is one ``serving.tick`` whose phases lie inside it and do not
overlap; the ``serving.emit`` records rebuild every request's token times;
a retrace records a ``compile`` span; the lowered decode and train steps
carry every scope name."""
import glob
import os
import re
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu import optimizer, profiler, telemetry
from paddle_tpu.text import engine, gpt, gpt_hybrid, serving

PHASES = ("admit", "feed", "dispatch", "wait", "book")


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    telemetry.reset()
    yield
    telemetry.reset()


@pytest.fixture(scope="module")
def tiny_model():
    cfg = gpt.GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                        num_heads=2, max_seq_len=32, dtype=jnp.float32)
    return cfg, gpt.init_params(cfg, jax.random.PRNGKey(0))


SERVERS = {
    "sync-slab": dict(layout="contiguous", async_dispatch=False),
    "async-slab": dict(layout="contiguous", async_dispatch=True),
    "sync-paged": dict(layout="paged", num_blocks=12, async_dispatch=False),
    "async-paged": dict(layout="paged", num_blocks=12, async_dispatch=True),
}


def serve(cfg, params, block=None, n_req=4, **kw):
    """Four requests on two slots (so two wait in the queue and are
    admitted inside a tick), served to the end."""
    srv = serving.DecodeServer(params, cfg, max_batch=2, max_len=32, **kw)
    prompts = np.random.default_rng(0).integers(1, 60, (n_req, 5))
    rids = [srv.submit(prompts[i], max_new_tokens=3 + 2 * i)
            for i in range(n_req)]
    while srv.pending():
        srv.tick_block(block) if block else srv.tick()
    out = {r: srv.result(r) for r in rids}
    srv.close()
    return out


def spans(name=None, prefix=None):
    return [e for e in telemetry.events() if "t0" in e
            and (name is None or e["name"] == name)
            and (prefix is None or e["name"].startswith(prefix))]


# -- the primitive -----------------------------------------------------------


def test_ids_parents_and_rid_nest():
    with telemetry.span("outer", rid=7) as outer:
        with telemetry.span("inner") as inner:
            telemetry.event("done", 0.0, 1.0, n=2)
        with telemetry.span("other", rid=9):
            pass
    by = {e["name"]: e for e in spans()}
    assert len({e["id"] for e in by.values()}) == 4
    assert "parent" not in by["outer"]
    assert by["inner"]["parent"] == outer.id
    assert by["done"]["parent"] == inner.id
    assert by["other"]["parent"] == outer.id
    # rid is inherited from the enclosing span unless a span gives its own
    assert by["inner"]["args"]["rid"] == 7
    assert by["done"]["args"] == {"n": 2, "rid": 7}
    assert by["other"]["args"]["rid"] == 9


def test_args_may_be_filled_until_the_span_closes():
    with telemetry.span("serving.tick") as sp:
        sp.args["kind"] = "async_step"
    (e,) = spans("serving.tick")
    assert e["args"] == {"kind": "async_step"} and e["t1"] >= e["t0"]


def test_the_open_span_is_per_thread():
    def work():
        with telemetry.span("in_thread"):
            pass

    with telemetry.span("main"):
        t = threading.Thread(target=work)
        t.start()
        t.join()
    assert "parent" not in spans("in_thread")[0]
    with telemetry.span("after"):       # and the stack unwound
        pass
    assert "parent" not in spans("after")[0]


def test_events_are_raw_seconds_and_chrome_events_carry_the_edges():
    telemetry.event("serving.request", 10.0, 12.5, tid=3, rid=1, tokens=4)
    (raw,) = telemetry.events()
    assert (raw["t0"], raw["t1"], raw["tid"]) == (10.0, 12.5, 3)
    (x,) = [e for e in telemetry.chrome_events() if e.get("ph") == "X"]
    assert x["ts"] == 10.0 * 1e6 and x["dur"] == 2.5 * 1e6
    assert x["args"]["rid"] == 1 and x["args"]["span_id"] == raw["id"]


def test_span_is_in_the_profilers_trace_with_its_span_id(tmp_path):
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    with telemetry.span("serving.tick", kind="step") as outer:
        with telemetry.span("serving.tick.wait") as inner:
            jnp.ones(8).block_until_ready()
    with profiler.RecordEvent("host_work"):
        pass
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    found = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name in ("serving.tick", "serving.tick.wait",
                              "host_work"):
                    found[e.name] = dict(e.stats)
    assert found["serving.tick"]["span_id"] == outer.id
    assert found["serving.tick"]["kind"] == "step"
    assert found["serving.tick.wait"]["span_id"] == inner.id
    assert found["serving.tick.wait"]["parent"] == outer.id
    assert "host_work" in found          # RecordEvent, the same helper


def test_one_place_constructs_a_trace_annotation():
    import ast

    root = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "paddle_tpu")
    sites = []
    for path in glob.glob(os.path.join(root, "**", "*.py"), recursive=True):
        with open(path, encoding="utf-8") as f:
            tree = ast.parse(f.read())
        sites += [(os.path.relpath(path, root), node.lineno)
                  for node in ast.walk(tree)
                  if isinstance(node, ast.Attribute)
                  and node.attr == "TraceAnnotation"]
    assert [s[0] for s in sites] == ["telemetry.py"], sites


def test_off_is_a_noop(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_TELEMETRY", "0")
    with telemetry.span("serving.tick", rid=1) as sp:
        sp.args["kind"] = "x"
    assert sp is telemetry.NO_SPAN and telemetry.events() == []


@pytest.mark.parametrize("kind", ["sync-slab", "async-paged"])
def test_tokens_are_bit_identical_with_telemetry_off(tiny_model, kind,
                                                     monkeypatch):
    cfg, params = tiny_model
    on = serve(cfg, params, **SERVERS[kind])
    assert spans("serving.tick")
    telemetry.reset()
    monkeypatch.setenv("PADDLE_TPU_TELEMETRY", "0")
    off = serve(cfg, params, **SERVERS[kind])
    assert off == on and telemetry.events() == []


# -- tick phases and token stamps --------------------------------------------


@pytest.mark.parametrize("block", [None, 3], ids=["tick", "tick_block"])
@pytest.mark.parametrize("kind", sorted(SERVERS))
def test_every_tick_is_one_span_with_disjoint_phases(tiny_model, kind,
                                                     block):
    cfg, params = tiny_model
    serve(cfg, params, block=block, **SERVERS[kind])
    ticks = {e["id"]: e for e in spans("serving.tick")}
    assert ticks and all("parent" not in t for t in ticks.values())
    seen = set()
    for tick in ticks.values():
        kids = sorted((e for e in spans(prefix="serving.tick.")
                       if e.get("parent") == tick["id"]),
                      key=lambda e: e["t0"])
        seen |= {k["name"].rsplit(".", 1)[1] for k in kids}
        assert {k["name"] for k in kids} <= {"serving.tick." + p
                                             for p in PHASES}
        edges = [tick["t0"]] + [t for k in kids
                                for t in (k["t0"], k["t1"])] + [tick["t1"]]
        assert edges == sorted(edges), (tick, kids)
        args = tick["args"]
        assert {"kind", "slots", "queue"} <= set(args)
        if any(k["name"].endswith(".dispatch") for k in kids):
            assert args["kind"] not in ("idle", "admit_only", "fetch_only")
    # no phase span outside a tick, and the run used every phase
    assert all(e.get("parent") in ticks
               for e in spans(prefix="serving.tick."))
    assert seen == set(PHASES)
    # ticks follow one another: none inside another
    order = sorted(ticks.values(), key=lambda t: t["t0"])
    assert all(a["t1"] <= b["t0"] for a, b in zip(order, order[1:]))


def test_a_tick_of_an_idle_server_records_nothing(tiny_model):
    cfg, params = tiny_model
    srv = serving.DecodeServer(params, cfg, max_batch=2, max_len=32)
    try:
        for _ in range(3):
            srv.tick()
            srv.tick_block(2)
    finally:
        srv.close()
    assert telemetry.events() == []


@pytest.mark.parametrize("kind", sorted(SERVERS))
def test_prefill_and_request_descend_from_where_they_ended(tiny_model, kind):
    cfg, params = tiny_model
    out = serve(cfg, params, **SERVERS[kind])
    by_id = {e["id"]: e for e in spans()}

    def root(e):
        while "parent" in e:
            e = by_id[e["parent"]]
        return e["name"]

    prefills = spans("serving.prefill")
    assert sorted(e["args"]["rid"] for e in prefills) == sorted(out)
    # two were admitted inside submit(), two from the queue inside a tick
    assert sorted(root(e) for e in prefills) == [
        "serving.admit", "serving.admit", "serving.tick", "serving.tick"]
    assert all(by_id[e["parent"]]["name"].endswith("admit")
               and e["args"]["kind"] for e in prefills)
    requests = spans("serving.request")
    assert sorted(e["args"]["rid"] for e in requests) == sorted(out)
    assert {root(e) for e in requests} == {"serving.tick"}


@pytest.mark.parametrize("block", [None, 3], ids=["tick", "tick_block"])
@pytest.mark.parametrize("kind", sorted(SERVERS))
def test_emit_records_rebuild_every_token_time(tiny_model, kind, block):
    cfg, params = tiny_model
    out = serve(cfg, params, block=block, **SERVERS[kind])
    emits = spans("serving.emit")
    stamps: dict = {}
    for e in emits:
        a = e["args"]
        assert len(a["rids"]) == len(a["n"]) == len(a["t"])
        assert e["t0"] == min(a["t"]) and e["t1"] == max(a["t"])
        for rid, n, t in zip(a["rids"], a["n"], a["t"]):
            stamps.setdefault(rid, []).extend([t] * n)
    total = telemetry.snapshot()["counters"]["serving.tokens_generated"]
    assert sum(len(v) for v in stamps.values()) == total
    for rid, toks in out.items():
        assert len(stamps[rid]) == len(toks)
        assert stamps[rid] == sorted(stamps[rid])
    # one record a tick at most, the prefill's first token in it
    per_parent: dict = {}
    for e in emits:
        per_parent[e["parent"]] = per_parent.get(e["parent"], 0) + 1
    ticks = {e["id"] for e in spans("serving.tick")}
    assert all(n == 1 for p, n in per_parent.items() if p in ticks)
    first = {e["args"]["rid"]: e["t1"] for e in spans("serving.prefill")}
    assert all(stamps[rid][0] == first[rid] for rid in out)


def test_gap_histograms_are_fed_from_the_stamps(tiny_model):
    cfg, params = tiny_model
    out = serve(cfg, params, **SERVERS["async-paged"])
    h = telemetry.snapshot()["histograms"]
    after_first = sum(len(t) - 1 for t in out.values())
    assert h["serving.tpot_ms"]["count"] == after_first
    assert h["serving.decode_gap_ms"]["count"] == after_first
    assert "serving.tick_ms" not in h


# -- compiles ----------------------------------------------------------------


def test_a_retrace_records_a_compile_span():
    fn = telemetry.instrument_compile(
        "t.step", ("t.step",), None, jax.jit(lambda x: x * 2))
    with telemetry.span("serving.tick.dispatch") as sp:
        fn(jnp.ones(4, jnp.float32))
    fn(jnp.ones(4, jnp.float32))                 # cached: no record
    fn(jnp.ones(4, jnp.int32))                   # a new type: a retrace
    first, again = spans("compile")
    assert first["args"]["fn"] == "t.step" and "retrace" not in first["args"]
    assert first["parent"] == sp.id and first["args"]["seconds"] >= 0
    assert again["args"]["retrace"] is True and "parent" not in again
    snap = telemetry.snapshot()
    assert [c["name"] for c in snap["compiles"]] == ["t.step", "t.step"]
    assert snap["counters"]["compile.count"] == 2


def test_executable_scopes_name_every_op_of_a_served_step(tiny_model):
    cfg, params = tiny_model
    serve(cfg, params, **SERVERS["async-paged"])
    by_name = {s["name"]: s for s in telemetry.executable_scopes()}
    step = by_name["serving.async_step"]
    assert step["module"] == "jit__lambda"
    comps = {re.sub(r"^(?:\w+\()+|\)+$", "", c)
             for path in step["ops"].values() for c in path.split("/")}
    assert {"serving.async_step", "attn", "kv_gather", "ln", "mlp",
            "sample"} <= comps
    # a prefill bucket's ``@`` is ``_`` in its scope (XLA cuts at ``@``)
    prefill = next(s for n, s in by_name.items()
                   if n.startswith("serving.paged_prefill@"))
    assert any(c.startswith("serving.paged_prefill_")
               for path in prefill["ops"].values() for c in path.split("/"))
    assert telemetry.executable_scopes(since=float("inf")) == []


def test_hlo_op_scopes_reads_metadata_and_called_computations():
    text = """HloModule jit__lambda, is_scheduled=true

%fused_computation.1 (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %multiply.1 = f32[4]{0} multiply(%p, %p), metadata={op_name="jit(<lambda>)/serving.step/mlp/mul" stack_frame_id=3}
}

ENTRY %main.3 (x: f32[4]) -> f32[4] {
  %x = f32[4]{0} parameter(0)
  %fusion.1 = f32[4]{0} fusion(%x), kind=kLoop, calls=%fused_computation.1
  %copy.1 = f32[4]{0} copy(%fusion.1)
  ROOT %attn.2 = f32[4]{0} add(%copy.1, %x), metadata={op_name="jit(<lambda>)/serving.step/attn/add"}
}
"""
    assert telemetry.hlo_op_scopes(text) == {
        "multiply.1": "jit(<lambda>)/serving.step/mlp/mul",
        "fusion.1": "jit(<lambda>)/serving.step/mlp/mul",
        "attn.2": "jit(<lambda>)/serving.step/attn/add"}


# -- scopes on the device side -----------------------------------------------


def scope_names(lowered) -> set:
    """Every name in a lowering's debug locations, without the
    transformations jax wraps around it (``vmap(lm_head)``,
    ``transpose(jvp(ln))``)."""
    text = lowered.as_text(debug_info=True)
    return {re.sub(r"^(?:\w+\()+|\)+$", "", c)
            for path in re.findall(r'loc\("([^"]+)"', text)
            for c in path.split("/")}


@pytest.mark.parametrize("layout", ["contiguous", "paged"])
def test_lowered_decode_step_carries_every_scope(tiny_model, layout):
    cfg, params = tiny_model
    kw = dict(num_blocks=12) if layout == "paged" else {}
    srv = serving.DecodeServer(params, cfg, max_batch=2, max_len=32,
                               layout=layout, async_dispatch=True, **kw)
    try:
        fn = serving._get_async_step_fn(cfg, layout == "paged", None)
        B = srv.max_batch
        i32 = lambda: jnp.zeros((B,), jnp.int32)  # noqa: E731
        lowered = fn.lower(
            params, srv.cache, i32(), jnp.zeros((B,), bool), i32(), i32(),
            jax.random.PRNGKey(0), jnp.zeros((B,), jnp.float32), i32(),
            jnp.ones((B,), jnp.float32))
    finally:
        srv.close()
    names = scope_names(lowered)
    want = {"serving.async_step", "embed", "ln", "attn", "mlp", "lm_head",
            "sample"}
    if layout == "paged":
        want.add("kv_gather")
    assert want <= names, want - names
    # the XLA module keeps its name: scopes are metadata
    assert re.search(r"module @jit__lambda\b", lowered.as_text())


def test_step_scope_name_has_no_at_sign(tiny_model):
    cfg, _ = tiny_model
    fn = serving._get_block_fn(cfg, 4, False, None)
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    from paddle_tpu.text import generate

    cache = generate.init_cache(cfg, 2, 32)
    lowered = fn.lower(params, cache, jnp.zeros((2,), jnp.int32),
                       jnp.zeros((2,), jnp.int32))
    names = scope_names(lowered)
    assert "serving.block_4" in names and "serving.block@4" not in names
    engine.ENGINE.purge(cfg)


def test_lowered_train_step_carries_every_scope():
    from jax.sharding import Mesh

    cfg = gpt.GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                        num_heads=2, max_seq_len=16, remat=True,
                        remat_policy="dots")
    mesh = Mesh(np.array(jax.devices()[:1]), ("dp",))
    init_fn, step_fn, _ = gpt_hybrid.build_gpt_train_step(
        cfg, mesh, optimizer.AdamW(learning_rate=1e-3), accum=2)
    state = init_fn(0)
    lowered = step_fn.lower(state, jnp.zeros((2, 16), jnp.int32),
                            jax.random.PRNGKey(0), jnp.float32(1e-3))
    names = scope_names(lowered)
    want = {"embed", "ln", "attn", "mlp", "lm_head", "loss", "optimizer",
            "grad_accum"}
    assert want <= names, want - names
    assert re.search(r"module @jit_step_fn\b", lowered.as_text())
