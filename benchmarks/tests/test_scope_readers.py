"""The readers of the program's spans and scopes, without a chip: on
hand-made data, on an extract recorded from this benchmark's own v5e
traces (``data/v5e_scope_extract.json``; its ``recorded`` field says how it
was cut), and in a CPU rehearsal.  Run from the root of the repo:

    python -m pytest benchmarks/tests -q"""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import common, trace  # noqa: E402
from benchmarks.families import gpt  # noqa: E402
from benchmarks.readers import (decode_mfu, module_time, ring,  # noqa: E402
                                scope_time, scope_trace, span_median,
                                tick_host, token_gap)
from benchmarks.serve import Sample  # noqa: E402
from benchmarks.tests.test_benchmark import check_line, rehearse  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CHAT, TRAIN = "gpt1p3b-serve-chat", "gpt590m-train-seq2048"


def args_of(metric: str) -> dict:
    return common.load_json("layer_metrics", metric + ".json")["args"]


# -- op_name paths -----------------------------------------------------------


@pytest.mark.parametrize("path, part", [
    ("jit(<lambda>)/serving.async_step/attn/paged_decode_attention/"
     "pallas_call", "attn"),
    ("jit(<lambda>)/serving.async_step/vmap(lm_head)/dot_general",
     "lm_head"),
    ("jit(step_fn)/transpose(jvp(ln))/jit(_var)/mul", "ln"),
    ("jit(<lambda>)/serving.async_step/attn/kv_gather/gather", "kv_gather"),
    ("jit(<lambda>)/serving.async_step/while/body/add", "(step)"),
    (None, "(no path)"),
    ("", "(no path)"),
])
def test_part_is_the_innermost_named_scope(path, part):
    assert scope_trace.part_of(path) == part


def test_self_time_leaves_out_nested_ops():
    ops = [["while.1", "while", 0.0, 1.0], ["fusion.1", "fusion", 0.1, 0.3],
           ["attn.2", "custom-call", 0.5, 0.4], ["copy.3", "copy", 1.0, 0.2]]
    assert {n: round(s, 9) for n, _, s in scope_trace.self_times(ops)} == {
        "while.1": 0.3, "fusion.1": 0.3, "attn.2": 0.4, "copy.3": 0.2}


# -- a hand-made slice: two decode steps, a prefill between them -------------

STEP = "jit(<lambda>)/serving.async_step/"
SCOPES = [
    {"name": "serving.async_step", "module": "jit__lambda", "ops": {
        "while.1": STEP[:-1], "paged_decode_attention.6": STEP + "attn/"
        "paged_decode_attention/pallas_call",
        "fusion.7": STEP + "kv_gather/dynamic_slice",
        "fusion.8": STEP + "vmap(mlp)/dot_general",
        "sort.2": STEP + "sample/jit(sort)/sort"}},
    {"name": "serving.paged_prefill@256", "module": "jit__lambda", "ops": {
        "flash_attention_fwd.3": "jit(<lambda>)/serving.paged_prefill_256/"
        "attn/flash_attention_fwd/pallas_call",
        "fusion.7": "jit(<lambda>)/serving.paged_prefill_256/mlp/dot",
        "fusion.9": "jit(<lambda>)/serving.paged_prefill_256/ln/mul"}},
    {"name": "hybrid.train_step", "module": "jit_step_fn", "ops": {}},
]


def step_ops(t0):
    return [["while.1", "while", t0, 0.30],
            ["fusion.7", "fusion", t0 + 0.00, 0.05],
            ["paged_decode_attention.6", "custom-call", t0 + 0.05, 0.20],
            ["fusion.8", "fusion", t0 + 0.25, 0.04],
            ["copy-start.1", "copy-start", t0 + 0.30, 0.002],
            ["sort.2", "sort", t0 + 0.302, 0.008]]


EXTRACT = {
    "slice": [[10.0, 1.2]],
    "devices": [{"name": "/device:TPU:0", "modules": [
        ["jit__lambda", 10.0, 0.31], ["jit__lambda", 10.32, 0.04],
        ["jit__lambda", 10.40, 0.31], ["jit__lambda", 10.75, 0.31]],
        "ops": step_ops(10.0) + [
            ["flash_attention_fwd.3", "custom-call", 10.32, 0.02],
            ["fusion.7", "fusion", 10.34, 0.015],
            ["fusion.9", "fusion", 10.355, 0.005]] + step_ops(10.40)
        + step_ops(10.75)}],
    "spans": [["serving.tick", 10.3, 0.45, 5, 0],
              ["serving.tick.admit", 10.305, 0.06, 6, 5],
              ["serving.tick.wait", 10.37, 0.37, 7, 5],
              ["serving.tick", 10.75, 0.4, 8, 0]],
}


def test_reduce_names_runs_by_their_scope_not_their_module():
    red = scope_trace.reduce(EXTRACT, SCOPES)
    # the last run of a device is cut short by the profiler's stop
    assert [(r["executable"], r["kind"]) for r in red["runs"]] == [
        ("serving.async_step", "serving.async_step"),
        ("serving.paged_prefill@256", "serving.paged_prefill_256"),
        ("serving.async_step", "serving.async_step")]
    parts = scope_trace.by_part([r for r in red["runs"]
                                 if r["kind"] == "serving.async_step"])
    assert {k: round(v, 9) for k, v in parts.items()} == {
        "(step)": 0.01, "kv_gather": 0.05, "attn": 0.20, "mlp": 0.04,
        "(no path)": 0.002, "sample": 0.008}
    assert [(round(a, 3), round(b, 3)) for a, b in red["gaps"]] == [
        (10.31, 10.32), (10.36, 10.4), (10.71, 10.75), (11.06, 11.2)]
    idle = scope_trace.idle_by_span(red, EXTRACT["spans"])
    # the innermost span over each gap, not the tick that holds it
    assert {k: round(v, 3) for k, v in idle.items()} == {
        "serving.tick.admit": 0.01, "serving.tick.wait": 0.08,
        "serving.tick": 0.14}


def test_decode_metrics_are_disjoint_and_cover_the_step(monkeypatch):
    red = scope_trace.reduce(EXTRACT, SCOPES)
    monkeypatch.setattr(scope_trace, "load", lambda run: red)
    got = {m: scope_time.read({}, args_of(m)) for m in (
        "decode_attn_dev_ms", "decode_kv_relayout_dev_ms",
        "decode_dense_dev_ms")}
    assert {k: round(v, 6) for k, v in got.items()} == {
        "decode_attn_dev_ms": 200.0, "decode_kv_relayout_dev_ms": 50.0,
        "decode_dense_dev_ms": 60.0}
    # the step's busy time: its 310 ms all under some op here
    assert sum(got.values()) == pytest.approx(310.0)


def test_zero_where_the_step_ran_and_none_where_nothing_can_be_read(
        monkeypatch):
    red = scope_trace.reduce(EXTRACT, SCOPES)
    monkeypatch.setattr(scope_trace, "load", lambda run: red)
    # the decode step ran and holds no flash kernel: 0.0, not nothing
    zero = dict(args_of("decode_attn_dev_ms"), kernel="^flash_attention_fwd$")
    assert scope_time.read({}, zero) == 0.0
    # no run of the train step in this slice: nothing to read
    assert scope_time.read({}, args_of("train_attn_fwd_dev_ms")) is None
    # no device trace (the CPU rehearsal), or a program that names no paths
    monkeypatch.setattr(scope_trace, "load", lambda run: None)
    assert scope_time.read({}, args_of("decode_attn_dev_ms")) is None
    unnamed = scope_trace.reduce(EXTRACT, [])
    monkeypatch.setattr(scope_trace, "load", lambda run: unnamed)
    assert scope_time.read({}, args_of("decode_attn_dev_ms")) is None


def test_train_metrics_tell_forward_recompute_and_backward_apart(
        monkeypatch):
    fwd = "jit(step_fn)/jvp(attn)/flash_attention_fwd/pallas_call"
    again = ("jit(step_fn)/transpose(jvp(checkpoint))/rematted_computation/"
             "attn/flash_attention_fwd/pallas_call")
    bwd = "jit(step_fn)/transpose(jvp(checkpoint))/attn/flash_attention_bwd_"
    scopes = [{"name": "hybrid.train_step", "module": "jit_step_fn", "ops": {
        "flash_attention_fwd.4": fwd, "flash_attention_fwd.5": again,
        "flash_attention_bwd_dq.2": bwd + "dq/pallas_call",
        "flash_attention_bwd_dkv.2": bwd + "dkv/pallas_call",
        "fusion.1": "jit(step_fn)/jvp(ln)/mul",
        "fusion.2": "jit(step_fn)/transpose(jvp(loss))/sub",
        "fusion.3": "jit(step_fn)/optimizer/mul"}}]
    ops = [["flash_attention_fwd.4", "custom-call", 0.0, 0.14],
           ["flash_attention_fwd.5", "custom-call", 0.2, 0.13],
           ["flash_attention_bwd_dq.2", "custom-call", 0.4, 0.1],
           ["flash_attention_bwd_dkv.2", "custom-call", 0.5, 0.098],
           ["fusion.1", "fusion", 0.6, 0.05], ["fusion.2", "fusion", 0.7,
                                               0.029],
           ["fusion.3", "fusion", 0.8, 0.1]]
    ex = {"slice": [[0.0, 3.0]], "spans": [], "devices": [{
        "name": "/device:TPU:0",
        "modules": [["jit_step_fn", 0.0, 0.98], ["jit_step_fn", 1.0, 0.98],
                    ["jit_step_fn", 2.0, 0.98]],
        "ops": ops + [[n, c, a + 1.0, d] for n, c, a, d in ops]}]}
    red = scope_trace.reduce(ex, scopes)
    monkeypatch.setattr(scope_trace, "load", lambda run: red)
    got = {m: round(scope_time.read({}, args_of(m)), 6) for m in (
        "train_attn_fwd_dev_ms", "train_attn_recompute_dev_ms",
        "train_attn_bwd_dev_ms", "train_norm_ce_dev_ms")}
    assert got == {"train_attn_fwd_dev_ms": 140.0,
                   "train_attn_recompute_dev_ms": 130.0,
                   "train_attn_bwd_dev_ms": 198.0,
                   "train_norm_ce_dev_ms": 79.0}


# -- the recorded extract: this PR's own v5e traces --------------------------


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(DATA, "v5e_scope_extract.json"),
              encoding="utf-8") as f:
        return json.load(f)


def test_recorded_decode_step_is_covered_by_its_three_parts(recorded,
                                                            monkeypatch):
    chat = recorded["chat"]
    red = scope_trace.reduce(chat["extract"], chat["scopes"])
    monkeypatch.setattr(scope_trace, "load", lambda run: red)
    steps = scope_time.step_runs(red, args_of("decode_attn_dev_ms"))
    assert len(steps) == chat["decode_steps"]
    assert {r["executable"] for r in steps} == {"serving.async_step"}
    got = {m: scope_time.read({}, args_of(m)) for m in (
        "decode_attn_dev_ms", "decode_kv_relayout_dev_ms",
        "decode_dense_dev_ms")}
    assert all(v > 0 for v in got.values())
    # disjoint and covering: the sum is the step's time less its idle share
    step_ms = common.median([r["s"] for r in steps]) * 1e3
    assert sum(got.values()) == pytest.approx(step_ms, rel=0.02)
    for name, want in chat["metrics"].items():
        assert got[name] == pytest.approx(want, rel=1e-6), name


def _module_reader_s_view(ex: dict) -> dict:
    """A ``scope_trace`` extract as ``trace.reduce`` reads one."""
    lo, dur = ex["slice"][0]
    return trace.reduce({"host": [[trace.SLICE, lo, dur]], "devices": [
        {"name": d["name"], "lines": {
            trace.MODULE_LINE: d["modules"],
            trace.OP_LINE: [[f"{n} {code}", a, s]
                            for n, code, a, s in d["ops"]]}}
        for d in ex["devices"]]})


def test_recorded_decode_step_by_scope_is_the_module_s_time(recorded,
                                                            monkeypatch):
    """``decode_step_dev_ms`` finds the step by its ``serving.<kind>``
    scope: within 0.1% of the module's run time on the same runs (how it
    was found until PR 28), and the same whatever the kernel is called,
    where the module-and-kernel reader finds nothing."""
    by_kernel = {"module": "^jit__lambda$",
                 "with_kernel": "^paged_decode_attention"}
    got = {}
    for kernel in ("paged_decode_attention", "walked_blocks_attn"):
        # as a program with another kernel name would have written it: in
        # the ops' names and in the op_name paths
        chat = json.loads(json.dumps(recorded["chat"]).replace(
            "paged_decode_attention", kernel))
        assert kernel in json.dumps(chat["scopes"])
        red = scope_trace.reduce(chat["extract"], chat["scopes"])
        monkeypatch.setattr(scope_trace, "load", lambda run: red)
        got[kernel] = (
            scope_time.read({}, args_of("decode_step_dev_ms")),
            sum(scope_time.read({}, args_of(m)) for m in (
                "decode_attn_dev_ms", "decode_kv_relayout_dev_ms",
                "decode_dense_dev_ms")),
            module_time.read(
                {"trace": _module_reader_s_view(chat["extract"])}, by_kernel))
    by_scope, parts, by_module = got["paged_decode_attention"]
    assert by_scope == pytest.approx(parts, rel=1e-9)
    assert by_scope == pytest.approx(by_module, rel=1e-3)
    assert by_scope <= by_module            # self times leave the gaps out
    assert got["walked_blocks_attn"] == (by_scope, parts, None)
    # no accepted metric names the module or the kernel any more
    for m in common.manifest()["per_layer"]:
        spec = json.dumps(common.load_json("layer_metrics",
                                            m["name"] + ".json"))
        if m["moves"] == "tpot_p50_ms":
            assert "paged_decode_attention" not in spec
            assert "jit__lambda" not in spec


def test_recorded_train_step_names_its_flash_kernels(recorded, monkeypatch):
    train = recorded["train"]
    red = scope_trace.reduce(train["extract"], train["scopes"])
    monkeypatch.setattr(scope_trace, "load", lambda run: red)
    for name, want in train["metrics"].items():
        got = scope_time.read({}, args_of(name))
        assert got == pytest.approx(want, rel=1e-6), name
    kernels = {n.rsplit(".", 1)[0] for r in red["runs"]
               for n, code, _, _ in r["ops"] if code == "custom-call"}
    assert {"flash_attention_fwd", "flash_attention_bwd_dq",
            "flash_attention_bwd_dkv"} <= kernels


# -- the ring's readers ------------------------------------------------------


def emit(t, rids, n=None):
    return {"name": "serving.emit", "t0": t, "t1": t, "id": 0,
            "args": {"rids": rids, "n": n or [1] * len(rids),
                     "t": [t] * len(rids)}}


def test_token_gaps_are_per_request_and_inside_the_window(monkeypatch):
    from paddle_tpu import telemetry

    events = [emit(0.9, [1]),                    # before the window
              emit(1.0, [1, 2]), emit(1.3, [1, 2]), emit(1.7, [1, 2, 3]),
              emit(2.0, [2, 3], n=[2, 1]),       # a block of two for rid 2
              emit(9.0, [3])]                    # after the window
    monkeypatch.setattr(telemetry, "events", lambda: events, raising=False)
    run = {"stats_window": (1.0, 5.0)}
    gaps = sorted(round(g, 6) for g in token_gap.gaps_ms(
        ring.spans(run, "serving.emit")))
    # rid 1: 300, 400; rid 2: 300, 400, 300, 0 (its block); rid 3: 300
    assert gaps == [0.0, 300.0, 300.0, 300.0, 300.0, 400.0, 400.0]
    assert token_gap.read(run, {"percentile": 99}) == pytest.approx(400.0)
    assert token_gap.read({"stats_window": (20.0, 30.0)},
                          {"percentile": 99}) is None


def test_tick_host_time_is_the_tick_less_its_wait(monkeypatch):
    from paddle_tpu import telemetry

    def tick(i, t0, dur, wait, kind="async_step"):
        out = [{"name": "serving.tick", "t0": t0, "t1": t0 + dur, "id": i,
                "args": {"kind": kind, "slots": 2, "queue": 0}}]
        if wait:
            out.append({"name": "serving.tick.wait", "t0": t0 + 0.001,
                        "t1": t0 + 0.001 + wait, "id": 100 + i,
                        "parent": i})
        return out

    events = (tick(1, 1.0, 0.310, 0.306) + tick(2, 1.4, 0.312, 0.306)
              + tick(3, 1.8, 0.350, 0.306) + tick(4, 2.2, 0.0002, 0,
                                                  kind="idle"))
    monkeypatch.setattr(telemetry, "events", lambda: events, raising=False)
    run = {"stats_window": (0.0, 5.0)}
    assert tick_host.read(run, {}) == pytest.approx(6.0)       # of 4, 6, 44
    events.append({"name": "serving.prefill", "t0": 1.0, "t1": 1.35,
                   "id": 9, "args": {"rid": 1}})
    assert span_median.read(run, {"span": "serving.prefill"}) \
        == pytest.approx(350.0)
    assert span_median.read(run, {"span": "serving.nothing"}) is None


def test_an_older_program_reads_nothing(monkeypatch):
    from paddle_tpu import telemetry

    monkeypatch.delattr(telemetry, "events")
    run = {"stats_window": (0.0, 5.0)}
    assert token_gap.read(run, {"percentile": 99}) is None
    assert tick_host.read(run, {}) is None
    assert span_median.read(run, {"span": "serving.prefill"}) is None


# -- the whole step's share of the peak, on the driver's own stamps ----------

MFU_SIZES = {"D": 2048, "L": 24, "H": 16, "F": 8192, "T": 2048, "V": 50304,
             "V_published": 50257}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def mfu_run(samples, joined=None, window=(10.0, 20.0)) -> dict:
    """Two requests hold a slot from t = 1 on, 100 and 300 prompt tokens,
    a token every 0.25 s."""
    if joined is None:
        joined = [{"rid": i, "t_due": 0.0, "t_first": 1.0, "t_retire": 101.0,
                   "tokens": 401, "prompt_len": p, "out_len": 401}
                  for i, p in enumerate((100, 300))]
    return {"peaks": PEAKS, "joined": joined, "samples": samples,
            "stats_window": window, "sizes": MFU_SIZES, "family": gpt}


def ticks(stamps, held=2, first_turn=1):
    return [Sample(t, 0, held, first_turn + i, held)
            for i, t in enumerate(stamps)]


def test_decode_step_mfu_is_the_least_time_over_the_tick_period():
    stamps = [10.0 + 0.25 * i for i in range(21)]        # 20 periods
    run = mfu_run(ticks(stamps))
    # each request has its prompt + 1 + (t - 1) / 0.25 tokens at t; the
    # ticks that count are those after the window's first: 10.25 .. 15.0
    counted = stamps[1:]
    kv = sum((100 + 300) + 2 * (1 + (t - 1.0) / 0.25) for t in counted) \
        / len(counted)
    weights = 2 * (1310982144 + 2048 * 2048)
    t_bytes = (weights + 2.0 * 24 * 2048 * 2 * kv) / 819e9
    t_flops = (2.0 * 1310982144 * 2 + 4.0 * 24 * 2048 * kv) / 197e12
    assert t_bytes > t_flops                              # memory-bound
    args = args_of("decode_step_mfu")
    assert decode_mfu.read(run, args) == pytest.approx(
        100.0 * t_bytes / 0.25, rel=1e-9)
    # the period is a median: one stalled tick moves nothing
    slow = ticks(stamps[:10] + [t + 0.4 for t in stamps[10:]])
    assert decode_mfu.read(mfu_run(slow), args) == pytest.approx(
        100.0 * t_bytes / 0.25, rel=2e-2)


def test_decode_step_mfu_counts_only_ticks_that_generated_tokens():
    args = args_of("decode_step_mfu")
    stamps = [10.0 + 0.25 * i for i in range(9)]
    base = decode_mfu.read(mfu_run(ticks(stamps)), args)
    # a sleep between two ticks (the loop turned without a tick): the gap
    # over it is no step's time
    gap = ticks(stamps[:4]) + ticks([t + 5.0 for t in stamps[4:]],
                                    first_turn=30)
    assert [round(p, 9) for _, p in decode_mfu.tick_periods(
        gap, 10.0, 20.0)] == [0.25] * 7
    # ticks in which no request held a slot (admission waiting) are left out
    idle = ticks(stamps, held=0)
    assert decode_mfu.tick_periods(idle, 10.0, 20.0) == []
    assert decode_mfu.read(mfu_run(idle), args) is None
    # ticks outside the statistics window, no peaks (a rehearsal), no
    # requests joined: nothing to read, so nothing in the line
    assert decode_mfu.read(mfu_run(ticks(stamps), window=(30.0, 40.0)),
                           args) is None
    assert decode_mfu.read(dict(mfu_run(ticks(stamps)), peaks=None),
                           args) is None
    assert decode_mfu.read(mfu_run(ticks(stamps), joined=[]), args) is None
    assert 0.0 < base < 100.0


def test_decode_step_mfu_cannot_pass_100_for_a_tick_no_shorter_than_the_least(
        ):
    args = args_of("decode_step_mfu")
    cost = gpt.decode_step_cost(MFU_SIZES, 2, 400 + 2 * 40)
    least = cost["bytes"] / PEAKS["hbm_bytes_per_s"]
    for stretch in (1.0, 1.5, 50.0):
        # every tick takes the least time its own live rows allow, or more
        stamps = [10.0]
        for _ in range(12):
            stamps.append(stamps[-1] + stretch * least * 1.01)
        got = decode_mfu.read(mfu_run(ticks(stamps)), args)
        assert 0.0 < got <= 100.0
        assert got == pytest.approx(100.0 / stretch, rel=0.03)


# -- a rehearsal with the new entries present --------------------------------


@pytest.mark.parametrize("workload", [CHAT, TRAIN])
def test_traced_rehearsal_reads_the_ring_and_no_device_metric(workload):
    line, out = rehearse(ROOT, workload, 1)
    cell = common.cell(workload)
    got = check_line(line, [m["name"] for m in cell["per_layer"]])
    host = {"token_gap_p99_ms", "tick_host_ms", "admit_prefill_ms"}
    if workload == CHAT:
        assert host <= got, out[-1500:]
        assert "[token gaps] n=" in out and "[ticks] n=" in out
    # no device plane in a CPU trace: a device metric reads None, never a
    # CPU number under its name
    device = {m["name"] for m in cell["per_layer"]
              if m["name"].endswith("_dev_ms")}
    assert not device & got
