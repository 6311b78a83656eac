"""Rehearsal of the benchmark on the CPU.  Run from the root of the repo:

    python -m pytest benchmarks/tests -q

Nothing here measures anything: ``run.py --rehearse`` proves the control
flow at a tiny size and prints its numbers under ``cpu_rehearsal.*``."""
import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import common, requests, trace  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MANIFEST = common.manifest()


def rehearse(root, workload, trace_flag, seconds=3):
    """The last line of one ``run.py --rehearse`` in ``root``, parsed."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("BENCH_RUN", None)
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", workload,
         "--seed", str(2 ** 31 + 11), "--seconds", str(seconds), "--trace",
         str(trace_flag), "--rehearse"],
        cwd=root, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stdout


def check_line(line, names):
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["correct"] is True
    assert isinstance(line["attempted"], int) and line["attempted"] > 0
    assert line["failed"] == 0
    dev = line["device"]
    assert dev["platform"] == "cpu" and dev["count"] == 1
    assert isinstance(dev["memory_peak_bytes"], int)
    # a rehearsal never prints under a device metric's name
    assert all(k.startswith("cpu_rehearsal.") for k in line["metrics"])
    got = {k[len("cpu_rehearsal."):] for k in line["metrics"]}
    assert got <= set(names)
    for m in line["metrics"].values():
        assert isinstance(m["value"], float) and isinstance(m["unit"], str)
    return got


@pytest.mark.parametrize("trace_flag", [0, 1])
@pytest.mark.parametrize("workload",
                         [w["name"] for w in MANIFEST["workloads"]])
def test_rehearse_end_to_end(workload, trace_flag):
    """Every cell (so every generator kind) end to end on the CPU: the
    last line's keys and types, the cell's own metrics and no others."""
    line, out = rehearse(ROOT, workload, trace_flag)
    cell = common.cell(workload)
    want = cell["per_layer"] if trace_flag else cell["end_to_end"]
    got = check_line(line, [m["name"] for m in want])
    if trace_flag:
        assert {"busy_s", "window_s"} <= set(line["device"])
        assert line["device"]["window_s"] > 0
        assert any(g.startswith("compiles_in_window") for g in got)
        assert all(v["value"] == 0.0 for k, v in line["metrics"].items()
                   if "compiles_in_window" in k), out[-1500:]
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    else:
        assert got == {m["name"] for m in want}
    assert "[device] platform=cpu kind=cpu count=1" in out


def test_fails_without_a_tpu():
    """Without ``--rehearse`` the run needs a TPU: non-zero exit and no
    result line on the CPU."""
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload",
         MANIFEST["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "need a tpu device" in p.stderr
    assert not any(ln.startswith("{") for ln in p.stdout.splitlines())


def _tree_digest(root):
    out = {}
    for base, dirs, files in os.walk(os.path.join(root, "benchmarks")):
        dirs[:] = [d for d in dirs if d not in ("_run", "__pycache__")]
        for f in files:
            path = os.path.join(base, f)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def _copy(tmp_path) -> str:
    """A copy of the benchmark beside the program, for a test to add to."""
    root = str(tmp_path / "copy")
    shutil.copytree(os.path.join(ROOT, "benchmarks"),
                    os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("_run", "__pycache__"))
    os.symlink(os.path.join(ROOT, "paddle_tpu"),
               os.path.join(root, "paddle_tpu"))
    return root


def _add(root, files: dict, man: dict) -> None:
    """New files under benchmarks/ (a dict is written as JSON) and the
    manifest beside it."""
    for rel, body in files.items():
        with open(os.path.join(root, "benchmarks", rel), "w") as f:
            f.write(body if isinstance(body, str) else json.dumps(body))
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(man, f)


def _serve_cell(man: dict) -> dict:
    return next(w for w in man["workloads"]
                if common.cell(w["name"])["config"]["role"] == "serve")


def test_fourth_cell_by_new_files_and_entries_only(tmp_path):
    """A later PR adds a cell, a mix and a per-layer metric with new files
    and new entries in BENCHMARK.json, and edits no file that is there.
    (A new end-to-end metric is another matter: the generator kind has to
    compute it, so it needs an edit to ``serve.py`` or ``train_steps.py``
    by a benchmark PR.)"""
    root = _copy(tmp_path)
    before = _tree_digest(root)
    chat = common.load_json("traffic", "chat-steady.json")
    new_files = {
        "traffic/chat-slow-test.json": dict(
            chat, arrivals={"process": "poisson", "rate_per_s": 0.3},
            population_seed=7,
            rehearse=dict(chat["rehearse"], arrivals={
                "process": "poisson", "rate_per_s": 4.0})),
        "layer_metrics/prefill_dev_ms.json": {
            "reader": "module_time",
            "args": {"module": "^jit__lambda$",
                     "with_kernel": "^decode_attention"}},
        "layer_metrics/compiles_in_window.slow-test.json": {
            "reader": "value", "args": {"key": "compiles_in_window"}},
    }
    man = json.loads(json.dumps(MANIFEST))
    serve = _serve_cell(man)
    name = "gpt1p3b-serve-chat-slow-test"
    man["workloads"].append(dict(serve, name=name, traffic="chat-slow-test"))
    e2e = [m for m in man["end_to_end"]
           if "workloads" in m and serve["name"] in m["workloads"]]
    for m in e2e:
        m["workloads"].append(name)
    for metric, unit in (("prefill_dev_ms", "ms"),
                         ("compiles_in_window.slow-test", "count")):
        man["per_layer"].append({
            "name": metric, "unit": unit, "better": "lower",
            "source": "device_trace", "layer": "model step",
            "moves": e2e[0]["name"], "workloads": [name]})
    _add(root, new_files, man)
    line, out = rehearse(root, name, 0)
    assert line["correct"] and line["failed"] == 0
    # round(4.0 requests/s x 3 s): the new mix's own rate, not chat's
    assert line["attempted"] == 12, out[-1500:]
    assert set(line["metrics"]) == {"cpu_rehearsal." + m["name"]
                                    for m in e2e} | {"cpu_rehearsal.setup_s"}
    line, _ = rehearse(root, name, 1)
    assert line["correct"] and line["failed"] == 0
    # prefill_dev_ms needs a device trace: its reader found nothing on the
    # CPU, and the line leaves it out
    assert set(line["metrics"]) == {
        "cpu_rehearsal.compiles_in_window.slow-test"}
    after = _tree_digest(root)
    assert {k: after[k] for k in before} == before       # nothing edited
    assert set(after) - set(before) == {
        "benchmarks/" + rel for rel in new_files}


TOY_FAMILY = '''"""A second family, for the test: its own key names in the configuration
file and in its sizes, its own wrapper of the reference; gpt underneath."""
from . import gpt


def _as_gpt(config):
    a = config["arch"]
    return dict(config, assumed={"vocab_rows": a["rows"]}, model={
        "n_embd": a["width"], "n_layer": a["depth"], "n_head": a["heads"],
        "n_inner": a["ffn"], "n_positions": a["positions"],
        "vocab_size": a["vocab"], "layer_norm_epsilon": a["eps"]})


def sizes(config):
    s = gpt.sizes(_as_gpt(config))
    return {"width": s["D"], "T": s["T"], "V": s["V"],
            "V_published": s["V_published"], "underneath": s}


def weights(config, seed):
    return gpt.weights(_as_gpt(config), seed)


def server(config, cfg, params):
    return gpt.server(config, cfg, params)


def served_margins(config, params, prompt, served):
    return gpt.served_margins(_as_gpt(config), params, prompt, served)


def decode_step_cost(sizes, batch_rows, live_kv_tokens):
    return gpt.decode_step_cost(sizes["underneath"], batch_rows,
                                live_kv_tokens)
'''

# the timed path broken underneath: a token altered where the server hands
# its answer over
TOY_BROKEN = '''from . import toy
from .toy import *  # noqa: F401,F403


def server(config, cfg, params):
    srv = toy.server(config, cfg, params)
    result = srv.result

    def altered(rid):
        out = list(result(rid))
        out[len(out) // 2] = (out[len(out) // 2] + 1) % config["arch"]["vocab"]
        return out

    srv.result = altered
    return srv
'''


def _toy_config(family: str) -> dict:
    chat = common.cell(_serve_cell(MANIFEST)["name"])["config"]
    tiny = common.merged(chat, chat["rehearse"])
    m = tiny["model"]
    return {"family": family, "role": "serve", "dtype": tiny["dtype"],
            "arch": {"width": m["n_embd"], "depth": m["n_layer"],
                     "heads": m["n_head"], "ffn": m["n_inner"],
                     "positions": m["n_positions"],
                     "vocab": m["vocab_size"],
                     "rows": tiny["assumed"]["vocab_rows"],
                     "eps": m["layer_norm_epsilon"]},
            "entry_point": tiny["entry_point"],
            "correctness": tiny["correctness"]}


def test_second_family_by_new_files_and_entries_only(tmp_path):
    """A later ``model_config`` PR adds an architecture with new files and
    new entries: a family module, its configuration file, a cell.  No file
    that is there is edited, the rehearsal is correct in both modes, and
    the same family with a token altered underneath is not."""
    root = _copy(tmp_path)
    before = _tree_digest(root)
    new_files = {"families/toy.py": TOY_FAMILY,
                 "families/toy_broken.py": TOY_BROKEN,
                 "configs/toy-serve.json": _toy_config("toy"),
                 "configs/toy-broken-serve.json": _toy_config("toy_broken")}
    man = json.loads(json.dumps(MANIFEST))
    serve = _serve_cell(man)
    cells = {"toy-serve-chat": "toy-serve",
             "toy-broken-serve-chat": "toy-broken-serve"}
    for name, config in cells.items():
        man["configs"].append({
            "name": config, "source": "a test", "reduced": [], "why": "test",
            "file": f"benchmarks/configs/{config}.json"})
        man["workloads"].append(dict(serve, name=name, config=config))
        for m in man["end_to_end"] + man["per_layer"]:
            if serve["name"] in m.get("workloads", []):
                m["workloads"].append(name)
    _add(root, new_files, man)
    chat = common.cell(serve["name"])
    for trace_flag, want in ((0, chat["end_to_end"]), (1, chat["per_layer"])):
        line, out = rehearse(root, "toy-serve-chat", trace_flag)
        assert line["correct"] and line["failed"] == 0, out[-1500:]
        got = check_line(line, [m["name"] for m in want])
        if not trace_flag:
            assert got == {m["name"] for m in want}
        assert "the toy family's reference" in out
        assert list(line)[-1] == "compared"
        assert all(c["value"] <= c["limit"]
                   for c in line["compared"].values())
    line, out = rehearse(root, "toy-broken-serve-chat", 0)
    assert line["correct"] is False, out[-1500:]
    worst = line["compared"]["worst_logit_margin"]
    assert worst["value"] > worst["limit"]
    after = _tree_digest(root)
    assert {k: after[k] for k in before} == before       # nothing edited
    assert set(after) - set(before) == {
        "benchmarks/" + rel for rel in new_files}


@pytest.mark.parametrize("family, message", [
    (None, 'states no "family"'),
    ("nonesuch", "no module for family 'nonesuch'"),
])
def test_a_configuration_needs_a_family_that_has_a_module(tmp_path, family,
                                                         message):
    """No default architecture: the run ends with the benchmark's own
    message and no result line."""
    root = _copy(tmp_path)
    config = _toy_config(family)
    if family is None:
        del config["family"]
    man = json.loads(json.dumps(MANIFEST))
    man["configs"].append({
        "name": "anon", "source": "a test", "reduced": [], "why": "test",
        "file": "benchmarks/configs/anon.json"})
    man["workloads"].append(dict(_serve_cell(man), name="anon-chat",
                                 config="anon"))
    _add(root, {"configs/anon.json": config}, man)
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "anon-chat",
         "--seed", "1", "--seconds", "1", "--trace", "0", "--rehearse"],
        cwd=root, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "benchmark: " in p.stderr and message in p.stderr
    assert not any(ln.startswith("{") for ln in p.stdout.splitlines())


# what benchmarks/model.py gave at the parent of PR 28 (commit 427c384),
# before it became families/gpt.py: (matmul_params, train_flops_per_token
# at 2048, decode_step_cost at 20 rows holding 9500.5 tokens)
GOLDEN = {
    "cerebras-gpt-1.3b-serve": (1310982144, 9073852416.0, {
        "bytes": 4498227200.0, "flops": 54307160064.0,
        "weight_bytes": 2630352896, "kv_bytes": 1867874304.0}),
    "cerebras-gpt-590m-train": (586874880, 4200726528.0, {
        "bytes": 2230720512.0, "flops": 24525674496.0,
        "weight_bytes": 1180041216, "kv_bytes": 1050679296.0}),
}


@pytest.mark.parametrize("entry", MANIFEST["configs"],
                         ids=lambda c: c["name"])
def test_gpt_family_counts_what_model_py_counted(entry):
    """100% may not move with the file: the counts to the digit."""
    with open(os.path.join(ROOT, entry["file"]), encoding="utf-8") as f:
        config = json.load(f)
    fam = common.family(config)
    assert fam.__name__ == "benchmarks.families.gpt"
    s = fam.sizes(config)
    assert {"T", "V", "V_published"} <= set(s)
    params, flops, step = GOLDEN[entry["name"]]
    assert fam.matmul_params(s) == params
    assert fam.train_flops_per_token(s, 2048) == flops
    assert fam.decode_step_cost(s, 20, 9500.5) == step


def test_schedule_is_the_mix_own_and_prompts_the_seed():
    """Every seed meets the same sizes at the same offsets (the order
    changes the work); only the prompt tokens differ."""
    from benchmarks.generators.open_loop import Source

    mix = common.load_json("traffic", "chat-steady.json")
    sizes = {"T": 2048, "V_published": 50257}
    a, b = (Source(mix, seed, sizes, 51.0, False) for seed in (1, 2 ** 31 + 5))
    assert a.rel == b.rel
    in_window = [x for x in a.rel if x[0] >= mix["ramp_s"]]
    assert len(in_window) == round(
        mix["arrivals"]["rate_per_s"] * 51.0) == 23
    assert all(0 <= off < mix["ramp_s"] + 51.0 for off, _ in a.rel)
    a.start(0.0), b.start(0.0)
    ra, rb = a.due(1e9), b.due(1e9)
    assert [len(r.prompt) for r in ra] == [len(r.prompt) for r in rb]
    assert any((x.prompt != y.prompt).any() for x, y in zip(ra, rb))
    other = Source(dict(mix, population_seed=mix["population_seed"] + 1),
                   1, sizes, 51.0, False)
    assert other.rel != a.rel
    for col in (0, 1):                 # the same lengths, paired anew
        assert sorted(s[col] for _, s in other.rel) == \
            sorted(s[col] for _, s in a.rel)


# --------------------------------------------------------------------------
# arithmetic on hand-made data
# --------------------------------------------------------------------------


@pytest.mark.parametrize("values,q,want", [
    ([1, 2, 3, 4, 5], 50, 3.0),
    ([1, 2, 3, 4], 50, 2.5),
    ([10, 20], 95, 19.5),
    (list(range(1, 101)), 95, 95.05),
    ([7], 95, 7.0),
])
def test_percentile(values, q, want):
    assert common.percentile(values, q) == pytest.approx(want)
    assert common.percentile(list(reversed(values)), q) == pytest.approx(want)


def _events():
    ev = lambda name, t0, t1, **a: {"name": name, "t0": t0, "t1": t1,  # noqa: E731
                                    "args": a}
    return [
        ev("serving.prefill", 10.2, 10.3, rid=0, prompt_len=4),
        ev("serving.request", 10.1, 11.3, rid=0, prompt_len=4, tokens=11),
        ev("serving.prefill", 10.6, 10.9, rid=1, prompt_len=8),
        # rid 1 was evicted and admitted again: the first prefill counts
        ev("serving.prefill", 11.5, 11.6, rid=1, prompt_len=9),
        ev("serving.request", 10.5, 12.9, rid=1, prompt_len=8, tokens=5),
        ev("serving.prefill", 11.0, 11.2, rid=2, prompt_len=3),
        ev("serving.request", 9.0, 9.5, rid=99, prompt_len=1, tokens=2),
        ev("profiler.capture", 0.0, 1.0),
    ]


def _records():
    return [{"rid": r, "t_due": d, "t_submit": s, "prompt_len": p,
             "out_len": o} for r, d, s, p, o in
            [(0, 10.0, 10.1, 4, 11), (1, 10.4, 10.5, 8, 5),
             (2, 10.9, 10.95, 3, 7), (3, 12.0, 12.0, 5, 5)]]


def test_join_by_rid_and_request_times():
    j = requests.join(_records(), _events())
    assert [r["rid"] for r in j] == [0, 1, 2, 3]
    assert j[0]["t_admit"] == 10.2 and j[0]["t_first"] == 10.3
    assert j[1]["t_first"] == 10.9 and j[1]["t_retire"] == 12.9
    assert "t_retire" not in j[2] and "t_first" not in j[3]
    # time to first token counts from when a request was due
    ttft = requests.spans_ms(requests.due_in(j, 10.0, 11.0), "t_due",
                             "t_first")
    assert ttft == pytest.approx([300.0, 500.0, 300.0])
    assert common.percentile(ttft, 95) == pytest.approx(480.0)
    late = requests.spans_ms(j, "t_due", "t_submit")
    assert late == pytest.approx([100.0, 100.0, 50.0, 0.0])
    # (retire - first token) / (tokens - 1) over requests retired in [lo, hi)
    assert requests.tpot_ms(requests.completed_in(j, 10.0, 12.0)) == \
        pytest.approx([100.0])
    assert requests.tpot_ms(requests.completed_in(j, 10.0, 13.0)) == \
        pytest.approx([100.0, 500.0])


def test_live_kv_tokens():
    j = requests.join(_records(), _events())
    # at 11.1: rid 0 has 1 + 0.8 / 0.1 = 9 of 11 tokens, rid 1 has
    # 1 + 0.2 / 0.5 = 1.4 of 5; rid 2 gets its first token at 11.2
    rows, tokens = requests.live_kv_tokens(j, 11.1)
    assert rows == 2
    assert tokens == pytest.approx((4 + 9) + (8 + 1.4))
    # at 12.0: rid 0 retired; rid 2 never retired and runs at the median
    # pace of the finished ones (0.5 s a token): 1 + 0.8 / 0.5 = 2.6
    rows, tokens = requests.live_kv_tokens(j, 12.0)
    assert rows == 2
    assert tokens == pytest.approx((8 + 1 + 1.1 / 0.5) + (3 + 2.6))


# --------------------------------------------------------------------------
# the trace reducer on the small recorded extract
# --------------------------------------------------------------------------


def test_op_and_module_names():
    hlo = ("%fusion.12 = bf16[32,1,2048]{2,1,0:T(8,128)(2,1)S(1)} "
           "fusion(bf16[32,1,2048]{2,1,0:T(8,128)(2,1)} %p.1), kind=kLoop")
    assert trace.op_name(hlo) == "fusion.12 fusion"
    assert trace.op_name(
        "%paged_decode_attention.6 = bf16[32,16,128]{2,1,0:T(8,128)(2,1)} "
        "custom-call(bf16[32,16,128]{2,1,0} %x)") == \
        "paged_decode_attention.6 custom-call"
    assert trace.op_name("%while.2 = (s32[]{:T(128)}, bf16[1,256]{1,0}) "
                         "while((s32[]{:T(128)}) %t)") == "while.2 while"
    assert trace.module_name("jit__lambda(11273838709458563232)") == \
        "jit__lambda"


def test_reduce_hand_made_extract():
    ex = {"devices": [{"name": "/device:TPU:0", "lines": {
        trace.MODULE_LINE: [["jit_a", 0.5, 1.0], ["jit_a", 2.0, 1.0],
                            ["jit_b", 3.2, 0.5], ["jit_a", 3.9, 0.05]],
        # while.1 covers the two ops of its body
        trace.OP_LINE: [["while.1 while", 0.5, 1.0],
                        ["fusion.1 fusion", 0.5, 0.4],
                        ["kern.2 custom-call", 0.9, 0.6],
                        ["fusion.1 fusion", 2.0, 0.5],
                        ["kern.2 custom-call", 2.4, 0.6],
                        ["fusion.9 fusion", 3.2, 0.5],
                        ["fusion.1 fusion", 3.9, 0.05]]}}],
        "host": [["bench.slice", 1.0, 3.0], ["bench.tick", 0.9, 1.05],
                 ["bench.submit", 3.0, 0.25], ["bench.tick", 3.3, 0.7]]}
    red = trace.reduce(ex)
    assert red["window_s"] == pytest.approx(3.0)
    # ops clipped to [1, 4]: 1.0-1.5, 2.0-3.0 (two ops overlap), 3.2-3.7,
    # 3.9-3.95
    assert red["busy_s"] == pytest.approx(0.5 + 1.0 + 0.5 + 0.05)
    # whole runs inside the slice only, and never a device's last run
    # (the profiler's stop cuts it short)
    assert red["modules"] == [
        {"name": "jit_a", "s": 1.0, "kernels": ["kern"]},
        {"name": "jit_b", "s": 0.5, "kernels": []}]
    # self time: the while keeps nothing of 1.0-1.5, its body has it all
    assert red["ops"]["while.1 while"] == pytest.approx(0.0)
    assert red["ops"]["kern.2 custom-call"] == pytest.approx(0.5 + 0.6)
    # 2.0-2.5 less the 0.1 that kern.2 overlaps, and 3.9-3.95
    assert red["ops"]["fusion.1 fusion"] == pytest.approx(0.4 + 0.05)
    assert [(n, round(s, 3)) for n, s in red["gaps"]] == [
        ("bench.tick", 0.5), ("bench.submit", 0.2), ("bench.tick", 0.2),
        ("bench.tick", 0.05)]
    bd = trace.breakdown(red)
    assert bd["device_ops"][0] == ["kern.2 custom-call",
                                   pytest.approx(1.1)]
    assert dict(bd["idle_gaps"]) == {"bench.tick": pytest.approx(0.75),
                                     "bench.submit": pytest.approx(0.2)}


def test_module_time_chooses_by_kernel():
    from benchmarks.readers import module_time

    run = {"trace": {"busy_s": 2.0, "n_devices": 1, "modules": [
        {"name": "jit__lambda", "s": 0.30, "kernels": ["paged_decode"]},
        {"name": "jit__lambda", "s": 0.34, "kernels": ["paged_decode"]},
        {"name": "jit__lambda", "s": 0.32, "kernels": ["paged_decode"]},
        {"name": "jit__lambda", "s": 0.04, "kernels": ["flash_attention"]},
        {"name": "jit_other", "s": 9.0, "kernels": []}]}}
    step = {"module": "^jit__lambda$", "with_kernel": "^paged_decode"}
    assert module_time.read(run, step) == pytest.approx(320.0)
    assert module_time.read(run, {
        "module": "^jit__lambda$", "with_kernel": "^flash"}) == \
        pytest.approx(40.0)
    assert module_time.read(run, {"module": "^jit_other$"}) == \
        pytest.approx(9000.0)
    # a reader that finds nothing to read returns nothing
    assert module_time.read(run, {"module": "^nothing$"}) is None
    assert module_time.read({"trace": None}, step) is None


def test_reduce_recorded_v5e_extract():
    """The extract of a real v5e trace (1.34 s of the chat cell's slice,
    recorded in PR 23: three decode steps, one prefill, and a fourth step
    that the cut ends)."""
    from benchmarks.readers import module_time

    with open(os.path.join(DATA, "v5e_serve_extract.json"),
              encoding="utf-8") as f:
        ex = json.load(f)
    red = trace.reduce(ex)
    assert red["n_devices"] == 1
    assert red["window_s"] == pytest.approx(1.338)
    assert 0.98 * red["window_s"] < red["busy_s"] < red["window_s"]
    step = {"module": "^jit__lambda$",
            "with_kernel": "^paged_decode_attention"}
    assert module_time.read({"trace": red}, step) == pytest.approx(
        335.49, abs=0.01)
    # the one prefill in the slice carries another kernel
    assert module_time.read({"trace": red}, {
        "module": "^jit__lambda$",
        "with_kernel": "^decode_attention"}) == pytest.approx(
        40.18, abs=0.01)
    assert sum(s for _, s in red["gaps"]) == pytest.approx(
        red["window_s"] - red["busy_s"], rel=1e-6)
    bd = trace.breakdown(red)
    assert bd["device_ops"][0][0] == "paged_decode_attention.6 custom-call"
    assert len(bd["device_ops"]) == 10
    assert all(n.startswith("bench.") or n == "(no bench span)"
               for n, _ in bd["idle_gaps"])
