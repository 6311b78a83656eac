"""The decode/serving arms and the harness around them (bench.py).

One process for each chip: arms and rungs run in bench.py's own process, in
order, and nothing a config raises is recorded and carried past.  The
assembler's contract — tok_s fields, ratios, the headline arm — is pinned so
drift between the decode and serving records can't reappear.
"""
import ast
import importlib.util
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench.py")


@pytest.fixture()
def bench():
    spec = importlib.util.spec_from_file_location("bench_arms_under_test",
                                                  BENCH)
    m = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(m)
    return m


def test_arms_run_in_this_process_in_order(bench, monkeypatch):
    def no_children(*a, **k):
        raise AssertionError("an arm started a child process")

    monkeypatch.setattr(bench.subprocess, "run", no_children)
    monkeypatch.setattr(bench.subprocess, "Popen", no_children)
    calls = []

    def measure(arm):
        calls.append(arm)
        return {"tok_s": 10.0 * len(calls), "first_token_ms": 1.0}

    res = bench._arm_results(["float", "int8"], measure)
    assert calls == ["float", "int8"]
    assert res == {"float": {"tok_s": 10.0, "first_token_ms": 1.0},
                   "int8": {"tok_s": 20.0, "first_token_ms": 1.0}}


def test_bare_tok_s_and_w4_flag_are_recorded(bench, monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_W4_KERNEL", "1")
    res = bench._arm_results(["int4"], lambda arm: 5.0)
    assert res == {"int4": {"tok_s": 5.0, "w4": {"enabled": True}}}


def test_an_arm_that_raises_ends_the_run(bench):
    """No record-and-continue: the healthy arms' numbers do not paper over
    a broken one."""
    def measure(arm):
        if arm == "int8":
            raise RuntimeError("kernel refused")
        return 1.0

    with pytest.raises(RuntimeError, match="kernel refused"):
        bench._arm_results(["float", "int8", "int4"], measure)


def test_assembler_ratio_and_headline_contract(bench):
    out = bench._assemble_arm_record(
        {}, {"float": {"tok_s": 100.0}, "int8": {"tok_s": 150.0},
             "int4": {"tok_s": 80.0, "w4": {"enabled": True}}},
        ["float", "int8", "int4"], "float", "int8", "t")
    assert out["value"] == 150.0 and out["value_arm"] == "int8"
    assert out["int8_vs_float"] == 1.5 and out["int4_vs_float"] == 0.8
    assert out["int4_w4"] == {"enabled": True}
    assert "float_vs_float" not in out
    assert not any(k.endswith("_error") for k in out)


def test_bench_starts_no_copy_of_itself():
    """Source scan: the only subprocess call left is git's, and no argv
    names this file or the interpreter."""
    tree = ast.parse(open(BENCH, encoding="utf-8").read())
    calls = [n for n in ast.walk(tree)
             if isinstance(n, ast.Call) and isinstance(n.func, ast.Attribute)
             and isinstance(n.func.value, ast.Name)
             and n.func.value.id == "subprocess"]
    assert [ast.unparse(c.args[0])[:8] for c in calls] == ["['git', "]
    src = open(BENCH, encoding="utf-8").read()
    for gone in ("--arm", "sys.executable", "BENCH_ARM", "_cpu_fallback",
                 "JAX_PLATFORMS\"] = \"cpu"):
        assert gone not in src, gone


def _bench(*args, **env):
    e = {k: v for k, v in os.environ.items() if not k.startswith("BENCH_")}
    e.update(JAX_PLATFORMS="cpu", **env)
    return subprocess.run([sys.executable, BENCH, *args], cwd=REPO, env=e,
                          capture_output=True, text=True, timeout=600)


def test_without_cpu_and_without_a_chip_nothing_is_measured():
    out = _bench("--small")
    assert out.returncode != 0
    assert out.stdout.strip() == ""          # no JSON line, no number
    assert "no TPU" in out.stderr and "Nothing was measured" in out.stderr
    assert "[bench] device=" not in out.stderr  # before any config ran


def test_a_config_that_raises_exits_nonzero(tmp_path):
    """--config with an unknown rung name: SystemExit, not a record."""
    out = _bench("--cpu", "--small", "--gpt-rung", "no_such_rung")
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "unknown rung" in out.stderr


def test_cpu_small_is_the_rehearsal_and_says_so():
    out = _bench("--cpu", "--small", "--config", "mnist")
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    prov = line["provenance"]
    assert prov["platform"] == "cpu" and line["device"] == "cpu"
    assert set(prov) == {"ts", "platform", "device_kind", "jax", "jaxlib",
                         "python", "git_rev", "flags"}
    assert "_cpu_fallback" not in line["metric"]
