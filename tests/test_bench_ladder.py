"""The bench GPT ladder's tournament selection (bench.py::bench_gpt).

The ladder's rung order encodes an MFU *guess*; the tournament measures up
to BENCH_LADDER_TOP fitting rungs and headlines the best MEASURED MFU, so
a wrong guess costs a few extra minutes instead of the round's headline
number.  Rungs run in bench.py's own process (a chip belongs to one
process): a rung that runs out of device memory is stepped past, anything
else it raises ends the run.  Control flow is tested like product code: the
rung runner and the HBM pre-filter are faked.
"""
import importlib.util
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def bench(monkeypatch):
    spec = importlib.util.spec_from_file_location(
        "bench_under_test", os.path.join(REPO, "bench.py"))
    m = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(m)
    # deterministic environment: every rung "fits", 3-rung tournament
    monkeypatch.setattr(m, "_hbm_bytes", lambda: 16e9)
    monkeypatch.setattr(
        m, "_gpt_rung_fits",
        lambda name, cfg_kwargs, B, T, sd, hbm, accum=1, fused=False: True)
    monkeypatch.delenv("BENCH_LADDER_TOP", raising=False)

    def no_children(*a, **k):
        raise AssertionError("the ladder started a child process")

    monkeypatch.setattr(m.subprocess, "run", no_children)
    return m


def _rungs(m, monkeypatch, names):
    monkeypatch.setattr(
        m, "_gpt_rungs",
        lambda: [(n, {}, 8, 2048, 10, "bfloat16", 1, False) for n in names])


OOM = RuntimeError("RESOURCE_EXHAUSTED: Ran out of memory in memory space "
                   "hbm. Used 20G of 15.75G hbm.")


def _rung_results(m, monkeypatch, by_name):
    """Fake the in-process rung runner: by_name[rung] is a result dict or
    an exception to raise."""
    calls = []
    names = [r[0] for r in m._gpt_rungs()]

    def fake_rung(idx):
        name = names[idx]
        calls.append(name)
        spec = by_name[name]
        if isinstance(spec, BaseException):
            raise spec
        return spec

    monkeypatch.setattr(m, "_run_gpt_rung", fake_rung)
    return calls


def _r(name, mfu, device="tpu"):
    return {"metric": f"tokens_per_sec_per_chip_{name}", "mfu": mfu,
            "value": mfu * 1e5, "step_ms": 100.0, "device": device}


def test_headline_is_best_mfu_not_first_success(bench, monkeypatch):
    _rungs(bench, monkeypatch, ["a", "b", "c", "d"])
    calls = _rung_results(bench, monkeypatch, {
        "a": _r("a", 0.21), "b": _r("b", 0.34), "c": _r("c", 0.28),
        "d": _r("d", 0.9)})
    out = bench.bench_gpt(small=False)
    # top_k=3 default: 'd' must never run; best of a/b/c wins
    assert calls == ["a", "b", "c"]
    assert out["metric"] == "tokens_per_sec_per_chip_b"
    assert [c["mfu"] for c in out["candidates"]] == [0.21, 0.34, 0.28]


def test_failed_rungs_dont_count_toward_top_k(bench, monkeypatch):
    _rungs(bench, monkeypatch, ["a", "b", "c", "d"])
    calls = _rung_results(bench, monkeypatch, {
        "a": OOM, "b": _r("b", 0.2), "c": OOM, "d": _r("d", 0.3)})
    out = bench.bench_gpt(small=False)
    assert calls == ["a", "b", "c", "d"]
    assert out["metric"] == "tokens_per_sec_per_chip_d"


def test_a_rung_that_raises_anything_else_ends_the_run(bench, monkeypatch):
    """Only an OOM is the ladder's own business: a compiler refusal (or any
    other error) is never stepped past to a number from a lesser rung."""
    _rungs(bench, monkeypatch, ["a", "b"])
    calls = _rung_results(bench, monkeypatch, {
        "a": ValueError("Mosaic: block shape refused"), "b": _r("b", 0.5)})
    with pytest.raises(ValueError, match="refused"):
        bench.bench_gpt(small=False)
    assert calls == ["a"]


def test_all_rungs_failing_raises(bench, monkeypatch):
    _rungs(bench, monkeypatch, ["a", "b"])
    _rung_results(bench, monkeypatch, {"a": OOM, "b": OOM})
    with pytest.raises(RuntimeError):
        bench.bench_gpt(small=False)


def test_top_k_env_override(bench, monkeypatch):
    monkeypatch.setenv("BENCH_LADDER_TOP", "1")
    _rungs(bench, monkeypatch, ["a", "b"])
    calls = _rung_results(bench, monkeypatch, {
        "a": _r("a", 0.2), "b": _r("b", 0.8)})
    out = bench.bench_gpt(small=False)
    assert calls == ["a"]
    assert out["metric"] == "tokens_per_sec_per_chip_a"


def test_unfit_rungs_are_skipped_entirely(bench, monkeypatch):
    bench._gpt_rung_fits = (
        lambda name, cfg_kwargs, B, T, sd, hbm, accum=1, fused=False: False)
    _rungs(bench, monkeypatch, ["a"])
    _rung_results(bench, monkeypatch, {})
    with pytest.raises(RuntimeError):
        bench.bench_gpt(small=False)


def test_calibrated_walk_matches_on_device_outcomes(monkeypatch):
    """The round-5 window-2 ground truth, frozen as a test: every rung
    PROVEN to run on the 15.75GiB v5e is admitted by the walk, every
    rung that OOMed there ("Used 29.05G / 20.26G of 15.75G hbm") is
    excluded, and the proven-fit bypass is void on smaller chips.

    Loads its own module copy: the shared fixture stubs _gpt_rung_fits
    to always-True, which is exactly what this test must NOT use."""
    # hermetic: an ambient BENCH_HEADROOM_GB export (natural when
    # experimenting with the pre-filter) must not flip the frozen facts
    monkeypatch.delenv("BENCH_HEADROOM_GB", raising=False)
    spec = importlib.util.spec_from_file_location(
        "bench_calibration_test", os.path.join(REPO, "bench.py"))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    rungs = {r[0]: r for r in bench._gpt_rungs()}
    hbm = 16.9e9  # 15.75 GiB in decimal bytes

    def fits(name, hbm_b=hbm):
        _, kw, B, T, _, sd, accum, fused = rungs[name]
        return bench._gpt_rung_fits(name, kw, B, T, sd, hbm_b, accum,
                                    fused)

    ran = ["gpt_760m_fused_dots_acc16_b16", "gpt_760m_fused_dots_acc8_b8",
           "gpt_350m_fused_dots_acc4_b8", "gpt_350m_dots_acc4_b8",
           "gpt_350m_dots_acc8_b8", "gpt_350m_remat_b8"]
    oomed = ["gpt_350m_fused_acc2_b8", "gpt_350m_fused_dots_acc2_b8",
             "gpt_350m_dots_acc2_b8", "gpt_350m_b2"]
    for name in ran:
        assert fits(name), name
    for name in oomed:
        assert not fits(name), name
    # empirical proof is chip-specific: an 8GB part gets the estimate
    for name in ran:
        assert not fits(name, 8e9), name
    # the proof is keyed by NAME but holds for a specific CONFIG: freeze
    # the shape of every proven rung so an edit under the same name
    # can't silently ride the bypass into a compile-to-OOM
    frozen = {
        "gpt_760m_fused_dots_acc16_b16": (1536, 24, 16, 2048, 16, True,
                                          "dots"),
        "gpt_760m_fused_dots_acc8_b8": (1536, 24, 8, 2048, 8, True,
                                        "dots"),
        "gpt_350m_fused_dots_acc4_b8": (1024, 24, 8, 2048, 4, True,
                                        "dots"),
        "gpt_350m_dots_acc4_b8": (1024, 24, 8, 2048, 4, False, "dots"),
        "gpt_350m_dots_acc8_b8": (1024, 24, 8, 2048, 8, False, "dots"),
        "gpt_350m_remat_b8": (1024, 24, 8, 2048, 1, False, None),
    }
    assert set(frozen) == set(bench._PROVEN_FIT)
    # extrapolated rungs are admitted to the walk but NOT certified as
    # ground truth; they must stay disjoint from the proven set, and
    # their shapes freeze too — the bypass is name-keyed, so a config
    # edit under the same name must not silently ride it into an OOM
    assert not (bench._EXTRAPOLATED_FIT & bench._PROVEN_FIT)
    frozen_extrapolated = {
        "gpt_760m_fused_dots_acc32_b32": (1536, 24, 32, 2048, 32, True,
                                          "dots"),
        "gpt_1.3b_fused_remat_af_acc8_b8": (2048, 24, 8, 2048, 8, True,
                                            None),
    }
    assert set(frozen_extrapolated) == set(bench._EXTRAPOLATED_FIT)
    for name, (h, L, B, T, accum, fused, policy) in             frozen_extrapolated.items():
        assert fits(name), name
        _, kw, rb, rt, _, _, raccum, rfused = rungs[name]
        assert (kw["hidden_size"], kw["num_layers"], rb, rt, raccum,
                rfused, kw.get("remat_policy")) == (h, L, B, T, accum,
                                                    fused, policy), name
    for name, (h, L, B, T, accum, fused, policy) in frozen.items():
        _, kw, rb, rt, _, _, raccum, rfused = rungs[name]
        assert (kw["hidden_size"], kw["num_layers"], rb, rt, raccum,
                rfused, kw.get("remat_policy")) == (h, L, B, T, accum,
                                                    fused, policy), name


def test_fused_rungs_exist_without_any_marker_file(bench):
    """Which rungs exist is decided by code in git, not by a git-ignored
    file a checker once wrote."""
    names = [r[0] for r in bench._gpt_rungs()]
    assert names[0] == "gpt_1.3b_fused_acc8_b8"
    assert sum(r[7] for r in bench._gpt_rungs()) >= 10  # fused=True rungs
    assert not os.path.exists(os.path.join(REPO, "FUSED_KERNELS_OK.json"))


def test_tournament_budget_stops_after_banked_result(bench, monkeypatch):
    monkeypatch.setenv("BENCH_TOURNAMENT_BUDGET", "0")  # instant exhaustion
    _rungs(bench, monkeypatch, ["a", "b", "c"])
    calls = _rung_results(bench, monkeypatch, {
        "a": _r("a", 0.2), "b": _r("b", 0.8), "c": _r("c", 0.9)})
    out = bench.bench_gpt(small=False)
    # the first rung banks a result; the exhausted budget stops the rest
    assert calls == ["a"]
    assert out["metric"] == "tokens_per_sec_per_chip_a"
