"""Device-truth observability (PR 6): per-compiled-step cost/memory
analysis captured at ``telemetry.instrument_compile`` time, live MFU /
roofline gauges, HBM sampling on the serving/fit hot paths (zero extra
device syncs — the PR-2/PR-4 pins re-asserted), the /healthz and
POST /profile endpoints, and the ``tools/check_instrumented.py``
watchtower.
"""
import json
import os
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu import nn, telemetry
from paddle_tpu.framework import monitor, platform as fw_platform
from paddle_tpu.hapi import Model
from paddle_tpu.hapi import model as hapi_model
from paddle_tpu.text import gpt, serving


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    telemetry.reset()
    yield
    telemetry.reset()


@pytest.fixture(scope="module")
def tiny_model():
    cfg = gpt.GPTConfig(vocab_size=64, hidden_size=32, num_layers=1,
                        num_heads=2, max_seq_len=32)
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _instrumented_matmul(name, n=64):
    """One compiled matmul routed through instrument_compile — the
    hand-computable FLOPs fixture (2*n^3 on the XLA cost model)."""
    fn = telemetry.instrument_compile(
        name, (name,), None, jax.jit(lambda a, b: a @ b))
    a = jnp.ones((n, n), jnp.float32)
    fn(a, a)
    return fn


class TestAnalysisCapture:
    def test_matmul_cost_and_memory_analysis(self):
        n = 64
        _instrumented_matmul("t.capture", n)
        feed = telemetry.device_feed()
        s = feed["steps"]["t.capture"]
        # XLA cost analysis: a dense [n,n]@[n,n] is exactly 2*n^3 FLOPs
        assert s["flops"] == 2 * n ** 3
        assert s["bytes_accessed"] > 0
        # memory analysis: two fp32 [n,n] args, one fp32 [n,n] output
        assert s["argument_bytes"] == 2 * n * n * 4
        assert s["output_bytes"] == n * n * 4
        assert "temp_bytes" in s
        assert s["compiles"] == 1
        # CPU: no peaks table entry -> MFU must be null, never fabricated
        assert feed["peak_flops"] is None
        assert s["mfu"] is None

    def test_serving_pass_populates_step_feed(self, tiny_model):
        cfg, params = tiny_model
        srv = serving.DecodeServer(params, cfg, max_batch=2, max_len=16)
        prompts = np.random.default_rng(0).integers(1, 60, (2, 4))
        rids = [srv.submit(p, max_new_tokens=4) for p in prompts]
        while srv.pending():
            srv.tick()
        assert all(len(srv.result(r)) == 4 for r in rids)
        snap = telemetry.snapshot()
        steps = snap["device"]["steps"]
        # prefill instruments per prompt BUCKET (its FLOPs are shape-
        # specific); the 4-token prompts land in bucket 4
        for name in ("serving.prefill@4", "serving.step"):
            assert steps.get(name, {}).get("flops", 0) > 0, (name, steps)
        # the tick walls were joined in (sync tick covers execution)
        assert steps["serving.step"].get("step_s", 0) > 0

    def test_device_feed_flag_disables_capture(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_DEVICE_FEED", "0")
        _instrumented_matmul("t.disabled")
        assert "t.disabled" not in telemetry.device_feed()["steps"]
        # the compile itself is still recorded (the recompile watch is
        # independent of the device feed)
        assert any(c["name"] == "t.disabled"
                   for c in telemetry.snapshot()["compiles"])


class TestMFU:
    def test_mfu_and_roofline_vs_hand_computed(self, monkeypatch):
        n = 64
        _instrumented_matmul("t.mfu", n)
        # pretend the capture ran on a known chip: peaks resolve from
        # the shared framework.platform table (platform too — a non-TPU
        # platform hard-gates peaks to None)
        monkeypatch.setitem(telemetry._device_info, "device_kind",
                            "TPU v5 lite")
        monkeypatch.setitem(telemetry._device_info, "platform", "tpu")
        wall = 1e-4
        # first note after a compile is deliberately discarded (it
        # overlapped the compiling call) — note twice for steady state
        telemetry.note_step_time("t.mfu", wall)
        telemetry.note_step_time("t.mfu", wall)
        feed = telemetry.device_feed()
        peak_f, peak_bw = fw_platform.device_peaks("TPU v5 lite", "tpu")
        assert (feed["peak_flops"], feed["peak_hbm_bytes_per_s"]) \
            == (peak_f, peak_bw)
        s = feed["steps"]["t.mfu"]
        flops = 2 * n ** 3
        assert s["mfu"] == pytest.approx(flops / wall / peak_f, rel=1e-3)
        assert s["hbm_bw_util"] == pytest.approx(
            s["bytes_accessed"] / wall / peak_bw, rel=1e-3)
        # roofline: AI of a 64^3 matmul (~6 FLOPs/byte) is far below the
        # v5e machine balance (~240) -> bandwidth-bound
        assert s["arithmetic_intensity"] == pytest.approx(
            flops / s["bytes_accessed"], rel=1e-3)
        assert s["bound"] == "bandwidth"

    def test_off_a_tpu_there_are_no_peaks(self):
        assert fw_platform.device_peaks("cpu", "cpu") == (None, None)
        # real CUDA kind strings carry no 'gpu' substring — the platform
        # is the gate
        assert fw_platform.device_peaks("NVIDIA A100-SXM4-40GB",
                                        "gpu") == (None, None)
        assert fw_platform.device_peaks("TPU v5 lite", None) \
            == (None, None)

    def test_unknown_kind_on_a_tpu_is_an_error_not_none(self, monkeypatch):
        """Peaks resolve from device_kind only: no environment hint stands
        in for a kind the table does not list, and where an MFU or a
        roofline is computed that is an error."""
        monkeypatch.setenv("PALLAS_TPU_GEN", "v5e")
        for kind in ("", "TPU vNext prototype"):
            with pytest.raises(ValueError, match="DEVICE_PEAKS"):
                fw_platform.device_peaks(kind, "tpu")
        assert fw_platform.device_peaks("TPU v5 lite", "tpu") \
            == (197e12, 0.82e12)
        _instrumented_matmul("t.unknownkind")
        monkeypatch.setitem(telemetry._device_info, "device_kind",
                            "TPU vNext prototype")
        monkeypatch.setitem(telemetry._device_info, "platform", "tpu")
        with pytest.raises(ValueError, match="vNext"):
            telemetry.device_feed()

    def test_unknown_chip_reports_null_mfu(self):
        _instrumented_matmul("t.nullmfu")
        telemetry.note_step_time("t.nullmfu", 1e-4)
        telemetry.note_step_time("t.nullmfu", 1e-4)
        s = telemetry.device_feed()["steps"]["t.nullmfu"]
        assert s["mfu"] is None and s["bound"] is None
        assert s["flops_per_s"] > 0  # the honest half still reports

    def test_compile_overlapped_wall_is_discarded(self):
        """The wall around an executable's compiling first call must
        not seed the EWMA: a name noted exactly once after its compile
        reports NO step time (honest absence) rather than a
        compile-dominated MFU."""
        _instrumented_matmul("t.skipwall")
        telemetry.note_step_time("t.skipwall", 5.0)  # compile-included
        with telemetry._device_lock:
            assert "t.skipwall" not in telemetry._step_times
        telemetry.note_step_time("t.skipwall", 0.01)  # steady state
        with telemetry._device_lock:
            assert telemetry._step_times["t.skipwall"]["ewma_s"] \
                == pytest.approx(0.01)

    def test_ewma_discards_compile_outlier_first_sample(self):
        telemetry.note_step_time("t.ewma", 2.0)   # compile-included wall
        telemetry.note_step_time("t.ewma", 0.01)  # steady state
        with telemetry._device_lock:
            assert telemetry._step_times["t.ewma"]["ewma_s"] \
                == pytest.approx(0.01)

    def test_prometheus_exports_device_gauges(self, monkeypatch):
        _instrumented_matmul("t.prom")
        monkeypatch.setitem(telemetry._device_info, "device_kind",
                            "TPU v5 lite")
        monkeypatch.setitem(telemetry._device_info, "platform", "tpu")
        telemetry.note_step_time("t.prom", 1e-4)
        telemetry.note_step_time("t.prom", 1e-4)
        prom = telemetry.render_prometheus()
        assert 'paddle_tpu_device_step_flops{step="t.prom"}' in prom
        assert 'paddle_tpu_device_step_mfu{step="t.prom"}' in prom


class _FakeDev:
    def __init__(self, in_use=123, peak=456, limit=1000):
        self.calls = 0
        self._stats = {"bytes_in_use": in_use,
                       "peak_bytes_in_use": peak, "bytes_limit": limit}

    def memory_stats(self):
        self.calls += 1
        return self._stats


class TestHBMGauges:
    def test_sample_sets_gauges_counters_and_timeline(self):
        dev = _FakeDev()
        out = telemetry.sample_device_stats(min_interval_s=0,
                                            devices=[dev])
        assert out["device0_bytes_in_use"] == 123
        snap = telemetry.snapshot()
        assert snap["gauges"]["device.device0_bytes_in_use"] == 123
        assert snap["gauges"]["device.device0_bytes_limit"] == 1000
        # monitor registry (STAT_gpuN_mem analog) sees the same numbers
        assert snap["counters"]["device0_peak_bytes_in_use"] == 456
        assert snap["device"]["hbm"]["device0_bytes_in_use"] == 123
        # Perfetto: one counter track sample next to the request spans
        counters = [e for e in telemetry.chrome_events()
                    if e.get("ph") == "C"]
        assert counters and counters[-1]["args"][
            "device0_bytes_in_use"] == 123.0

    def test_rate_limit_caches_between_samples(self):
        dev = _FakeDev()
        first = telemetry.sample_device_stats(min_interval_s=100,
                                              devices=[dev])
        again = telemetry.sample_device_stats(min_interval_s=100,
                                              devices=[dev])
        assert dev.calls == 1
        assert again == first

    def test_cpu_backend_is_null_safe(self):
        # the real CPU device has no memory_stats -> silently empty
        assert telemetry.sample_device_stats(min_interval_s=0) == {}

    def test_serving_async_parity_with_hbm_sampling(self, tiny_model,
                                                    monkeypatch):
        """The PR-1/PR-4 pin, re-asserted with the HBM sampler live on
        every gauge update: sampling is a host-side stats read and must
        not perturb scheduling — async and sync ticks stay
        bit-identical."""
        monkeypatch.setenv("PADDLE_TPU_HBM_SAMPLE_MS", "0")
        fake = _FakeDev()
        real = monitor.snapshot_device_stats
        calls = []
        monkeypatch.setattr(
            monitor, "snapshot_device_stats",
            lambda devices=None: (calls.append(1),
                                  real(devices=[fake]))[1])

        def serve(async_):
            srv = serving.DecodeServer(tiny_model[1], tiny_model[0],
                                       max_batch=2, max_len=16,
                                       async_dispatch=async_)
            prompts = np.random.default_rng(0).integers(1, 60, (3, 4))
            rids = [srv.submit(p, max_new_tokens=5) for p in prompts]
            while srv.pending():
                srv.tick()
            return [srv.result(r) for r in rids]

        sync_toks = serve(False)
        async_toks = serve(True)
        assert sync_toks == async_toks
        assert calls, "HBM sampler never ran on the serving hot path"
        assert telemetry.snapshot()["gauges"][
            "device.device0_bytes_in_use"] == 123

    def test_fit_zero_host_sync_pin_with_device_feed(self, monkeypatch):
        """The PR-2 invariant re-pinned with the FULL device feed on:
        analysis capture + HBM sampling + step-time notes add zero
        _host_scalar drains to a steady-state async epoch."""
        monkeypatch.setenv("PADDLE_TPU_HBM_SAMPLE_MS", "0")
        drains = []
        real = hapi_model._host_scalar
        monkeypatch.setattr(hapi_model, "_host_scalar",
                            lambda x: (drains.append(1), real(x))[1])

        def fit_steps(n):
            drains.clear()
            X = np.random.default_rng(0).standard_normal(
                (n, 8)).astype(np.float32)
            Y = np.random.default_rng(0).integers(0, 4, n).astype(np.int64)
            paddle.seed(0)
            net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(),
                                nn.Linear(16, 4))
            m = Model(net)
            m.prepare(paddle.optimizer.Adam(
                1e-2, parameters=net.parameters()), F.cross_entropy,
                async_metrics=True)
            m.fit((X, Y), batch_size=8, epochs=1, verbose=0,
                  shuffle=False, log_freq=0)
            return len(drains)

        assert telemetry.enabled()
        assert fit_steps(32) == fit_steps(128) == 1
        # the fit loop feeds the TrainStep's honest per-step wall — the
        # epoch-1 note is deliberately discarded (it overlaps the step's
        # compile), so a 2-epoch fit is the first recorded sample
        X = np.random.default_rng(0).standard_normal(
            (32, 8)).astype(np.float32)
        Y = np.random.default_rng(0).integers(0, 4, 32).astype(np.int64)
        paddle.seed(0)
        net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(),
                            nn.Linear(16, 4))
        m = Model(net)
        m.prepare(paddle.optimizer.Adam(
            1e-2, parameters=net.parameters()), F.cross_entropy,
            async_metrics=True)
        m.fit((X, Y), batch_size=8, epochs=2, verbose=0, shuffle=False,
              log_freq=0)
        with telemetry._device_lock:
            assert "jit.TrainStep" in telemetry._step_times


class TestEndpoints:
    def test_healthz_endpoint(self):
        """The in-process watchdog is the one wedge authority: 200 until
        it says a live step blew its budget, 503 (status-code signaling
        for k8s-style probes that never read the body) while it stands."""
        import urllib.error

        _instrumented_matmul("t.healthz")
        ms = telemetry.serve_metrics(0)
        url = f"http://127.0.0.1:{ms.port}/healthz"
        try:
            h = json.load(urllib.request.urlopen(url))
            assert h["ok"] is True and "probe" not in h
            assert h["runtime_wedge"]["wedged"] is False
            assert h["telemetry_enabled"] and h["device_feed_enabled"]
            assert "t.healthz" in h["instrumented_steps"]
            telemetry.set_runtime_wedge("tick blew its wall budget")
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(url)
            assert ei.value.code == 503
            h = json.load(ei.value)
            assert h["ok"] is False
            assert h["runtime_wedge"]["reason"].startswith("tick blew")
            telemetry.clear_runtime_wedge()
            assert json.load(urllib.request.urlopen(url))["ok"] is True
        finally:
            telemetry.clear_runtime_wedge()
            ms.close()

    def test_profile_capture_function(self, tmp_path):
        out = telemetry.capture_device_profile(
            30, str(tmp_path / "trace"))
        files = [os.path.join(r, f) for r, _, fs in os.walk(out)
                 for f in fs]
        assert files, "profiler trace dir is empty"
        with pytest.raises(ValueError):
            telemetry.capture_device_profile(0)

    def test_profile_endpoint_around_live_traffic(self, tiny_model,
                                                  tmp_path, monkeypatch):
        # the endpoint never honors a client-chosen dir (unauthenticated
        # write primitive); the server-side env var picks the target
        monkeypatch.setenv("PADDLE_TPU_PROFILE_DIR",
                           str(tmp_path / "htrace"))
        ms = telemetry.serve_metrics(0)
        try:
            req = urllib.request.Request(
                f"http://127.0.0.1:{ms.port}/profile?ms=30"
                f"&dir={tmp_path / 'attacker'}", method="POST")
            # traffic keeps flowing while the capture window is open
            srv = serving.DecodeServer(tiny_model[1], tiny_model[0],
                                       max_batch=2, max_len=16)
            srv.submit([3, 5], max_new_tokens=3)
            resp = json.load(urllib.request.urlopen(req))
            while srv.pending():
                srv.tick()
        finally:
            ms.close()
        assert resp["ms"] == 30.0
        assert resp["trace_dir"] == str(tmp_path / "htrace")
        assert not (tmp_path / "attacker").exists()  # dir param ignored
        assert any(fs for _, _, fs in os.walk(resp["trace_dir"]))

