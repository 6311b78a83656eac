"""Profiler: host events + device trace (XPlane) + chrome-trace export.

Reference capability: platform/profiler.{h,cc} — ``RecordEvent`` RAII
(profiler.h:127), EnableProfiler/DisableProfiler (:213) with table report and
chrome-trace export (profiler.proto); CUPTI device correlation
(platform/device_tracer.cc); Python surface fluid/profiler.py:190-314.

TPU-native: device-side tracing IS ``jax.profiler`` (XPlane, viewable in
TensorBoard/Perfetto — the CUPTI role is played by the TPU runtime itself);
``RecordEvent`` enters the same ``TraceAnnotation`` helper as
``telemetry.span`` so host spans land in the same timeline, and a lightweight
host-event table + chrome-trace JSON covers the report/export surface.
"""
from __future__ import annotations

import functools
import json
import os
import threading
import time
from collections import defaultdict

from . import telemetry as _telemetry

_state = threading.local()
_events: list = []  # (name, start_s, stop_s, thread_id)
_events_lock = threading.Lock()
_enabled = False
_trace_dir: str | None = None


class RecordEvent:
    """Context manager / decorator naming a host span (profiler.h:127).

    Re-entrant and thread-safe: one shared instance may be entered
    concurrently from several threads (or nested in one) — per-thread
    span state lives in a thread-local STACK, so every ``__enter__``
    gets its own ``t0``/annotation instead of clobbering a sibling's."""

    def __init__(self, name: str):
        self.name = name
        self._tls = threading.local()

    def _stack(self):
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def __enter__(self):
        t0 = time.perf_counter()
        try:
            ann = _telemetry._annotation(self.name)
            ann.__enter__()
        except Exception:
            ann = None
        self._stack().append((t0, ann))
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        t0, ann = self._stack().pop()
        if ann is not None:
            ann.__exit__(*exc)
        if _enabled:
            with _events_lock:
                _events.append((self.name, t0, t1,
                                threading.get_ident()))
        return False

    def __call__(self, fn):
        @functools.wraps(fn)
        def wrapped(*a, **k):
            with self:
                return fn(*a, **k)

        return wrapped


def start_profiler(log_dir: str | None = None, tracer_option: str = "Default"):
    """EnableProfiler analog; with log_dir also starts the device XPlane
    trace (jax.profiler.start_trace → TensorBoard 'profile' plugin)."""
    global _enabled, _trace_dir
    with _events_lock:
        _events.clear()
    _enabled = True
    if log_dir is not None:
        import jax

        _trace_dir = log_dir
        jax.profiler.start_trace(log_dir)


def stop_profiler(sorted_key: str = "total", profile_path: str | None = None):
    """DisableProfiler analog: stops tracing, prints the host-span table,
    optionally writes chrome://tracing JSON to profile_path."""
    global _enabled, _trace_dir
    _enabled = False
    if _trace_dir is not None:
        import jax

        jax.profiler.stop_trace()
        _trace_dir = None
    with _events_lock:
        evts = list(_events)
    if profile_path:
        _write_chrome_trace(evts, profile_path)
    return summary(evts, sorted_key)


class profiler:
    """``with paddle.profiler.profiler(log_dir):`` context (fluid/profiler.py:314)."""

    def __init__(self, log_dir=None, profile_path=None):
        self.log_dir, self.profile_path = log_dir, profile_path

    def __enter__(self):
        start_profiler(self.log_dir)
        return self

    def __exit__(self, *exc):
        self.report = stop_profiler(profile_path=self.profile_path)
        return False


def capture_device_trace(ms: float = 500.0,
                         log_dir: str | None = None) -> str:
    """On-demand device-trace capture (the fluid-profiler-shaped entry
    to the telemetry layer's ``capture_device_profile``): start a
    ``jax.profiler`` XPlane trace, let ``ms`` milliseconds of live
    traffic run, stop, and return the trace dir.  The same capture the
    metrics endpoint serves as ``POST /profile?ms=...`` — the reference
    enabled its CUPTI device tracer this way (EnableProfiler around a
    window of work)."""
    return _telemetry.capture_device_profile(ms, log_dir)


def host_events() -> list:
    """Snapshot of the recorded host spans as (name, t0, t1, tid) tuples
    (``time.perf_counter`` seconds) — the telemetry layer merges these
    with its request-lifecycle spans into one chrome-trace timeline
    (``telemetry.dump_chrome_trace``)."""
    with _events_lock:
        return list(_events)


def summary(evts=None, sorted_key: str = "total"):
    """Aggregate host spans into the reference's profiler table shape."""
    if evts is None:
        with _events_lock:
            evts = list(_events)
    agg: dict = defaultdict(lambda: {"calls": 0, "total": 0.0, "max": 0.0})
    for name, t0, t1, _tid in evts:
        a = agg[name]
        a["calls"] += 1
        a["total"] += t1 - t0
        a["max"] = max(a["max"], t1 - t0)
    rows = [{"name": k, **v, "avg": v["total"] / max(v["calls"], 1)}
            for k, v in agg.items()]
    rows.sort(key=lambda r: r.get(sorted_key, r["total"]), reverse=True)
    return rows


def _write_chrome_trace(evts, path: str):
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    tr = [{"name": n, "ph": "X", "pid": 0, "tid": tid,
           "ts": t0 * 1e6, "dur": (t1 - t0) * 1e6}
          for n, t0, t1, tid in evts]
    with open(path, "w") as f:
        json.dump({"traceEvents": tr}, f)
