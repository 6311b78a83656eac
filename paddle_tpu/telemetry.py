"""Runtime telemetry: one structured observability layer with three feeds.

Reference capability: platform/profiler.cc ``RecordEvent`` + chrome-trace
export and platform/monitor.h ``StatRegistry`` give the reference a
profiler/monitor surface; serving-systems work (Orca, vLLM — PAPERS.md)
treats per-request TTFT/TPOT percentiles and cache-occupancy gauges as the
first-class product metric.  This module is the TPU-native equivalent,
built on the seeds in :mod:`paddle_tpu.profiler` (host spans) and
:mod:`paddle_tpu.framework.monitor` (StatRegistry):

1. **Serving request tracing** — every ``DecodeServer`` submit→retire
   lifecycle records queue-wait / TTFT / per-token / end-to-end latency
   into streaming histograms (fixed log-spaced buckets, O(1) memory) plus
   batch-slot / KV-cache / queue-depth gauges, sampled from host values
   the server already fetched (no extra device syncs).  Speculative
   serving adds the ``spec.*`` counter family — ``spec.proposed`` /
   ``spec.accepted`` / ``spec.fallbacks`` (plus ``spec.draft_steps`` and
   the self-draft ``spec.ngram_hits``/``spec.ngram_misses``) — and the
   per-server ``serving.spec_accept_rate`` gauge; all auto-export to
   :func:`snapshot`/:func:`render_prometheus` like every registry stat,
   and ``tools/check_instrumented.py`` lints that every spec
   accept/reject/fallback path counts or delegates.  Draft-TREE
   speculation (round 17) extends the family: ``spec.tree_rounds``
   (tree-masked verify passes), ``spec.tree_nodes_proposed`` /
   ``spec.tree_nodes_accepted`` (token-bearing nodes dispatched vs
   root-to-leaf edges committed — their ratio is the tree's acceptance
   efficiency), ``spec.tree_pruned_constrained`` (grammar-forbidden
   branches a constrained slot's DFA lookahead removed BEFORE the
   verify pass — the mechanism that keeps ``constraint.spec_fallbacks``
   at zero for constrained workloads), and ``spec.reearns`` (fallen-
   back slots that re-entered speculation after the doubling cooldown);
   the per-server ``serving.spec_tree_accept_len`` gauge (mean accepted
   path length per round) rides ``load_stats()`` and the Prometheus
   export, and the same lint covers every
   ``*tree_propose*``/``*tree_accept*``/``*prune_branch*`` path.
   The fleet-scale
   prefix cache adds its own family: ``kv_pool.radix_splits`` (no-copy
   radix node splits on partial-block prompt overlap),
   ``kv_pool.spilled_blocks`` / ``kv_pool.restored_blocks`` /
   ``kv_pool.restore_drains`` (host-RAM spill tier traffic),
   ``kv_pool.prefix_evictions`` (cold-leaf drops, spilled or not), and
   ``fleet.prefix_routed`` (dispatches where prefix affinity — not the
   load triple — picked the replica); gauges
   ``kv_pool.prefix_hit_rate`` (token-granular: adopted rows over
   adoptable rows) and ``kv_pool.host_spill_bytes`` (resident host
   bytes held by the spill tier) ride ``load_stats()`` and the
   Prometheus export, and the same lint requires every
   ``*split*``/``*spill*``/``*restore*``/``*prefix_route*`` path in
   kv_pool/fleet to count or delegate.  The elastic-fleet streaming
   transport (round 18) adds the STREAM family:
   ``fleet.stream_chunks`` / ``fleet.stream_bytes`` (raw KV chunk
   frames a prefill worker shipped, and their payload bytes),
   ``fleet.stream_aborts`` (half-streamed handoffs torn down on worker
   death / TTL / replica removal), ``fleet.scale_outs`` /
   ``fleet.scale_ins`` (autoscale topology moves; ``fleet.replicas``
   gauges LIVE replicas), ``fleet.replica_adds`` /
   ``fleet.replica_removes`` (every live attach/detach, autoscaled or
   operator-driven), and ``kv_pool.chain_migrations`` /
   ``kv_pool.chain_migrations_out`` (spilled prefix chains adopted
   from / shipped to another replica over the raw transport); the lint
   covers every ``*stream*``/``*scale_out*``/``*scale_in*``/
   ``*migrate*`` path in fleet/kv_pool and bans ``pickle.`` call sites
   in fleet.py outright.
2. **Training step telemetry** — ``Model.fit`` / ``TrainStep`` emit
   step-time and throughput histograms, and the fit loop's host-sync
   count lands in the shared counter registry via the
   ``hapi.model._host_scalar`` choke point.
3. **Recompile watch** — every jit-cache miss funnels through
   :func:`instrument_compile`, which records (fn name, cfg/flags key,
   wall time) on the executable's first call and raises a rate-limited
   ``RuntimeWarning`` with the key diff when the flags portion of a key
   flips mid-process (the ``flags.decode_jit_key`` /
   ``flags.train_step_key`` retrace discipline, made observable).

Export surface: :func:`snapshot` (JSON dict with quantiles),
:func:`render_prometheus` (+ :func:`serve_metrics` HTTP endpoint, wired
as ``DecodeServer(metrics_port=...)``), a JSONL event log
(``PADDLE_TPU_TELEMETRY_LOG=<path>``), and :func:`dump_chrome_trace`
merging request-lifecycle spans with :mod:`paddle_tpu.profiler` host
events into one Perfetto-loadable timeline (``tools/merge_timeline.py``
folds in ``jax.profiler`` device traces).

All hot-path work is lock-cheap counters/bucket increments;
``PADDLE_TPU_TELEMETRY=0`` turns every record call into an early-out
no-op (and :func:`instrument_compile` returns the raw executable).
"""
from __future__ import annotations

import bisect
import contextlib
import functools
import itertools
import json
import math
import os
import re
import threading
import time
import warnings
from collections import deque

from . import flags as _flags
from .framework import monitor as _monitor

__all__ = [
    "enabled", "reset", "hist", "gauge", "observe", "set_gauge", "count",
    "event", "span", "events", "NO_SPAN", "record_compile",
    "instrument_compile", "executable_scopes", "hlo_op_scopes", "snapshot",
    "latency_summary", "render_prometheus", "serve_metrics",
    "chrome_events", "dump_chrome_trace", "Histogram", "Gauge",
    "MetricsServer", "note_step_time", "sample_device_stats",
    "device_feed", "capture_device_profile",
    "set_runtime_wedge", "clear_runtime_wedge", "runtime_wedge",
    "quantile_from_counts", "SpanRing", "mint_trace", "spans_to_chrome",
]


def enabled() -> bool:
    """Master switch (re-read per call so tests can flip the env)."""
    return _flags.telemetry_enabled()


# ---------------------------------------------------------------------------
# streaming metrics: histogram / gauge / counter
# ---------------------------------------------------------------------------

# Fixed log-spaced bucket bounds shared by every histogram: 20 buckets per
# decade from 1e-3 to 1e7 (unit-agnostic; in ms that spans 1 µs .. ~3 h).
# O(1) memory per histogram regardless of sample count, and quantiles
# interpolate to within one bucket ratio (10^(1/20) ≈ 12% worst case).
_BOUNDS: tuple = tuple(10.0 ** (i / 20.0) for i in range(-60, 141))


class Histogram:
    """Streaming latency histogram: fixed log-spaced buckets, O(1) memory,
    lock-cheap ``observe``, Prometheus-compatible cumulative export."""

    __slots__ = ("name", "_counts", "_count", "_sum", "_min", "_max",
                 "_lock")

    def __init__(self, name: str):
        self.name = name
        self._counts = [0] * (len(_BOUNDS) + 1)  # last = overflow
        self._count = 0
        self._sum = 0.0
        self._min = math.inf
        self._max = -math.inf
        self._lock = threading.Lock()

    def observe(self, value: float, n: int = 1) -> None:
        """Record ``n`` observations of ``value`` (n > 1 folds a batch of
        identical-latency samples — e.g. one block tick's tokens — in one
        lock acquisition)."""
        v = float(value)
        i = bisect.bisect_left(_BOUNDS, v) if v > 0.0 else 0
        with self._lock:
            self._counts[i] += n
            self._count += n
            self._sum += v * n
            if v < self._min:
                self._min = v
            if v > self._max:
                self._max = v

    def quantile(self, q: float) -> float:
        """Interpolated quantile from the bucket counts (the
        histogram_quantile rule: linear within the containing bucket,
        clamped to the observed min/max)."""
        with self._lock:
            total = self._count
            if total == 0:
                return 0.0
            counts = list(self._counts)
            lo_obs, hi_obs = self._min, self._max
        rank = q * total
        cum = 0.0
        for i, c in enumerate(counts):
            if c == 0:
                continue
            if cum + c >= rank:
                lo = _BOUNDS[i - 1] if 0 < i <= len(_BOUNDS) else 0.0
                hi = _BOUNDS[i] if i < len(_BOUNDS) else hi_obs
                frac = (rank - cum) / c
                v = lo + (hi - lo) * frac
                return min(max(v, lo_obs), hi_obs)
            cum += c
        return hi_obs

    def summary(self) -> dict:
        with self._lock:
            n, s = self._count, self._sum
            mn = self._min if n else 0.0
            mx = self._max if n else 0.0
        return {"count": n, "sum": round(s, 6), "avg": round(s / n, 6)
                if n else 0.0, "min": round(mn, 6), "max": round(mx, 6),
                "p50": round(self.quantile(0.50), 6),
                "p90": round(self.quantile(0.90), 6),
                "p99": round(self.quantile(0.99), 6)}

    def raw_counts(self) -> list:
        """A consistent copy of the raw per-bucket counts (cumulative
        since process start).  Consumers that need a WINDOWED
        distribution — the admission controller's SLO verdicts — keep
        the previous copy and feed the elementwise delta to
        :func:`quantile_from_counts`; the histogram itself stays O(1)
        and never resets under a live scrape."""
        with self._lock:
            return list(self._counts)

    def buckets(self):
        """(upper_bound, cumulative_count) pairs for Prometheus exposition
        — only bounds where the cumulative count changes, plus +Inf (a
        subset of ``le`` values is valid exposition and keeps the text
        small)."""
        with self._lock:
            counts = list(self._counts)
        out, cum = [], 0
        for i, c in enumerate(counts[:-1]):
            if c:
                cum += c
                out.append((_BOUNDS[i], cum))
        out.append((math.inf, cum + counts[-1]))
        return out

    def state(self) -> dict:
        """JSON-safe serialized form (raw bucket counts + count/sum +
        observed extremes) — the wire shape replicas ship so a router can
        :meth:`merge` distributions without the samples."""
        with self._lock:
            return {"counts": list(self._counts), "count": self._count,
                    "sum": self._sum,
                    "min": self._min if self._count else None,
                    "max": self._max if self._count else None}

    def merge(self, other) -> "Histogram":
        """Fold another histogram — or a :meth:`state` dict shipped over
        the wire — into this one by exact bucket-count addition.  Every
        histogram shares the fixed ``_BOUNDS`` ladder, so the merge is
        LOSSLESS: quantiles of the merged histogram equal quantiles of
        the concatenated samples to within one bucket width.  Returns
        ``self`` so folds chain."""
        st = other.state() if isinstance(other, Histogram) else other
        counts = st["counts"]
        with self._lock:
            if len(counts) != len(self._counts):
                raise ValueError(
                    f"bucket ladder mismatch: {len(counts)} vs "
                    f"{len(self._counts)}")
            for i, c in enumerate(counts):
                self._counts[i] += int(c)
            self._count += int(st["count"])
            self._sum += float(st["sum"])
            if st.get("min") is not None and float(st["min"]) < self._min:
                self._min = float(st["min"])
            if st.get("max") is not None and float(st["max"]) > self._max:
                self._max = float(st["max"])
        return self


def quantile_from_counts(counts, q: float) -> float:
    """Interpolated quantile over a RAW bucket-count vector (the
    :meth:`Histogram.raw_counts` shape — typically a delta between two
    snapshots, i.e. a windowed distribution).  Same interpolation rule
    as :meth:`Histogram.quantile`, minus the observed min/max clamp
    (per-window extremes are not tracked); 0.0 on an empty window."""
    total = sum(counts)
    if total <= 0:
        return 0.0
    rank = q * total
    cum = 0.0
    for i, c in enumerate(counts):
        if c <= 0:
            continue
        if cum + c >= rank:
            lo = _BOUNDS[i - 1] if 0 < i <= len(_BOUNDS) else 0.0
            hi = _BOUNDS[i] if i < len(_BOUNDS) else _BOUNDS[-1]
            return lo + (hi - lo) * ((rank - cum) / c)
        cum += c
    return _BOUNDS[-1]


class Gauge:
    """Last-write-wins float gauge."""

    __slots__ = ("name", "_v", "_lock")

    def __init__(self, name: str):
        self.name = name
        self._v = 0.0
        self._lock = threading.Lock()

    def set(self, v: float) -> None:
        with self._lock:
            self._v = float(v)

    def add(self, v: float) -> None:
        with self._lock:
            self._v += float(v)

    def get(self) -> float:
        with self._lock:
            return self._v


# ---------------------------------------------------------------------------
# the registry: histograms + gauges here, counters in framework.monitor
# ---------------------------------------------------------------------------

def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, default))
    except ValueError:
        return default


_lock = threading.Lock()
_hists: dict[str, Histogram] = {}
_gauges: dict[str, Gauge] = {}
_events: deque = deque(maxlen=_env_int("PADDLE_TPU_TELEMETRY_EVENTS",
                                       65536))
_log_lock = threading.Lock()  # JSONL I/O only — never blocks recording
_log_fh = None
_log_path: str | None = None
_counter_names: set[str] = set()

# ---------------------------------------------------------------------------
# device feed state: per-executable cost/memory analyses + step-time EWMAs
# ---------------------------------------------------------------------------
# Analyses are COMPILE-TIME facts captured once per jit-cache miss; they
# share the lifetime of the compiled executables (which reset() does not
# drop either — the instrument wrappers never re-capture), so reset()
# clears only the measurement state (_step_times / _hbm_last).
_device_lock = threading.Lock()
_step_analysis: dict[str, dict] = {}      # instrument name -> analysis
_step_times: dict[str, dict] = {}         # instrument name -> ewma state
# names whose NEXT noted wall overlapped the compiling first call — that
# wall is compile-dominated and must not seed the step-time EWMA (a
# bucket hit exactly once would otherwise export a ~100x-low MFU forever)
_skip_first_wall: set = set()
_device_info: dict = {}                   # platform/device_kind, jax live
_hbm_last: dict = {}                      # last sample_device_stats result
_hbm_state = {"t": 0.0}
# EWMA weight for step walls: ~last 8 calls dominate — responsive to a
# batch-size change without one cold outlier owning the gauge
_STEP_EWMA_ALPHA = 0.25

# recompile watch state: per (name, flagless key) the last-seen flags key
_compile_lock = threading.Lock()
_compile_seen: dict[tuple, tuple] = {}
# ring like _events: a model-cycling server recompiles forever — the log
# must not grow with it
_compile_log: deque = deque(maxlen=_env_int(
    "PADDLE_TPU_TELEMETRY_COMPILES", 4096))
_warn_last: dict[str, float] = {}
# rate limit: at most one recompile warning per fn name per interval
# (module-level so tests can shrink it)
_WARN_INTERVAL_S = 30.0

# ---------------------------------------------------------------------------
# runtime wedge state: the resilience watchdog's live verdict
# ---------------------------------------------------------------------------
# The serving loop's own watchdog saying an in-process step blew its wall
# budget — /healthz answers 503 while it stands.
# State lives here (not in resilience.py) so the HTTP handler needs no
# import cycle: resilience -> telemetry only.
_runtime_wedge_lock = threading.Lock()
_runtime_wedge: dict = {"wedged": False, "reason": None, "since": None,
                        "detections": 0, "recoveries": 0}


def set_runtime_wedge(reason: str) -> None:
    """Mark the process wedged (watchdog verdict) — /healthz answers 503
    until :func:`clear_runtime_wedge`."""
    with _runtime_wedge_lock:
        _runtime_wedge["wedged"] = True
        _runtime_wedge["reason"] = str(reason)
        _runtime_wedge["since"] = time.time()
        _runtime_wedge["detections"] += 1


def clear_runtime_wedge() -> None:
    """The loop recovered (a full step completed after a wedge) —
    /healthz flips back to ok."""
    with _runtime_wedge_lock:
        if _runtime_wedge["wedged"]:
            _runtime_wedge["recoveries"] += 1
        _runtime_wedge["wedged"] = False
        _runtime_wedge["reason"] = None
        _runtime_wedge["since"] = None


def runtime_wedge() -> dict:
    with _runtime_wedge_lock:
        return dict(_runtime_wedge)


def hist(name: str) -> Histogram:
    h = _hists.get(name)
    if h is None:
        with _lock:
            h = _hists.setdefault(name, Histogram(name))
    return h


def gauge(name: str) -> Gauge:
    g = _gauges.get(name)
    if g is None:
        with _lock:
            g = _gauges.setdefault(name, Gauge(name))
    return g


def observe(name: str, value: float, n: int = 1) -> None:
    if not enabled():
        return
    hist(name).observe(value, n)


def set_gauge(name: str, value: float) -> None:
    if not enabled():
        return
    gauge(name).set(value)


def count(name: str, n: int = 1) -> None:
    """Counter feed — lands in the SAME registry the reference's monitor
    surface reads (``framework.monitor.StatRegistry``), so one
    ``monitor.stats()`` call observes telemetry counters next to the
    existing runtime counters."""
    if not enabled():
        return
    if name not in _counter_names:  # steady state: no lock, no add
        with _lock:
            _counter_names.add(name)
    _monitor.get_stat(name).add(n)


def admission_snapshot() -> dict:
    """Every ``admission.*`` gauge and counter currently registered
    (rung, budget level, per-class sheds, tenant throttles, ...), as one
    flat dict — the ``/healthz`` admission block and the fleet router's
    health aggregation both read it here so the name set can't diverge
    between the two."""
    out = {}
    with _lock:
        gauges = [(n, g) for n, g in _gauges.items()
                  if n.startswith("admission.")]
        counters = [n for n in _counter_names if n.startswith("admission.")]
    for n, g in gauges:
        out[n] = g.get()
    for n in counters:
        out[n] = _monitor.get_stat(n).get()
    return out


def reset() -> None:
    """Drop every histogram/gauge/event/compile record and this module's
    counters (tests and the benchmark isolate their windows with this).
    The rest of the monitor registry is left alone."""
    global _log_fh, _log_path
    with _lock:
        _hists.clear()
        _gauges.clear()
        _events.clear()
        for n in _counter_names:
            _monitor.get_stat(n).reset()
        _counter_names.clear()
    with _log_lock:
        if _log_fh is not None:
            with contextlib.suppress(Exception):
                _log_fh.close()
        _log_fh = None
        _log_path = None
    with _compile_lock:
        _compile_seen.clear()
        _compile_log.clear()
        _warn_last.clear()
    with _device_lock:
        # measurement state only: the captured cost/memory analyses are
        # compile-time facts tied to executables reset() doesn't drop
        # (the instrument wrappers capture exactly once) — clearing them
        # would leave the device feed permanently dark after a reset
        _step_times.clear()
        _skip_first_wall.clear()
        _hbm_last.clear()
        _hbm_state["t"] = 0.0


# ---------------------------------------------------------------------------
# spans / events: ring buffer + JSONL log + chrome-trace export
# ---------------------------------------------------------------------------


def _jsonl_write(rec: dict) -> None:
    global _log_fh, _log_path
    path = _flags.telemetry_log()
    if not path:
        return
    # dedicated lock: a slow flush must stall only other log writers,
    # never the lock-cheap metric recording or a /metrics scrape
    with _log_lock:
        if _log_fh is None or _log_path != path:
            if _log_fh is not None:
                with contextlib.suppress(Exception):
                    _log_fh.close()
            d = os.path.dirname(os.path.abspath(path))
            os.makedirs(d, exist_ok=True)
            _log_fh = open(path, "a", encoding="utf-8")
            _log_path = path
        _log_fh.write(json.dumps(rec) + "\n")
        _log_fh.flush()


# One span primitive.  A span has an id, the id of the span that caused it
# (the innermost span open on the same thread) and, where it belongs to a
# request, ``rid`` among its args (inherited from the enclosing span when
# not given).  ``span`` also enters a ``jax.profiler.TraceAnnotation`` with
# the same name, id and parent, so the span is in the profiler's trace on
# the clock the device planes share; ``event`` records a span that is
# already over (a request's lifetime), ring only.
_span_ids = itertools.count(1)          # next() is atomic under the GIL
_open = threading.local()               # .stack: [(span id, rid)] per thread
_TraceAnnotation = None                 # jax's class, looked up once
_SCALARS = (str, int, float, bool)


def _stack() -> list:
    st = getattr(_open, "stack", None)
    if st is None:
        st = _open.stack = []
    return st


def _caused_by(args: dict):
    """The id of the span open on this thread (None: none); ``args``
    inherits its ``rid`` unless it brings its own."""
    st = _stack()
    if not st:
        return None
    parent, rid = st[-1]
    if rid is not None:
        args.setdefault("rid", rid)
    return parent


def _annotation(name: str, **args):
    """The one place a ``TraceAnnotation`` is constructed: a host span in
    the profiler's own trace (its keyword arguments arrive as the event's
    stats).  With no profile session it costs one Python call."""
    global _TraceAnnotation
    if _TraceAnnotation is None:
        import jax

        _TraceAnnotation = jax.profiler.TraceAnnotation
    return _TraceAnnotation(name, **args)


def _record(name: str, t0: float, t1: float, tid: int, sid: int,
            parent, args: dict) -> None:
    rec = {"name": name, "t0": t0, "t1": t1, "tid": int(tid), "id": sid}
    if parent is not None:
        rec["parent"] = parent
    if args:
        rec["args"] = args
    with _lock:
        _events.append(rec)
    _jsonl_write(rec)


def event(name: str, t0: float, t1: float, tid: int = 0, **args) -> None:
    """Record a completed host span [t0, t1] (``time.perf_counter``
    seconds — the same clock profiler.py stamps, so the two event streams
    merge onto one timeline) as a child of the span open on this thread.
    Ring-buffered in memory, appended to the ``PADDLE_TPU_TELEMETRY_LOG``
    JSONL when set."""
    if not enabled():
        return
    _record(name, t0, t1, tid, next(_span_ids), _caused_by(args), args)


def _counter_event(name: str, values: dict) -> None:
    """Record a Perfetto COUNTER sample (chrome 'C' phase): the HBM
    gauges land on the merged timeline as counter tracks next to the
    request spans.  Same ring buffer + JSONL sinks as :func:`event`;
    consumers that only understand spans skip these (no t0/t1)."""
    if not enabled() or not values:
        return
    rec = {"name": name, "ph": "C", "t": time.perf_counter(),
           "args": {k: float(v) for k, v in values.items()}}
    with _lock:
        _events.append(rec)
    _jsonl_write(rec)


class _Span:
    """An open span (what ``with telemetry.span(...) as sp`` yields).
    ``sp.args`` may be filled until the span closes: what is known only
    at the end (the step kind a tick dispatched) reaches the ring; the
    profiler's annotation carries what was known on entry."""

    __slots__ = ("name", "tid", "args", "id", "parent", "t0", "_ann")

    def __init__(self, name: str, tid: int, args: dict):
        self.name, self.tid, self.args = name, tid, args

    def __enter__(self):
        args = self.args
        self.parent = _caused_by(args)
        self.id = next(_span_ids)
        _stack().append((self.id, args.get("rid")))
        self._ann = _annotation(
            self.name, span_id=self.id, parent=self.parent or 0,
            **{k: v for k, v in args.items() if isinstance(v, _SCALARS)})
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        _stack().pop()
        _record(self.name, self.t0, t1, self.tid, self.id, self.parent,
                self.args)
        return False


class _NoSpan:
    """``span`` with telemetry off: nothing is stamped, entered or kept."""

    __slots__ = ()
    args: dict = {}                      # writes to it are never read

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NO_SPAN = _NoSpan()


def span(name: str, tid: int = 0, **args):
    """``with telemetry.span("serving.tick", rid=3) as sp: ...`` — the
    one way the program records a host span: into the ring on exit, and
    into the profiler's trace (a no-op when telemetry is disabled)."""
    if not enabled():
        return NO_SPAN
    return _Span(name, tid, args)


def events() -> list:
    """The ring's raw records, oldest first: spans as ``{"name", "t0",
    "t1", "tid", "id"[, "parent"][, "args"]}`` in ``time.perf_counter``
    seconds, counter samples as ``{"name", "ph": "C", "t", "args"}``."""
    with _lock:
        return list(_events)


def chrome_events(pid: int = 1, shift: float = 0.0) -> list:
    """The ring buffer as chrome://tracing 'X' events (µs timestamps).
    ``shift`` (seconds) is added to every timestamp — pass the
    perf_counter→wall offset to co-display this perf-clock ring beside
    the wall-clock fleet span tracks in one timeline."""
    out = [{"name": "process_name", "ph": "M", "pid": pid,
            "args": {"name": "paddle_tpu.telemetry"}}]
    with _lock:
        events = list(_events)
    for e in events:
        if e.get("ph") == "C":  # counter sample (HBM gauges)
            out.append({"name": e["name"], "ph": "C", "pid": pid,
                        "tid": 0, "ts": (e["t"] + shift) * 1e6,
                        "args": e.get("args", {})})
            continue
        ev = {"name": e["name"], "ph": "X", "pid": pid, "tid": e["tid"],
              "ts": (e["t0"] + shift) * 1e6,
              "dur": (e["t1"] - e["t0"]) * 1e6}
        args = dict(e.get("args", ()), span_id=e["id"])
        if "parent" in e:
            args["parent"] = e["parent"]
        ev["args"] = args
        out.append(ev)
    return out


# ---------------------------------------------------------------------------
# fleet tracing: trace contexts + per-entity span rings
# ---------------------------------------------------------------------------
# A trace context is a tiny JSON-safe dict of scalars ({"trace_id": ...},
# optionally {"parent": ...}) minted once at Router.submit and carried on
# the request dict — it rides the raw-row transport's JSON header frame,
# adopt_request's dict() copies, and the spill/migrate codec without any
# wire-format change.  Each process-side entity (a DecodeServer replica, a
# PrefillWorker, the Router itself) records completed spans into its own
# bounded SpanRing; remote rings are drained onto existing reply/stats
# messages and reassembled by the Router into one wall-clock timeline.

_trace_lock = threading.Lock()
_trace_seq = [0]


def mint_trace(parent=None):
    """Mint a fleet trace context: a JSON-safe ``{"trace_id": ...}`` dict
    (plus ``parent`` when nesting spans) unique across the processes of
    one fleet run (pid + per-process sequence + wall-ms).  Returns
    ``None`` when telemetry is disabled — no key is ever attached to the
    request dict, so the ``PADDLE_TPU_TELEMETRY=0`` path is bit-identical
    by construction.  ``PADDLE_TPU_TRACE=0`` turns off just the tracing
    plane while the metrics plane keeps running."""
    if not enabled() or not _flags.trace_enabled():
        return None
    with _trace_lock:
        _trace_seq[0] += 1
        seq = _trace_seq[0]
    tid = (f"{os.getpid():x}-{seq:x}-"
           f"{int(time.time() * 1e3) & 0xFFFFFFFF:x}")
    ctx = {"trace_id": tid}
    if parent is not None:
        ctx["parent"] = parent
    return ctx


class SpanRing:
    """Bounded buffer of COMPLETED trace spans for one entity (replica /
    prefill worker / router track).  Spans are stamped in WALL-CLOCK
    seconds (``time.time``) so rings collected from different processes
    assemble onto one timeline — the perf_counter inputs every call site
    already holds are shifted by the clock offset measured at record
    time (µs-level error, zero new stamps on the hot path).  A full ring
    drops new spans and counts them instead of growing without bound:
    span loss is accounted, never silent."""

    __slots__ = ("_cap", "_spans", "_dropped", "_lock")

    def __init__(self, cap=None):
        self._cap = (_flags.trace_ring_spans() if cap is None
                     else max(1, int(cap)))
        self._spans: list = []
        self._dropped = 0
        self._lock = threading.Lock()

    def record(self, trace, name, t0, t1, **args) -> None:
        """Record one completed span ``[t0, t1]`` (``time.perf_counter``
        seconds) under ``trace``.  No-op without a trace context or with
        telemetry disabled — untraced requests pay one dict lookup."""
        if not trace or not enabled():
            return
        off = time.time() - time.perf_counter()
        span = {"trace_id": trace.get("trace_id"), "name": name,
                "ts": t0 + off, "dur": max(0.0, t1 - t0)}
        if "parent" in trace:
            span["parent"] = trace["parent"]
        if args:
            span["args"] = dict(args)
        self.push(span)
        _jsonl_write(dict(span, ph="S"))

    def push(self, span: dict) -> None:
        """Append one already-formed span dict (a router absorbing a
        remote ring's drained spans); counts a drop when full."""
        with self._lock:
            if len(self._spans) >= self._cap:
                self._dropped += 1
            else:
                self._spans.append(span)

    def add_drops(self, n: int) -> None:
        """Fold a remote ring's reported drop count into this one so the
        fleet-side accounting sums losslessly."""
        if n > 0:
            with self._lock:
                self._dropped += int(n)

    def drain(self, cap=None):
        """Destructively take up to ``cap`` spans (the piggyback bound)
        plus the drop count so far; the drop counter resets with the
        take so repeated collections sum exactly."""
        with self._lock:
            if cap is None or cap >= len(self._spans):
                spans, self._spans = self._spans, []
            else:
                spans = self._spans[:cap]
                del self._spans[:cap]
            dropped, self._dropped = self._dropped, 0
        return spans, dropped

    def spans(self) -> list:
        """Non-destructive snapshot (the dump/export path)."""
        with self._lock:
            return list(self._spans)

    @property
    def dropped(self) -> int:
        with self._lock:
            return self._dropped

    def __len__(self) -> int:
        with self._lock:
            return len(self._spans)


def spans_to_chrome(spans, pid: int, name: str) -> list:
    """Wall-clock trace spans as chrome 'X' events on one process track
    (one ``tid`` row per request id, trace_id surfaced in args)."""
    out = [{"name": "process_name", "ph": "M", "pid": pid,
            "args": {"name": name}}]
    for s in spans:
        args = dict(s.get("args", {}))
        tid = args.get("rid", 0)
        args["trace_id"] = s.get("trace_id")
        out.append({"name": s.get("name", "?"), "ph": "X", "pid": pid,
                    "tid": int(tid) if isinstance(tid, (int, float))
                    else 0,
                    "ts": float(s.get("ts", 0.0)) * 1e6,
                    "dur": float(s.get("dur", 0.0)) * 1e6,
                    "args": args})
    return out


def dump_chrome_trace(path: str, include_profiler: bool = True) -> str:
    """Write one Perfetto-loadable chrome-trace JSON: telemetry spans
    (request lifecycles, compiles) next to :mod:`paddle_tpu.profiler`
    host events — drop the file (or its ``tools/merge_timeline.py`` merge
    with a ``jax.profiler`` device trace) into ui.perfetto.dev."""
    evs = []
    if include_profiler:
        from . import profiler as _profiler

        evs.append({"name": "process_name", "ph": "M", "pid": 0,
                    "args": {"name": "paddle_tpu.profiler"}})
        evs.extend({"name": n, "ph": "X", "pid": 0, "tid": tid,
                    "ts": t0 * 1e6, "dur": (t1 - t0) * 1e6}
                   for n, t0, t1, tid in _profiler.host_events())
    evs.extend(chrome_events(pid=1))
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    with open(path, "w") as f:
        json.dump({"traceEvents": evs, "displayTimeUnit": "ms"}, f)
    return path


# ---------------------------------------------------------------------------
# recompile watch
# ---------------------------------------------------------------------------


def _strip_flags(key, flags_key):
    """``key`` with every (possibly nested) occurrence of ``flags_key``
    replaced by a sentinel — the cfg-identity part of a jit-cache key
    (generate._cfg_key embeds flags.decode_jit_key as a sub-tuple)."""
    if key == flags_key:
        return "<flags>"
    if isinstance(key, tuple):
        return tuple(_strip_flags(k, flags_key) for k in key)
    return key


def _key_diff(old: tuple, new: tuple) -> str:
    if not (isinstance(old, tuple) and isinstance(new, tuple)
            and len(old) == len(new)):
        return f"{old!r} -> {new!r}"
    ds = [f"[{i}] {a!r} -> {b!r}" for i, (a, b) in
          enumerate(zip(old, new)) if a != b]
    return "; ".join(ds) or f"{old!r} -> {new!r}"


def record_compile(name: str, key, flags_key=None,
                   seconds: float | None = None, retrace: bool = False
                   ) -> None:
    """Record one compile: counter + wall-time histogram + a ``compile``
    span on the timeline (under the span in which the stall happened),
    and the recompile watch — if this (name, cfg-part) compiled before
    under a DIFFERENT flags key, the compile is a mid-process flag-flip
    retrace: warn (rate-limited) with the key diff.  A fresh config
    compiling for the first time never warns.  ``retrace`` marks a call
    that grew the jit cache of an executable already built (a new
    argument type or shape under the same key)."""
    if not enabled():
        return
    count("compile.count")
    last = None
    with _compile_lock:
        _compile_log.append({"name": name, "key": repr(key),
                             "seconds": None if seconds is None
                             else round(seconds, 4)})
        if flags_key is not None:
            base = (name, _strip_flags(key, flags_key))
            last = _compile_seen.get(base)
            _compile_seen[base] = flags_key
    flipped = last is not None and last != flags_key
    if seconds is not None:
        hist("compile.ms").observe(seconds * 1e3)
        now = time.perf_counter()
        args = {"fn": name, "seconds": round(seconds, 4)}
        if flipped:
            args["key_diff"] = _key_diff(last, flags_key)
        if retrace:
            args["retrace"] = True
        event("compile", now - seconds, now, **args)
    if not flipped:
        return
    with _compile_lock:
        now = time.monotonic()
        rate_ok = now - _warn_last.get(name, -math.inf) >= _WARN_INTERVAL_S
        if rate_ok:
            _warn_last[name] = now
    count("compile.recompiles")
    if rate_ok:
        warnings.warn(
            f"[paddle_tpu.telemetry] steady-state recompile of {name!r}: "
            f"the trace-time flags key changed mid-process "
            f"({_key_diff(last, flags_key)}) — an executable bakes these "
            f"in, so the flip forced a retrace (flags.decode_jit_key / "
            f"train_step_key discipline)", RuntimeWarning, stacklevel=3)


def instrument_compile(name: str, key, flags_key, fn):
    """Wrap a freshly built jitted callable from a jit-cache MISS: the
    first call (where tracing + XLA compilation actually happen) is timed
    and recorded via :func:`record_compile`, and so is any later call
    that grows the function's jit cache (a retrace for a new argument
    type, which builds no new wrapper); other calls pay one clock read
    and one ``_cache_size()``.  Returns ``fn`` unchanged when telemetry
    is off — the hot path compiles down to the raw executable.  The
    original jit function stays reachable as ``wrapper._telemetry_inner``
    (``jax.export`` callers must unwrap through that attribute — NOT
    ``__wrapped__``, which a raw ``jax.jit`` result also carries,
    pointing past the jit)."""
    if not enabled():
        return fn

    entries = getattr(fn, "_cache_size", None)
    seen = -1                            # jit-cache entries after last call

    @functools.wraps(fn)
    def wrapper(*a, **k):
        nonlocal seen
        t0 = wrapper._telemetry_last_call = time.perf_counter()
        out = fn(*a, **k)
        n = entries() if entries is not None else 1
        if n <= seen:                    # nothing compiled (or the cache
            seen = n                     # was cleared under us)
            return out
        first, seen = seen < 0, n
        record_compile(name, key, flags_key, time.perf_counter() - t0,
                       retrace=not first)
        # what executable_scopes() lowers again, on demand: shapes only
        # (best effort: a reader's question never breaks a step)
        with contextlib.suppress(Exception):
            wrapper._telemetry_specs = _arg_specs((a, k))
        if first:
            with _device_lock:
                # the caller's wall around THIS call includes the compile
                # — note_step_time must discard it, not seed the EWMA
                _skip_first_wall.add(name)
            _capture_analysis(name, fn, a, k)
        return out

    wrapper._telemetry_inner = fn
    wrapper._telemetry_name = name
    with _compile_lock:
        # newest last; the oldest goes once the ring is full
        _instrumented.pop((name, repr(key)), None)
        _instrumented[(name, repr(key))] = wrapper
        while len(_instrumented) > _INSTRUMENTED_KEPT:
            _instrumented.pop(next(iter(_instrumented)))
    # the AOT surface of the jitted function, so an instrumented step can
    # still be lowered and compiled ahead of time (for a described chip)
    for attr in ("lower", "trace", "eval_shape"):
        if hasattr(fn, attr):
            setattr(wrapper, attr, getattr(fn, attr))
    return wrapper


# ---------------------------------------------------------------------------
# which part of the model a device op belongs to
# ---------------------------------------------------------------------------
# A device trace names an op by its HLO text, which carries no op_name
# (read off a v5e trace: an ``XLA Ops`` event has device_offset_ps,
# device_duration_ps and nothing else).  The compiled module's text does:
# ``metadata={op_name="jit(<lambda>)/serving.async_step/attn/..."}``.  So a
# trace reader asks the program, after the trace, for {HLO op name:
# op_name path} of the executables that ran, and joins on the op's name.

# The wrappers executable_scopes() can still lower again, by (instrument
# name, cfg/flags key).  Held strongly, a bounded ring of the newest: a
# trace is read AFTER the serving that wrote it, often after the server
# closed and the Engine dropped its executables, and a weak set emptied
# whenever the cyclic collector happened to run in between (a wrapper
# refers to itself), so the per-part metrics of a run came or went with it.
_instrumented: dict = {}
_INSTRUMENTED_KEPT = 64
_HLO_COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.\-]+) \(.*\{\s*$")
_HLO_INSTRUCTION = re.compile(r"^\s+(ROOT )?%([\w.\-]+) = ")
_HLO_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_HLO_CALLS = re.compile(r"\bcalls=%([\w.\-]+)")


def hlo_op_scopes(text: str) -> dict:
    """{HLO instruction name: op_name path} of a compiled module's text.
    An instruction without metadata of its own (some fusions, an async
    start) takes the path of the computation it calls: its root's, else
    the most frequent among its instructions.  One that names no path
    either way (a copy, a buffer allocation) is left out."""
    own: dict = {}                 # instruction -> path
    calls: dict = {}               # instruction without a path -> callee
    root: dict = {}                # computation -> its root's path
    inside: dict = {}              # computation -> its instructions' paths
    comp = None
    for line in text.splitlines():
        m = _HLO_INSTRUCTION.match(line)
        if m is None:
            c = _HLO_COMPUTATION.match(line)
            if c is not None:
                comp = c.group(1)
            continue
        path = _HLO_OP_NAME.search(line)
        if path is not None:
            own[m.group(2)] = path.group(1)
            inside.setdefault(comp, []).append(path.group(1))
            if m.group(1):
                root[comp] = path.group(1)
        else:
            callee = _HLO_CALLS.search(line)
            if callee is not None:
                calls[m.group(2)] = callee.group(1)
    for ins, callee in calls.items():
        paths = inside.get(callee)
        if paths:
            own[ins] = root.get(callee) or max(set(paths), key=paths.count)
    return own


def _arg_specs(tree):
    """Shapes, dtypes and (where committed) shardings of a call's
    arguments: enough to lower the same program again, no buffer kept."""
    import jax

    def spec(x):
        if isinstance(x, jax.Array) and not isinstance(x, jax.core.Tracer):
            return jax.ShapeDtypeStruct(
                x.shape, x.dtype, weak_type=x.weak_type,
                sharding=x.sharding if x.committed else None)
        if hasattr(x, "shape") and hasattr(x, "dtype"):
            return jax.ShapeDtypeStruct(x.shape, x.dtype)
        return x

    return jax.tree_util.tree_map(spec, tree)


def executable_scopes(since: float | None = None) -> list:
    """For every instrumented executable called at or after ``since``
    (``time.perf_counter`` seconds; None = ever): ``{"name": instrument
    name, "module": XLA module name, "ops": {HLO op name: op_name
    path}}``, from the text of the program compiled again for the shapes
    of its last compile (a read from the persistent compile cache where
    that is on).  Costs nothing until asked; an executable that cannot
    be lowered again is left out."""
    out = []
    for w in list(_instrumented.values()):
        specs = getattr(w, "_telemetry_specs", None)
        if specs is None or (since is not None and
                             getattr(w, "_telemetry_last_call", 0.0) < since):
            continue
        try:
            text = w.lower(*specs[0], **specs[1]).compile().as_text()
        except Exception:  # noqa: BLE001 - a reader's question, never fatal
            continue
        head = re.match(r"HloModule ([\w.\-]+)", text)
        out.append({"name": w._telemetry_name,
                    "module": head.group(1) if head else "",
                    "ops": hlo_op_scopes(text)})
    return out


def _capture_analysis(name: str, fn, args, kwargs) -> None:
    """Device feed, capture half: pull the freshly compiled step's
    ``cost_analysis``/``memory_analysis`` out of jax's AOT surface —
    per-executable FLOPs, bytes moved, argument/output/temp sizes — and
    stash them under the instrument name for :func:`device_feed` to
    join with measured step walls.

    Runs ONCE per jit-cache miss, right after the compiling first call:
    ``fn.lower`` reuses the cached trace (args are the exact call's — a
    donated buffer's aval survives deletion) and ``lowered.compile()``
    is an AOT recompile that the persistent compile cache turns into a
    disk read.  Strictly best-effort: any backend that lacks an
    analysis yields nulls, never an exception on the hot path."""
    if not _flags.device_feed_enabled():
        return
    rec: dict = {"captured_at": time.time()}
    try:
        import jax

        d = jax.devices()[0]
        with _device_lock:
            _device_info.setdefault("platform", d.platform)
            _device_info.setdefault(
                "device_kind", str(getattr(d, "device_kind", "")))
        lowered = fn.lower(*args, **kwargs)
    except Exception:  # noqa: BLE001 - feed capture must never break a step
        return
    def _fold_cost(ca):
        if isinstance(ca, list):
            ca = ca[0] if ca else {}
        if ca.get("flops", 0) > 0:
            rec["flops"] = float(ca["flops"])
        if ca.get("bytes accessed", 0) > 0:
            rec["bytes_accessed"] = float(ca["bytes accessed"])

    # The memory-analysis half needs an AOT recompile (lowered.compile()
    # does not share the jit dispatch cache).  Pay it only where it is
    # cheap or amortized: CPU (test/dev compiles are sub-second), any
    # backend with the persistent compile cache configured (serving
    # warmup calls init_compile_cache, making this a disk read), or an
    # explicit PADDLE_TPU_DEVICE_FEED=full.  Otherwise an unwarmed TPU
    # server would pay minutes of double compile inside its first ticks.
    try:
        full = (d.platform == "cpu"
                or bool(jax.config.jax_compilation_cache_dir)
                or _flags.device_feed_mode() == "full")
    except Exception:  # noqa: BLE001
        full = False
    if not full:
        with contextlib.suppress(Exception):
            _fold_cost(lowered.cost_analysis())
        _store_analysis(name, rec)
        return
    try:
        compiled = lowered.compile()
        with contextlib.suppress(Exception):
            _fold_cost(compiled.cost_analysis())
        ma = compiled.memory_analysis()
        if ma is not None:
            for field in ("argument_size_in_bytes", "output_size_in_bytes",
                          "temp_size_in_bytes", "alias_size_in_bytes",
                          "generated_code_size_in_bytes"):
                v = getattr(ma, field, None)
                if v is not None:
                    rec[field.replace("_size_in_bytes", "_bytes")] = int(v)
    except Exception:  # noqa: BLE001 - memory analysis is the optional half
        pass
    if "flops" not in rec:
        # backend without compiled-level analysis: the unoptimized-HLO
        # cost model still yields FLOPs/bytes (no XLA compile needed)
        with contextlib.suppress(Exception):
            _fold_cost(lowered.cost_analysis())
    _store_analysis(name, rec)


def _store_analysis(name: str, rec: dict) -> None:
    if len(rec) <= 1:  # nothing beyond the timestamp — keep the feed null
        return
    with _device_lock:
        prev = _step_analysis.get(name)
        rec["compiles"] = (prev.get("compiles", 0) + 1) if prev else 1
        _step_analysis[name] = rec
        # a re-capture means a NEW executable now owns this name (e.g. a
        # server built for a different config): the old executable's wall
        # EWMA must not blend into the new one's MFU.  Two same-named
        # configs ticking CONCURRENTLY still blend — a documented
        # limitation; per-config name suffixes would explode gauge
        # cardinality for the common one-config-per-process case.
        _step_times.pop(name, None)
        # cardinality bound: per-construction names (jit.to_static:*#N)
        # would otherwise grow /metrics and host memory for the life of
        # a process that keeps wrapping new functions — evict the oldest
        # capture past the cap (reset() never clears this store)
        while len(_step_analysis) > 256:
            oldest = min(_step_analysis,
                         key=lambda n: _step_analysis[n]
                         .get("captured_at", 0.0))
            del _step_analysis[oldest]
            _step_times.pop(oldest, None)
            _skip_first_wall.discard(oldest)
        while len(_skip_first_wall) > 1024:  # names never noted
            _skip_first_wall.pop()


def note_step_time(name: str, seconds: float) -> None:
    """Feed one measured per-call wall of the ``name`` executable into
    the device feed's EWMA (callers: the serving tick/fit sites that
    already hold an honest wall covering device execution — never the
    async dispatch time, which returns before the device finishes)."""
    if not enabled() or seconds <= 0.0:
        return
    s = float(seconds)
    with _device_lock:
        if name in _skip_first_wall:
            # this wall overlapped the executable's compiling first call
            # (instrument_compile flagged it) — compile-dominated, and a
            # name hit exactly once would export it as a live gauge
            _skip_first_wall.discard(name)
            return
        t = _step_times.get(name)
        if t is None:
            _step_times[name] = {"ewma_s": s, "last_s": s, "calls": 1}
        elif t["calls"] == 1 and t["ewma_s"] > 3.0 * s:
            # the first wall of a fresh executable usually includes its
            # XLA compile — once a steady-state sample shows it was an
            # outlier, restart the EWMA instead of averaging it in
            _step_times[name] = {"ewma_s": s, "last_s": s, "calls": 2}
        else:
            t["ewma_s"] += _STEP_EWMA_ALPHA * (s - t["ewma_s"])
            t["last_s"] = s
            t["calls"] += 1


def sample_device_stats(min_interval_s: float | None = None,
                        devices=None) -> dict:
    """Rate-limited PJRT memory-stats sample for the hot paths: folds
    ``monitor.snapshot_device_stats`` (bytes_in_use / peak / limit per
    device — the STAT_gpuN_mem analog) into the shared registry, mirrors
    the numbers as telemetry gauges, and drops one Perfetto counter
    event so HBM rides the timeline next to the request spans.

    A host-side PJRT query, never a device sync; backends without
    memory stats (CPU) yield {} silently.  ``devices`` overrides the
    sampled device list (tests inject fakes)."""
    if not _flags.device_feed_enabled():
        return {}
    now = time.monotonic()
    interval = (_flags.hbm_sample_interval_s() if min_interval_s is None
                else min_interval_s)
    with _device_lock:
        if now - _hbm_state["t"] < interval:
            return dict(_hbm_last)
        _hbm_state["t"] = now
    try:
        out = _monitor.snapshot_device_stats(devices=devices)
    except Exception:  # noqa: BLE001 - a stats query must not kill a tick
        return {}
    if not out:
        return {}
    for k, v in out.items():
        gauge(f"device.{k}").set(v)
    with _device_lock:
        _hbm_last.clear()
        _hbm_last.update(out)
    _counter_event("hbm", {k: v for k, v in out.items()
                           if "bytes_in_use" in k})
    return dict(out)


def device_feed() -> dict:
    """The device half of :func:`snapshot`: per-compiled-step FLOPs /
    bytes / sizes joined with measured step walls into live MFU and
    roofline (compute- vs bandwidth-bound) gauges, plus the last HBM
    sample.  Null-safe by construction — an unknown chip kind (or CPU)
    has ``peak_flops`` None and every MFU reports null rather than a
    fabricated percentage (framework.platform.DEVICE_PEAKS is the one
    peaks table)."""
    from .framework import platform as _platform

    with _device_lock:
        info = dict(_device_info)
        analyses = {n: dict(r) for n, r in _step_analysis.items()}
        times = {n: dict(t) for n, t in _step_times.items()}
        hbm = dict(_hbm_last)
    peak_f, peak_bw = _platform.device_peaks(info.get("device_kind"),
                                             info.get("platform"))
    balance = (peak_f / peak_bw) if peak_f and peak_bw else None
    steps = {}
    for nm, rec in analyses.items():
        s = dict(rec)
        s.pop("captured_at", None)
        flops = rec.get("flops")
        bts = rec.get("bytes_accessed")
        s["mfu"] = None
        s["bound"] = None
        if flops and bts:
            ai = flops / bts  # arithmetic intensity, FLOPs/byte
            s["arithmetic_intensity"] = round(ai, 3)
            if balance is not None:
                s["bound"] = "compute" if ai >= balance else "bandwidth"
        t = times.get(nm)
        if t and t.get("ewma_s", 0) > 0:
            s["step_s"] = round(t["ewma_s"], 6)
            s["step_calls"] = t["calls"]
            if flops:
                fps = flops / t["ewma_s"]
                s["flops_per_s"] = round(fps, 1)
                if peak_f:
                    # full precision: a tiny step's MFU is legitimately
                    # ~1e-5 and fixed-decimal rounding would zero it
                    s["mfu"] = fps / peak_f
            if bts:
                bps = bts / t["ewma_s"]
                s["bytes_per_s"] = round(bps, 1)
                if peak_bw:
                    s["hbm_bw_util"] = bps / peak_bw
        steps[nm] = s
    return {"platform": info.get("platform"),
            "device_kind": info.get("device_kind"),
            "peak_flops": peak_f, "peak_hbm_bytes_per_s": peak_bw,
            "steps": steps, "hbm": hbm}


# ---------------------------------------------------------------------------
# export: snapshot / prometheus / HTTP
# ---------------------------------------------------------------------------


def snapshot() -> dict:
    """One JSON-serializable dict over all three feeds: histogram
    quantiles, gauges, the shared counter registry, and the compile log.
    Histogram count/sum are also pushed into the monitor registry as
    float stats, so ``monitor.stats()`` alone sees every feed."""
    # copy under the registry lock: the MetricsServer thread snapshots
    # while serving threads insert new names / reset() clears
    with _lock:
        hists = sorted(_hists.items())
        gauges = sorted(_gauges.items())
    hs = {}
    for name, h in hists:
        s = h.summary()
        hs[name] = s
        with _lock:
            _counter_names.add(name + ".count")
            _counter_names.add(name + ".sum")
        _monitor.get_stat(name + ".count").set(s["count"])
        _monitor.get_stat(name + ".sum", as_float=True).set(s["sum"])
    with _compile_lock:
        compiles = list(_compile_log)
    return {
        "enabled": enabled(),
        "histograms": hs,
        "gauges": {n: g.get() for n, g in gauges},
        "counters": _monitor.stats(),
        "compiles": compiles,
        "device": device_feed(),
        "events": len(_events),
    }


def latency_summary(prefix: str = "serving.") -> dict:
    """Compact {short_name: {count, p50, p99}} over histograms under
    ``prefix``: distributions, not means."""
    with _lock:
        hists = sorted(_hists.items())
    out = {}
    for name, h in hists:
        if not name.startswith(prefix):
            continue
        s = h.summary()
        out[name[len(prefix):]] = {"count": s["count"], "p50": s["p50"],
                                   "p99": s["p99"]}
    return out


def _prom_name(name: str) -> str:
    """Sanitize the metric name but keep a monitor-style ``{k="v"}``
    label block intact (``monitor.get_stat(name, **labels)`` built it in
    valid exposition syntax already)."""
    base, brace, labels = name.partition("{")
    out = "".join(c if c.isalnum() or c == "_" else "_" for c in base)
    return "paddle_tpu_" + out + brace + labels


def render_prometheus() -> str:
    """Prometheus text exposition (v0.0.4) over the whole registry."""
    with _lock:  # the endpoint thread renders while serving code records
        hists = sorted(_hists.items())
        gauges = sorted(_gauges.items())
    lines = []
    for name, h in hists:
        pn = _prom_name(name)
        s = h.summary()
        lines.append(f"# TYPE {pn} histogram")
        for ub, cum in h.buckets():
            le = "+Inf" if ub == math.inf else f"{ub:.6g}"
            lines.append(f'{pn}_bucket{{le="{le}"}} {cum}')
        lines.append(f"{pn}_sum {s['sum']:.6g}")
        lines.append(f"{pn}_count {s['count']}")
    for name, g in gauges:
        pn = _prom_name(name)
        lines.append(f"# TYPE {pn} gauge")
        lines.append(f"{pn} {g.get():.6g}")
    # the '<hist>.count'/'<hist>.sum' monitor mirrors snapshot() writes
    # would sanitize to the histogram's own _count/_sum sample names —
    # duplicate families are invalid exposition, so skip them here.
    # Device-memory stats are skipped the same way: sample_device_stats
    # already exports them as 'device.*' GAUGES (the honest typing for a
    # value that goes down), and the counter-typed monitor twin would be
    # a second, rate()-breaking name for the same number
    mirror = {f"{n}.count" for n, _ in hists} | \
             {f"{n}.sum" for n, _ in hists} | \
             {n[len("device."):] for n, _ in gauges
              if n.startswith("device.")}
    for name, v in sorted(_monitor.stats().items()):
        if name in mirror:
            continue
        pn = _prom_name(name)
        # TYPE declares the FAMILY (label-free); the sample keeps labels
        lines.append(f"# TYPE {pn.partition('{')[0]} counter")
        lines.append(f"{pn} {v:.6g}" if isinstance(v, float)
                     else f"{pn} {v}")
    # device feed: per-step FLOPs/MFU/roofline as labeled gauges (null
    # MFUs — unknown chip — are simply absent, never a fabricated 0)
    feed = device_feed()
    if feed["steps"]:
        emitted = set()
        for metric, field in (("step_flops", "flops"),
                              ("step_bytes_accessed", "bytes_accessed"),
                              ("step_mfu", "mfu"),
                              ("step_hbm_bw_util", "hbm_bw_util"),
                              ("step_seconds", "step_s")):
            for nm, s in sorted(feed["steps"].items()):
                v = s.get(field)
                if v is None:
                    continue
                if metric not in emitted:
                    emitted.add(metric)
                    lines.append(f"# TYPE paddle_tpu_device_{metric} gauge")
                lines.append(
                    f'paddle_tpu_device_{metric}{{step="{nm}"}} {v:.6g}')
    return "\n".join(lines) + "\n"


_profile_lock = threading.Lock()


def capture_device_profile(ms: float = 500.0,
                           out_dir: str | None = None) -> str:
    """On-demand device profiling: ``jax.profiler.start_trace`` /
    ``stop_trace`` around ``ms`` milliseconds of whatever traffic is
    live (the serving threads keep ticking — this blocks only the
    caller).  Returns the trace directory (TensorBoard 'profile'
    plugin / Perfetto loadable).  One capture at a time: a concurrent
    request raises rather than corrupting the active trace."""
    ms = float(ms)
    if not 0 < ms <= 60_000:
        raise ValueError(f"profile window must be in (0, 60000] ms, "
                         f"got {ms}")
    if not _profile_lock.acquire(blocking=False):
        raise RuntimeError("a device profile capture is already running")
    try:
        import tempfile

        import jax

        out_dir = (out_dir or os.environ.get("PADDLE_TPU_PROFILE_DIR")
                   or tempfile.mkdtemp(prefix="paddle_tpu_trace_"))
        os.makedirs(out_dir, exist_ok=True)
        # the capture window itself lands on the telemetry timeline, so
        # the merged Perfetto view shows WHICH requests the device trace
        # overlapped
        with span("profiler.capture", dir=out_dir, ms=ms):
            jax.profiler.start_trace(out_dir)
            try:
                time.sleep(ms / 1e3)
            finally:
                jax.profiler.stop_trace()
        return out_dir
    finally:
        _profile_lock.release()


class MetricsServer:
    """Tiny opt-in HTTP endpoint: ``GET /metrics`` (Prometheus text),
    ``GET /snapshot`` (the JSON snapshot), ``GET /healthz`` (probe/wedge
    + feed state), ``POST /profile?ms=500`` (on-demand device trace
    around live traffic; returns the trace dir).  Daemon-threaded;
    ``port=0`` picks an ephemeral port (``.port`` has the bound one).
    Binds loopback by default — the endpoint is unauthenticated, so
    exposing it beyond the host (``host="0.0.0.0"`` for a scraper
    sidecar) is an explicit opt-in.

    ``render``/``snap`` override what ``/metrics`` and ``/snapshot``
    serve (callables returning exposition text / a JSON-safe dict) — the
    Router passes its fleet-merged views so one port covers the whole
    fleet; ``None`` keeps the process-local registry."""

    def __init__(self, port: int, host: str = "127.0.0.1",
                 render=None, snap=None):
        import http.server

        render_fn = render if render is not None else render_prometheus
        snap_fn = snap if snap is not None else snapshot

        class Handler(http.server.BaseHTTPRequestHandler):
            def _reply(self_h, code, body, ctype):  # noqa: N805
                self_h.send_response(code)
                self_h.send_header("Content-Type", ctype)
                self_h.send_header("Content-Length", str(len(body)))
                self_h.end_headers()
                self_h.wfile.write(body)

            def do_GET(self_h):  # noqa: N805
                if self_h.path.startswith("/snapshot"):
                    body = json.dumps(snap_fn()).encode()
                    ctype = "application/json"
                elif self_h.path.startswith("/healthz"):
                    feed = device_feed()
                    wedge = runtime_wedge()
                    # the in-process resilience watchdog (a live step
                    # blew its budget) is the one wedge authority
                    healthy = not wedge["wedged"]
                    body = json.dumps({
                        "ok": healthy,
                        "telemetry_enabled": enabled(),
                        "device_feed_enabled":
                            _flags.device_feed_enabled(),
                        "runtime_wedge": wedge,
                        "platform": feed.get("platform"),
                        "device_kind": feed.get("device_kind"),
                        "instrumented_steps": sorted(feed["steps"]),
                        "hbm": feed.get("hbm", {}),
                        # admission-control state (degradation rung,
                        # budget level, per-class sheds, throttles) —
                        # empty dict until a controller records
                        "admission": admission_snapshot(),
                    }).encode()
                    # healthz convention: status-code signaling — a
                    # k8s-style httpGet probe never reads the body, so a
                    # wedged process must be a non-2xx
                    self_h._reply(200 if healthy else 503, body,
                                  "application/json")
                    return
                elif self_h.path.startswith("/metrics") or \
                        self_h.path == "/":
                    body = render_fn().encode()
                    ctype = "text/plain; version=0.0.4"
                else:
                    self_h.send_error(404)
                    return
                self_h._reply(200, body, ctype)

            def do_POST(self_h):  # noqa: N805
                if not self_h.path.startswith("/profile"):
                    self_h.send_error(404)
                    return
                from urllib.parse import parse_qs, urlparse

                q = parse_qs(urlparse(self_h.path).query)
                try:
                    ms = float(q.get("ms", ["500"])[0])
                    # no client-chosen output dir: the endpoint is
                    # unauthenticated, so the write target stays server-
                    # side (PADDLE_TPU_PROFILE_DIR or a fresh tempdir)
                    trace_dir = capture_device_profile(ms)
                except ValueError as e:
                    self_h._reply(400, json.dumps(
                        {"error": str(e)}).encode(), "application/json")
                    return
                except RuntimeError as e:  # capture already running
                    self_h._reply(409, json.dumps(
                        {"error": str(e)}).encode(), "application/json")
                    return
                except Exception as e:  # noqa: BLE001 - report, don't die
                    self_h._reply(500, json.dumps(
                        {"error": f"{type(e).__name__}: {e}"}).encode(),
                        "application/json")
                    return
                self_h._reply(200, json.dumps(
                    {"trace_dir": trace_dir, "ms": ms}).encode(),
                    "application/json")

            def log_message(self_h, *a):  # noqa: N805 - quiet by design
                pass

        self._httpd = http.server.ThreadingHTTPServer((host, int(port)),
                                                      Handler)
        self.port = self._httpd.server_address[1]
        self._thread = threading.Thread(target=self._httpd.serve_forever,
                                        name="paddle-tpu-metrics",
                                        daemon=True)
        self._thread.start()

    def close(self):
        with contextlib.suppress(Exception):
            self._httpd.shutdown()
            self._httpd.server_close()
        # join the serve_forever thread (bounded): interpreter exit after
        # a fault must never hang on a half-shut HTTP server.  The thread
        # is a daemon, so a pathological join timeout still cannot pin
        # the process — the bound is about making close() deterministic.
        with contextlib.suppress(Exception):
            if self._thread.is_alive():
                self._thread.join(timeout=5.0)


def serve_metrics(port: int, host: str = "127.0.0.1",
                  render=None, snap=None) -> MetricsServer:
    """Start the /metrics endpoint (``DecodeServer(metrics_port=...)``
    calls this; standalone use works too).  ``render``/``snap`` override
    the served views — the Router's fleet aggregation plane."""
    return MetricsServer(port, host, render=render, snap=snap)
