"""Resilience primitives: bounded retry, deadlines, and a wedge watchdog.

Reference capability: the reference's production credibility rests on its
fault handling — the allocator stack retries an OOM through a chain of
fallbacks (auto-growth best-fit -> garbage collect -> synchronous free ->
retry, PAPER.md §L1) instead of killing the process, and error-clip /
check_nan_inf guard training from one bad batch.  This module is the
TPU-native equivalent at RUNTIME granularity: the schedulers and loops
that sit above XLA (DecodeServer ticks, Model.fit steps) get one shared
vocabulary of

* :func:`retry` — bounded attempts with capped exponential backoff and
  DETERMINISTIC jitter (seeded, so chaos tests can assert the exact
  schedule), every engagement counted into the telemetry registry;
* :class:`Deadline` — TTL arithmetic for request shedding;
* :func:`call_with_budget` — a wall-budget watchdog around a blocking
  call (the async serving fetch): on timeout the caller gets a
  :class:`WedgeError` while the hung call is abandoned on a daemon
  thread, which is the only honest option Python has against a wedged
  device RPC;
* :func:`is_oom` — one classifier for allocator exhaustion, covering
  real ``RESOURCE_EXHAUSTED`` XlaRuntimeErrors and the fault harness's
  :class:`faults.InjectedOOM` by the same string rule.

``PADDLE_TPU_RESILIENCE=0`` restores fail-fast everywhere: :func:`retry`
runs its function exactly once and every caller's degradation chain is
skipped (the chaos suite pins this parity).
"""
from __future__ import annotations

import random
import threading
import time
from typing import Callable, Iterable, Sequence

from . import flags as _flags
from . import telemetry as _telemetry

__all__ = [
    "enabled", "DeadlineExceeded", "Overloaded", "WedgeError", "Deadline",
    "backoff_schedule", "retry", "is_oom", "call_with_budget",
]


def enabled() -> bool:
    """Master switch (re-read per call so tests can flip the env)."""
    return _flags.resilience_enabled()


class DeadlineExceeded(TimeoutError):
    """A TTL/deadline expired — e.g. a queued serving request shed
    before admission (``DecodeServer.result`` raises this for requests
    retired with the ``timeout`` status)."""


class Overloaded(RuntimeError):
    """Admission control shed this request at the DOOR — a per-tenant
    rate limit, a bounded per-class queue overflowing, or the SLO
    degradation ladder's shed rung (``DecodeServer.result`` /
    ``fleet.Router.result`` raise this for requests retired with the
    ``rejected`` status).  Distinct from :class:`DeadlineExceeded` on
    purpose: a TTL ``timeout`` means the request WAITED and lost; a
    ``rejected`` means the server refused to queue it at all, which is
    the signal a client should back off on."""


class WedgeError(RuntimeError):
    """A guarded call exceeded its wall budget (the watchdog's verdict:
    the step is wedged, not slow)."""


class Deadline:
    """Absolute deadline built from a TTL: ``Deadline(0.5)`` expires
    0.5 s from construction.  ``ttl_s=None`` never expires (the
    default-off shape every deadline knob here shares)."""

    __slots__ = ("t0", "ttl_s")

    def __init__(self, ttl_s: float | None, t0: float | None = None):
        self.t0 = time.perf_counter() if t0 is None else float(t0)
        self.ttl_s = None if ttl_s is None else float(ttl_s)

    def remaining(self) -> float:
        if self.ttl_s is None:
            return float("inf")
        return self.ttl_s - (time.perf_counter() - self.t0)

    @property
    def expired(self) -> bool:
        return self.remaining() <= 0.0


def backoff_schedule(attempts: int, base: float = 0.05,
                     factor: float = 2.0, max_delay: float = 2.0,
                     jitter: float = 0.1, seed: int = 0) -> list:
    """The delay (seconds) before each RETRY of a failed call:
    ``attempts`` total attempts yield ``attempts - 1`` delays,
    ``min(base * factor**i, max_delay)`` each, plus-or-minus a uniform
    jitter fraction drawn from ``random.Random(seed)`` — deterministic
    for a given seed, so tests assert the exact schedule while distinct
    seeds (e.g. per-request rids) still de-synchronize a thundering
    herd."""
    rng = random.Random(seed)
    out = []
    for i in range(max(0, int(attempts) - 1)):
        d = min(base * (factor ** i), max_delay)
        if jitter:
            d *= 1.0 + jitter * (2.0 * rng.random() - 1.0)
        out.append(max(0.0, d))
    return out


def is_oom(exc: BaseException) -> bool:
    """True when ``exc`` is allocator exhaustion: a real XlaRuntimeError
    (or any jax error) carrying ``RESOURCE_EXHAUSTED`` / an OOM marker,
    or the fault harness's InjectedOOM (same marker by construction).
    One string rule on purpose — jaxlib moves the exception class
    between versions, the message marker is the stable API."""
    msg = f"{type(exc).__name__}: {exc}"
    return ("RESOURCE_EXHAUSTED" in msg
            or "Out of memory" in msg
            or "out of memory" in msg)


def retry(fn: Callable, *, name: str, attempts: int = 3,
          base: float = 0.05, factor: float = 2.0, max_delay: float = 2.0,
          jitter: float = 0.1, seed: int | None = None,
          retry_on: type | tuple = Exception,
          deadline: Deadline | None = None,
          sleep: Callable[[float], None] = time.sleep,
          on_retry: Callable | None = None):
    """Call ``fn`` with bounded retries and capped exponential backoff.

    ``name`` is REQUIRED and is the telemetry identity: every engaged
    retry counts ``resilience.retries`` and ``resilience.retries.<name>``
    (tools/check_instrumented.py lints that no call site omits it, so
    every retry loop in the tree is observable).  ``retry_on`` bounds
    WHAT is retried — a non-matching exception propagates immediately.
    ``deadline`` (optional) stops retrying once expired, raising the
    last error rather than :class:`DeadlineExceeded` (the error is the
    truth; the deadline just stopped us burning more attempts on it).
    With resilience disabled this is exactly one attempt — today's
    fail-fast behavior.
    """
    if not name:
        raise ValueError("retry() requires a non-empty name= (the "
                         "telemetry counter identity)")
    if not enabled():
        attempts = 1
    attempts = max(1, int(attempts))
    if seed is None:
        # default jitter seed varies per (site, process): N processes
        # retrying the same contended resource
        # must not sleep in lockstep — identical schedules re-contend
        # simultaneously, the herd the jitter exists to break.  Still
        # deterministic for a fixed (name, pid); tests pin seed= (or
        # jitter=0) explicitly.
        import os as _os
        import zlib as _zlib

        seed = _zlib.crc32(f"{name}:{_os.getpid()}".encode())
    delays = backoff_schedule(attempts, base, factor, max_delay, jitter,
                              seed)
    last: BaseException | None = None
    for i in range(attempts):
        try:
            return fn()
        except retry_on as e:  # noqa: PERF203 - the retry loop IS the point
            last = e
            if i + 1 >= attempts:
                break
            if deadline is not None and deadline.expired:
                break
            _telemetry.count("resilience.retries")
            _telemetry.count(f"resilience.retries.{name}")
            if on_retry is not None:
                on_retry(i + 1, e)
            sleep(delays[i])
    assert last is not None
    raise last


def call_with_budget(fn: Callable, budget_s: float, *, name: str):
    """Run ``fn()`` under a wall budget: returns its result, or raises
    :class:`WedgeError` after ``budget_s`` seconds.  The call runs on a
    daemon worker thread; on timeout that thread is ABANDONED (Python
    cannot cancel a blocking device RPC) — its late result, if any, is
    discarded, and ``resilience.wedge_detected`` +
    ``resilience.wedge_detected.<name>`` count the event.  Use only
    around calls whose results the caller can afford to drop and
    recompute (the async serving fetch qualifies: the scheduler rolls
    its slots back and re-decodes)."""
    if budget_s is None or budget_s <= 0:
        return fn()
    box: dict = {}
    done = threading.Event()

    def run():
        try:
            box["out"] = fn()
        except BaseException as e:  # noqa: BLE001 - re-raised on the caller
            box["err"] = e
        finally:
            done.set()

    t = threading.Thread(target=run, daemon=True,
                         name=f"paddle-tpu-budget-{name}")
    t.start()
    if not done.wait(budget_s):
        _telemetry.count("resilience.wedge_detected")
        _telemetry.count(f"resilience.wedge_detected.{name}")
        raise WedgeError(
            f"{name} exceeded its wall budget of {budget_s:.3f}s "
            f"(the step is wedged; the hung call is abandoned)")
    if "err" in box:
        raise box["err"]
    return box["out"]
