"""The program's own span ring (``telemetry.events()``: raw records in
``time.perf_counter`` seconds, the clock of the run's windows), for the
readers of the scheduler's metrics.  Per-request statistics of a traced
run end where the profiler starts (``stats_window``): its slow stop stalls
the loop for seconds, which is the measurement's doing, not the server's.

A program without ``telemetry.events`` (an older commit) gives no records,
and the readers then read nothing."""
from __future__ import annotations


def window(run: dict) -> list:
    """The ring's spans that ended inside the run's statistics window,
    oldest first."""
    from paddle_tpu import telemetry

    events = getattr(telemetry, "events", None)
    if events is None or "stats_window" not in run:
        return []
    lo, hi = run["stats_window"]
    return [e for e in events() if "t1" in e and lo <= e["t1"] < hi]


def spans(run: dict, name: str) -> list:
    return [e for e in window(run) if e["name"] == name]


def token_times(emits) -> dict:
    """{rid: [stamp of each token, in order]} from ``serving.emit``
    records: each holds parallel lists of rids, token counts and the
    stamps at which those tokens were on the host."""
    out: dict = {}
    for e in emits:
        a = e["args"]
        for rid, n, t in zip(a["rids"], a["n"], a["t"]):
            out.setdefault(rid, []).extend([t] * n)
    return out
