"""Host time of one scheduler tick in ms: the median over the statistics
window's ticks of the ``serving.tick`` span less its ``serving.tick.wait``
children (the one blocking device-to-host fetch).  What is left is the
host's own work: admission, feed building, the enqueue, bookkeeping.  At a
step ten times shorter than today's this is the floor of a tick.  Ticks
that dispatched no step (idle, admission only) are left out; the earlier
line has every phase's median."""
from ..common import log, median
from . import ring

PHASES = ("admit", "feed", "dispatch", "wait", "book")


def read(run: dict, args: dict):
    events = ring.window(run)
    ticks = {e["id"]: e for e in events if e["name"] == "serving.tick"
             and e.get("args", {}).get("kind") not in
             (None, "idle", "admit_only", "fetch_only")}
    if not ticks:
        return None
    phase: dict = {p: {} for p in PHASES}       # phase -> tick id -> seconds
    for e in events:
        p = e["name"].rpartition("serving.tick.")[2]
        if p in phase and e.get("parent") in ticks:
            by = phase[p]
            by[e["parent"]] = by.get(e["parent"], 0.0) + e["t1"] - e["t0"]
    host = [(t["t1"] - t["t0"] - phase["wait"].get(i, 0.0)) * 1e3
            for i, t in ticks.items()]
    log(f"[ticks] n={len(ticks)} ticks that dispatched a step in the "
        f"statistics window: median ms of the tick "
        f"{median([(t['t1'] - t['t0']) * 1e3 for t in ticks.values()]):.3f}"
        f", of its phases "
        f"{ {p: round(median(list(v.values())) * 1e3, 3) for p, v in phase.items() if v} }"
        f" (admit: over the {len(phase['admit'])} ticks that had one); "
        f"host = tick - wait, median {median(host):.3f}, max {max(host):.3f}")
    return median(host)
