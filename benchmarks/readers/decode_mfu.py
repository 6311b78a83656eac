"""The whole decode step's share of the chip's peak, in percent, on the
benchmark's own clock: the least time the chip could take for what one
step must do, over the time one step took.

The time of a step is the median period between consecutive ticks that
generated tokens inside the statistics window, from the stamps the load
driver takes itself (``serve.Driver.samples``: one a ``tick()``, with the
turn of its loop and the requests that held a slot by their ``status``).
No module, kernel, scope or span of the program is asked, so none can blank
it; a tick also holds the host's work, so the share is of what a client
waits for, not of the device's time alone.

The least time is max(bytes / peak bytes/s, FLOPs / peak FLOP/s) from
``peaks.json``, with bytes = every weight once + the state rows live, and
FLOPs for one token per occupied slot (the family's ``decode_step_cost``),
at the mean occupancy and live rows over those ticks.  The run's earlier
line says which bound applies, and gives the device time of one step (by
its ``serving.<kind>`` scope, ``args``) beside the tick period."""
from .. import requests
from ..common import log, median
from . import scope_time


def tick_periods(samples, lo: float, hi: float) -> list:
    """[(stamp, seconds since the tick before)] for every tick in [lo, hi)
    that generated tokens (a request held a slot in it) and came straight
    after another tick of the window (no sleep between them)."""
    rows = [r for r in samples if lo <= r.t < hi]
    return [(b.t, b.t - a.t) for a, b in zip(rows, rows[1:])
            if b.held > 0 and b.turn == a.turn + 1]


def read(run: dict, args: dict):
    if not run.get("peaks") or "joined" not in run:
        return None
    ticks = tick_periods(run["samples"], *run["stats_window"])
    live = [requests.live_kv_tokens(run["joined"], t) for t, _ in ticks]
    live = [rk for rk in live if rk[0]]
    if not live:
        return None
    period = median([p for _, p in ticks])
    rows = sum(r for r, _ in live) / len(live)
    kv_tokens = sum(k for _, k in live) / len(live)
    cost = run["family"].decode_step_cost(run["sizes"], rows, kv_tokens)
    t_bytes = cost["bytes"] / run["peaks"]["hbm_bytes_per_s"]
    t_flops = cost["flops"] / run["peaks"]["bf16_flops_per_s"]
    dev_ms = scope_time.read(run, args)
    log(f"[roofline] decode step over {len(ticks)} ticks that generated "
        f"tokens: mean {rows:.1f} occupied slots, {kv_tokens:.0f} live KV "
        f"tokens; must move {cost['bytes'] / 1e9:.3f} GB "
        f"({t_bytes * 1e3:.3f} ms at peak) and do {cost['flops'] / 1e9:.1f} "
        f"GFLOP ({t_flops * 1e3:.3f} ms at peak): "
        f"{'memory' if t_bytes >= t_flops else 'compute'}-bound; median tick "
        f"period {period * 1e3:.3f} ms; device time of one step "
        f"{'not read' if dev_ms is None else format(dev_ms, '.3f') + ' ms'}")
    return 100.0 * max(t_bytes, t_flops) / period
