"""The expert layers' share of their roofline in a decode step, in
percent: the least time the chip could take for what the step's expert
layers must do, over the device time they took.

What they must do is the family's ``moe_step_cost`` at the mean number of
occupied slots over the ticks of the statistics window that generated
tokens (``decode_mfu.tick_periods``, the driver's own stamps), with what
the PROGRAM counted of its own routing: the gauge ``moe.experts_hit``
(distinct held experts hit a layer a step, mean) and the share of all
token-expert selections that went to a held expert (counters
``moe.pairs_held`` / ``pairs_zero`` / ``pairs_absent``, published when
the server drains its device-side counts at ``close()``: since the ramp
began, not the window alone).  A layer reads its router and each hit
expert once and multiplies the held selections through an expert: required
bytes and operations only, so the share cannot pass 100.  The time is the
self time a decode step of the device ops under the ``moe`` scope
(``scope_time`` with this metric's ``args``), whatever they are called.

None where the family has no such cost, the program no such scope or
counters, or the run no device trace: the line leaves the metric out."""
from .. import requests
from ..common import log
from . import decode_mfu, scope_time


def program_counts():
    """(experts hit a layer a step, share of selections held here) as the
    program published them, or None."""
    try:
        from paddle_tpu import telemetry
    except ImportError:
        return None
    snap = telemetry.snapshot()
    hit = snap.get("gauges", {}).get("moe.experts_hit")
    c = snap.get("counters", {})
    pairs = [c.get("moe.pairs_" + k, 0)
             for k in ("held", "zero", "absent")]
    if hit is None or not sum(pairs):
        return None
    return float(hit), pairs[0] / sum(pairs)


def read(run: dict, args: dict):
    cost_of = getattr(run.get("family"), "moe_step_cost", None)
    if cost_of is None or not run.get("peaks") or "joined" not in run:
        return None
    counted = program_counts()
    dev_ms = scope_time.read(run, args)
    if counted is None or not dev_ms:
        return None
    ticks = decode_mfu.tick_periods(run["samples"], *run["stats_window"])
    rows = [requests.live_kv_tokens(run["joined"], t)[0] for t, _ in ticks]
    rows = [r for r in rows if r]
    if not rows:
        return None
    mean_rows = sum(rows) / len(rows)
    hit, held_share = counted
    pairs = mean_rows * run["sizes"]["k"] * held_share
    cost = cost_of(run["sizes"], mean_rows, hit, pairs)
    t_bytes = cost["bytes"] / run["peaks"]["hbm_bytes_per_s"]
    t_flops = cost["flops"] / run["peaks"]["bf16_flops_per_s"]
    log(f"[roofline] expert layers of a decode step at {mean_rows:.1f} "
        f"occupied slots: {hit:.2f} held experts hit and {pairs:.1f} "
        f"selections held a layer (the program's counts); must move "
        f"{cost['bytes'] / 1e9:.3f} GB ({t_bytes * 1e3:.3f} ms at peak) and "
        f"do {cost['flops'] / 1e9:.1f} GFLOP ({t_flops * 1e3:.3f} ms at "
        f"peak): {'memory' if t_bytes >= t_flops else 'compute'}-bound; "
        f"device time under moe {dev_ms:.3f} ms a step")
    return 100.0 * max(t_bytes, t_flops) * 1e3 / dev_ms
