"""The mixers' share of their roofline in a decode step, in percent: the
least time the chip could take for what the step's mixers must do, over
the device time they took.

What they must do is the family's ``ssm_step_cost`` at the mean number of
occupied slots over the ticks of the statistics window that generated
tokens (``decode_mfu.tick_periods``, the driver's own stamps): the mixers'
weights once, and every occupied slot's recurrent state and conv window
read and written.  Required bytes only: slots that hold no request, the
copies a compiler adds and a state re-read by a second pass are the step's
own waste, so the share cannot pass 100.  The time is the self time a
decode step of the device ops under the ``ssm`` scope (``scope_time`` with
this metric's ``args``), whatever they are called.

None where the family has no such cost, the program no such scope, or the
run no device trace: the line leaves the metric out."""
from .. import requests
from ..common import log
from . import decode_mfu, scope_time


def read(run: dict, args: dict):
    cost_of = getattr(run.get("family"), "ssm_step_cost", None)
    if cost_of is None or not run.get("peaks") or "joined" not in run:
        return None
    dev_ms = scope_time.read(run, args)
    if not dev_ms:
        return None
    ticks = decode_mfu.tick_periods(run["samples"], *run["stats_window"])
    rows = [requests.live_kv_tokens(run["joined"], t)[0] for t, _ in ticks]
    rows = [r for r in rows if r]
    if not rows:
        return None
    mean_rows = sum(rows) / len(rows)
    cost = cost_of(run["sizes"], mean_rows)
    t_bytes = cost["bytes"] / run["peaks"]["hbm_bytes_per_s"]
    t_flops = cost["flops"] / run["peaks"]["bf16_flops_per_s"]
    log(f"[roofline] mixers of a decode step at {mean_rows:.1f} occupied "
        f"slots: must move {cost['bytes'] / 1e9:.3f} GB "
        f"({t_bytes * 1e3:.3f} ms at peak, of which state "
        f"{cost['state_bytes'] / 1e9:.3f} GB) and do "
        f"{cost['flops'] / 1e9:.1f} GFLOP ({t_flops * 1e3:.3f} ms at "
        f"peak): {'memory' if t_bytes >= t_flops else 'compute'}-bound; "
        f"device time under ssm {dev_ms:.3f} ms a step")
    return 100.0 * max(t_bytes, t_flops) * 1e3 / dev_ms
