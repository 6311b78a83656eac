"""The latent attention's share of its roofline in a decode step, in
percent: the least time the chip could take for what the step's absorbed
attention must do, over the device time it took.  The yardstick a latent
paged kernel is held to.

What it must do is the family's ``mla_attn_cost`` at the mean live cache
rows and occupied slots over the ticks of the statistics window that
generated tokens (``decode_mfu.tick_periods``, ``requests.live_kv_tokens``):
every live latent row read once a sublayer as published (not the lanes it
is padded to), the up-projection's two halves read once, the heads' scores
against the rows and the weighted sums.  Required bytes and operations
only, so the share cannot pass 100.  The time is the self time a decode
step of the device ops under the ``attn`` scope (``scope_time`` with this
metric's ``args``: what ``decode_attn_dev_ms`` reads).

None where the family has no such cost, the program no such scope, or the
run no device trace: the line leaves the metric out."""
from .. import requests
from ..common import log
from . import decode_mfu, scope_time


def read(run: dict, args: dict):
    cost_of = getattr(run.get("family"), "mla_attn_cost", None)
    if cost_of is None or not run.get("peaks") or "joined" not in run:
        return None
    dev_ms = scope_time.read(run, args)
    if not dev_ms:
        return None
    ticks = decode_mfu.tick_periods(run["samples"], *run["stats_window"])
    live = [requests.live_kv_tokens(run["joined"], t) for t, _ in ticks]
    live = [rk for rk in live if rk[0]]
    if not live:
        return None
    rows = sum(r for r, _ in live) / len(live)
    kv_tokens = sum(k for _, k in live) / len(live)
    cost = cost_of(run["sizes"], kv_tokens, rows)
    t_bytes = cost["bytes"] / run["peaks"]["hbm_bytes_per_s"]
    t_flops = cost["flops"] / run["peaks"]["bf16_flops_per_s"]
    log(f"[roofline] latent attention of a decode step at {rows:.1f} "
        f"occupied slots and {kv_tokens:.0f} live rows: must move "
        f"{cost['bytes'] / 1e9:.3f} GB ({t_bytes * 1e3:.3f} ms at peak, of "
        f"which rows {cost['row_bytes'] / 1e9:.3f} GB) and do "
        f"{cost['flops'] / 1e9:.1f} GFLOP ({t_flops * 1e3:.3f} ms at "
        f"peak): {'memory' if t_bytes >= t_flops else 'compute'}-bound; "
        f"device time under attn {dev_ms:.3f} ms a step")
    return 100.0 * max(t_bytes, t_flops) * 1e3 / dev_ms
