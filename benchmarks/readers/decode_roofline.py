"""The decode step's share of its roofline, in percent: the least time the
chip could take for what the step must do, over the device time of one
execution of the decode-step module.

The least time is max(bytes / peak bytes/s, FLOPs / peak FLOP/s) with
bytes = every bf16 weight once + the KV rows live at the middle of the
traced slice, and FLOPs for one token per occupied slot
(``model.decode_step_cost``).  The run's earlier lines say which bound
applies."""
from .. import model, requests
from ..common import log
from . import module_time


def read(run: dict, args: dict):
    step_ms = module_time.read(run, args)
    if step_ms is None or not run.get("peaks") or "joined" not in run:
        return None
    lo, hi = run["slice"]
    rows, kv_tokens = requests.live_kv_tokens(run["joined"], (lo + hi) / 2)
    if not rows:
        return None
    cost = model.decode_step_cost(run["sizes"], rows, kv_tokens)
    t_bytes = cost["bytes"] / run["peaks"]["hbm_bytes_per_s"]
    t_flops = cost["flops"] / run["peaks"]["bf16_flops_per_s"]
    log(f"[roofline] decode step: {rows} occupied slots, {kv_tokens:.0f} "
        f"live KV tokens; must move {cost['bytes'] / 1e9:.3f} GB "
        f"({t_bytes * 1e3:.3f} ms at peak) and do {cost['flops'] / 1e9:.1f} "
        f"GFLOP ({t_flops * 1e3:.3f} ms at peak): "
        f"{'memory' if t_bytes >= t_flops else 'compute'}-bound; device "
        f"time of one step {step_ms:.3f} ms")
    return 100.0 * max(t_bytes, t_flops) * 1e3 / step_ms
