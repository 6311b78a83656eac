"""The median length in ms of one of the program's spans, over those that
ended in the statistics window (``span``: its name; ``serving.prefill``
runs from the slot being claimed to the request's first token on the
host, so it holds what the prefill waited for on the device)."""
from ..common import log, median
from . import ring


def read(run: dict, args: dict):
    ms = [(e["t1"] - e["t0"]) * 1e3 for e in ring.spans(run, args["span"])]
    if not ms:
        return None
    log(f"[spans] {args['span']}: n={len(ms)} in the statistics window, "
        f"median {median(ms):.3f} ms, min {min(ms):.3f}, max {max(ms):.3f}")
    return median(ms)
