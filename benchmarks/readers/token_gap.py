"""A percentile, in ms, of the gaps between consecutive output tokens of
one request, over every request live in the statistics window: both
tokens of a gap reached the host inside it.  From the program's
``serving.emit`` records, one a tick, which stamp each request's tokens
where they are already on the host.  ``tpot_p50_ms`` is a median of
per-request means; this is the tail a client sees, a stall of the batch at
an admission included.  The count is on an earlier line."""
from ..common import log, percentile
from . import ring


def gaps_ms(emits) -> list:
    out = []
    for stamps in ring.token_times(emits).values():
        out += [(b - a) * 1e3 for a, b in zip(stamps, stamps[1:])]
    return out


def read(run: dict, args: dict):
    gaps = gaps_ms(ring.spans(run, "serving.emit"))
    if not gaps:
        return None
    q = float(args["percentile"])
    log(f"[token gaps] n={len(gaps)} gaps between consecutive tokens of "
        f"one request in the statistics window: p50 "
        f"{percentile(gaps, 50):.3f} ms, p{q:g} {percentile(gaps, q):.3f} "
        f"ms, max {max(gaps):.3f} ms")
    return percentile(gaps, q)
