"""Per-request times: the benchmark's own records joined with the
program's ``serving.request`` and ``serving.prefill`` events by ``rid``.

All times are ``time.perf_counter`` seconds (the program stamps its events
with the same clock).  A record is what the load generator knows:
``{"rid", "t_due", "t_submit", "prompt_len", "out_len"}``.  The join adds,
where the program recorded them: ``t_enqueue`` (the program's own submit
stamp), ``t_admit`` (a slot was claimed), ``t_first`` (first token on the
host), ``t_retire`` and ``tokens``."""
from __future__ import annotations



def ring_events() -> list:
    """The program's event ring as ``{"name", "t0", "t1", "args"}`` in
    seconds, through its public ``telemetry.chrome_events()``."""
    from paddle_tpu import telemetry

    out = []
    for e in telemetry.chrome_events():
        if e.get("ph") == "X":
            t0 = e["ts"] / 1e6
            out.append({"name": e["name"], "t0": t0,
                        "t1": t0 + e["dur"] / 1e6,
                        "args": e.get("args", {})})
    return out


def join(records, events) -> list:
    """One dict per record, in ``t_due`` order, with the event times of
    the same ``rid`` laid in.  An evicted and re-admitted request has
    several ``serving.prefill`` events: the first one holds its first
    token."""
    by_rid = {r["rid"]: dict(r) for r in records}
    for e in sorted(events, key=lambda e: e["t1"]):
        r = by_rid.get(e["args"].get("rid"))
        if r is None:
            continue
        if e["name"] == "serving.prefill" and "t_first" not in r:
            r["t_admit"], r["t_first"] = e["t0"], e["t1"]
        elif e["name"] == "serving.request":
            r["t_enqueue"], r["t_retire"] = e["t0"], e["t1"]
            r["tokens"] = int(e["args"]["tokens"])
    return sorted(by_rid.values(), key=lambda r: r["t_due"])


def due_in(joined, lo: float, hi: float) -> list:
    return [r for r in joined if lo <= r["t_due"] < hi]


def completed_in(joined, lo: float, hi: float) -> list:
    return [r for r in joined if "t_retire" in r and lo <= r["t_retire"] < hi]


def spans_ms(rows, start: str, end: str) -> list:
    """``end - start`` in ms for every row that has both."""
    return [(r[end] - r[start]) * 1e3 for r in rows
            if start in r and end in r]


def tpot_ms(rows) -> list:
    """(retire - first token) / (tokens - 1) per completed request: what a
    client sees between output tokens, stalls of the batch included."""
    return [(r["t_retire"] - r["t_first"]) * 1e3 / (r["tokens"] - 1)
            for r in rows
            if "t_retire" in r and "t_first" in r and r["tokens"] > 1]


def live_kv_tokens(joined, t: float) -> tuple:
    """(requests holding a slot at time ``t``, KV rows they hold): each has
    its prompt plus what it has generated by ``t``, interpolated between
    its first token and its retirement (a request still running when the
    run ended advances at the median pace of the finished ones)."""
    paces = [(r["t_retire"] - r["t_first"]) / (r["tokens"] - 1)
             for r in joined
             if "t_retire" in r and "t_first" in r and r["tokens"] > 1]
    pace = sorted(paces)[len(paces) // 2] if paces else None
    rows, tokens = 0, 0.0
    for r in joined:
        if "t_first" not in r or r["t_first"] > t:
            continue
        if "t_retire" in r:
            if r["t_retire"] <= t:
                continue
            own = (r["t_retire"] - r["t_first"]) / max(r["tokens"] - 1, 1)
        elif pace is None:
            continue
        else:
            own = pace
        done = 1 + (t - r["t_first"]) / own if own > 0 else r["out_len"]
        rows += 1
        tokens += r["prompt_len"] + min(done, r["out_len"])
    return rows, tokens
