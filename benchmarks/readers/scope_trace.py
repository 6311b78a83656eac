"""Device time by the part of the model an op belongs to, and idle gaps by
the program's own spans: what the ``scope_time`` reader and the host-span
readers share.  Three steps, the last two checkable on a recorded extract
(``tests/data/``) without a chip:

``extract(path)``  the run's own ``.xplane.pb`` as plain lists: per device
    plane the ``XLA Modules`` runs and the ``XLA Ops`` events (HLO op name,
    start, duration), and from the host planes ``bench.slice`` and every
    span the program wrote (a ``TraceAnnotation`` that carries a
    ``span_id`` stat: ``telemetry.span``).  Seconds on the trace's clock.
``scopes``         ``telemetry.executable_scopes(since)`` of the program
    that just ran: {HLO op name: op_name path} for each executable.  A v5e
    trace was read by hand first (PERF.md section 6, PR 25): an ``XLA Ops``
    event has three stats (``device_offset_ps``, ``device_duration_ps``,
    ``Time Scale Multiplier``) and its name is the HLO text without its
    metadata, so no event carries an op_name; the compiled module's text
    does, and the join is on the op's name.
``reduce(ex, scopes)``  for every whole module run inside the slice: the
    executable it is a run of (same module name, the one that knows most of
    the run's ops), and self seconds of each op under its path.  A run is
    of a step kind when its ops' paths hold the kind's scope
    (``serving.async_step``), never by ``jit__lambda`` or a kernel's name.

Readers are not handed the trace's path: they run in the process that just
wrote it, so the newest ``.xplane.pb`` under ``benchmarks/_run/*/trace`` is
the run's own.  It is extracted once and kept for the other readers.  With
a program that has no ``executable_scopes`` (an older commit) or a trace
with no device plane (the CPU rehearsal) ``load`` gives None."""
from __future__ import annotations

import glob
import os
import re
import time

from .. import trace
from ..common import HERE, log, median

# the parts the program names, in the order the tables print them; an op's
# part is the innermost of these in its path
PARTS = ("embed", "ln", "attn", "kv_gather", "mlp", "lm_head", "sample",
         "loss", "optimizer", "grad_accum")
REMAT = ("rematted_computation", "checkpoint")
_OP = re.compile(r"^%([\w.\-]+) = .*?\s([a-z][a-z\-]*)\(")
_kept: dict = {}                     # xplane path -> what load() returned


def newest_xplane():
    found = glob.glob(os.path.join(HERE, "_run", "*", "trace", "plugins",
                                   "profile", "*", "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def extract(path: str) -> dict:
    from jax.profiler import ProfileData

    devices, spans, slices = [], [], []
    for plane in ProfileData.from_file(path).planes:
        if trace.DEVICE_PLANE.match(plane.name):
            dev = {"name": plane.name, "modules": [], "ops": []}
            for line in plane.lines:
                if line.name == trace.MODULE_LINE:
                    dev["modules"] = [
                        [trace.module_name(e.name), e.start_ns / 1e9,
                         e.duration_ns / 1e9] for e in line.events]
                elif line.name == trace.OP_LINE:
                    for e in line.events:
                        m = _OP.match(e.name)
                        dev["ops"].append(
                            [m.group(1) if m else e.name[:80],
                             m.group(2) if m else "", e.start_ns / 1e9,
                             e.duration_ns / 1e9])
            devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == trace.SLICE:
                        slices.append([e.start_ns / 1e9,
                                       e.duration_ns / 1e9])
                        continue
                    stats = dict(e.stats)
                    if "span_id" in stats:
                        spans.append([e.name, e.start_ns / 1e9,
                                      e.duration_ns / 1e9,
                                      int(stats["span_id"]),
                                      int(stats.get("parent", 0))])
    devices.sort(key=lambda d: d["name"])
    spans.sort(key=lambda s: s[1])
    return {"devices": devices, "spans": spans, "slice": slices[:1]}


def self_times(ops) -> list:
    """[(name, opcode, self seconds)] for ops [(name, opcode, start, dur)]
    of one run: every instant goes to the op that started last among those
    running then, so an op's time is less what ran inside it (a ``while``
    covers its body) and two ops that overlap share no instant: the sum is
    the union of the intervals, the run's busy time."""
    ops = sorted(ops, key=lambda e: (e[2], -e[3]))
    out = [[name, code, 0.0] for name, code, _, _ in ops]
    ends = [a + dur for _, _, a, dur in ops]
    times = sorted({t for (_, _, a, _), b in zip(ops, ends) for t in (a, b)})
    running, nxt, prev = [], 0, None     # running: indices, by start
    for t in times:
        while running and ends[running[-1]] <= prev:
            running.pop()                # over before this stretch began
        if running:
            out[running[-1]][2] += t - prev
        while nxt < len(ops) and ops[nxt][2] == t:
            running.append(nxt)
            nxt += 1
        prev = t
    return out


def components(path: str | None) -> list:
    """The names in an op_name path, outermost first, each without the
    transformations jax wraps around it: ``jit(<lambda>)/serving.step/
    vmap(lm_head)/dot_general`` -> ``<lambda>, serving.step, lm_head,
    dot_general``; ``transpose(jvp(ln))`` -> ``ln``."""
    return [re.sub(r"^(?:\w+\()+|\)+$", "", c)
            for c in (path or "").split("/") if c]


def part_of(path: str | None) -> str:
    """The innermost named part in an op_name path, ``(step)`` for an op
    directly under the step, ``(no path)`` where the program named none."""
    if not path:
        return "(no path)"
    for comp in reversed(components(path)):
        if comp in PARTS:
            return comp
    return "(step)"


def reduce(ex: dict, scopes: list) -> dict:
    """{"runs": [{"module", "executable", "kind", "s", "ops": [(name,
    opcode, path, self seconds)]}], "gaps": [(start, end)] of the first
    device, "window": (lo, hi)}.  ``kind`` is the ``serving.<kind>`` scope
    in the run's paths, or None."""
    if not ex["slice"] or not ex["devices"]:
        return {"runs": [], "gaps": [], "window": None}
    lo = ex["slice"][0][0]
    hi = lo + ex["slice"][0][1]
    by_module: dict = {}
    for sc in scopes:
        by_module.setdefault(sc["module"], []).append(sc)
    runs, gaps = [], []
    for i, dev in enumerate(ex["devices"]):
        ops = sorted(dev["ops"], key=lambda e: e[2])
        whole = sorted(dev["modules"], key=lambda e: e[1])[:-1]
        k = 0
        for module, a, dur in whole:
            if a < lo or a + dur > hi:
                continue
            while k < len(ops) and ops[k][2] < a:
                k += 1
            j = k
            while j < len(ops) and ops[j][2] < a + dur:
                j += 1
            mine = self_times(ops[k:j])
            names = {n for n, _, _ in mine}
            best = max(by_module.get(module, []), default=None,
                       key=lambda sc: len(names & sc["ops"].keys()))
            paths = best["ops"] if best else {}
            kinds = {c for n in names for c in components(paths.get(n))
                     if c.startswith("serving.")}
            runs.append({
                "module": module, "s": dur,
                "executable": best["name"] if best else None,
                "kind": sorted(kinds)[0] if kinds else None,
                "ops": [(n, c, paths.get(n), s) for n, c, s in mine]})
        if i == 0:                   # gaps: what no op of the slice covers
            t = lo
            for _, _, a, d in ops:
                if a + d <= lo or a >= hi:
                    continue
                if a > t:
                    gaps.append((t, a))
                t = max(t, min(a + d, hi))
            if hi > t:
                gaps.append((t, hi))
    return {"runs": runs, "gaps": gaps, "window": (lo, hi)}


def idle_by_span(red: dict, spans: list) -> dict:
    """Idle seconds of the slice by the program span over each gap: the
    innermost (shortest) span that covers most of the gap, else the span
    that overlaps it longest."""
    out: dict = {}
    for a, b in red["gaps"]:
        over = [(min(b, s0 + dur) - max(a, s0), dur, name)
                for name, s0, dur, _, _ in spans]
        most = [o for o in over if o[0] * 2 >= b - a]
        if most:
            best = min(most, key=lambda o: o[1])[2]
        else:
            best = max((o for o in over if o[0] > 0),
                       default=(0, 0, "(no program span)"))[2]
        out[best] = out.get(best, 0.0) + (b - a)
    return out


def by_part(runs: list) -> dict:
    """Self seconds a run by part: the median over ``runs`` (the run that
    was on the device when the profiler started is cut short)."""
    per_run = []
    for r in runs:
        mine: dict = {}
        for _, _, path, s in r["ops"]:
            p = part_of(path)
            mine[p] = mine.get(p, 0.0) + s
        per_run.append(mine)
    return {p: median([m.get(p, 0.0) for m in per_run])
            for p in {p for m in per_run for p in m}}


def load(run: dict):
    """The run's reduced trace, or None where there is nothing to read.
    The first call of a run logs the tables."""
    path = newest_xplane()
    if path is None or not run.get("trace"):
        return None
    if path in _kept:
        return _kept[path]
    from paddle_tpu import telemetry

    _kept.clear()
    _kept[path] = None
    ask = getattr(telemetry, "executable_scopes", None)
    ex = extract(path)
    if ask is None or not ex["devices"] or not ex["slice"]:
        return None
    t0 = time.perf_counter()
    # the executables called since the slice began, on the program's
    # clock: the trace was written when the slice ended (its file's time),
    # and stopping the profiler takes seconds, so half a minute of room
    written = os.path.getmtime(path) - (time.time() - t0)
    scopes = ask(since=written - ex["slice"][0][1] - 30.0)
    t_scopes = time.perf_counter() - t0
    red = reduce(ex, scopes)
    red["spans"] = ex["spans"]
    log(f"[scopes] {os.path.relpath(path, HERE)}: "
        f"{len(red['runs'])} whole module runs in the slice; op_name paths "
        f"of {[s['name'] for s in scopes]} compiled again in "
        f"{t_scopes:.2f}s")
    groups: dict = {}
    for r in red["runs"]:
        groups.setdefault((r["module"], r["executable"], r["kind"]),
                          []).append(r)
    for (module, exe, kind), rs in groups.items():
        parts = by_part(rs)
        log(f"[scopes] {module} = {exe} (kind scope {kind}), {len(rs)} "
            f"runs, median {median([r['s'] for r in rs]) * 1e3:.3f} ms: "
            f"median self ms a run by part "
            f"{ {p: round(parts[p] * 1e3, 3) for p in (*PARTS, '(step)', '(no path)') if p in parts} }")
    idle = idle_by_span(red, ex["spans"])
    log(f"[scopes] idle seconds of the slice by program span: "
        f"{ {k: round(v, 6) for k, v in sorted(idle.items(), key=lambda kv: -kv[1])} }")
    _kept[path] = red
    return red
