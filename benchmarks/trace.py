"""From a ``jax.profiler`` trace to numbers.

Two steps, so that the second can be checked on a small recorded extract
(``tests/data/``) without a chip:

``extract(path)``  reads the ``.xplane.pb`` with ``jax.profiler.ProfileData``
    into plain lists: for every device plane the events of its ``XLA
    Modules`` and ``XLA Ops`` lines, and from the host planes the
    benchmark's own ``bench.*`` annotations.  Times are seconds on the
    trace's clock, which host and device share.
``reduce(ex)``     cuts everything to the traced slice (the ``bench.slice``
    annotation), and gives device busy seconds (union of op intervals,
    averaged over the device planes), every whole run of a module with the
    kernels (custom calls) that ran inside it, per-op self time, and the
    idle gaps attributed to the host span that covers most of each.

What the planes and lines hold was read off a v5e trace by hand (PERF.md
section 6, PR 23).  Device planes are ``/device:TPU:<n>``.  ``XLA Modules``
has one event per executable run, ``jit_<fn>(<fingerprint>)``; every step
the program's Engine builds is a lambda, so they are all ``jit__lambda``
and a reader tells them apart by the kernels inside.  ``XLA Ops`` has one
event per HLO op, named by its whole HLO text, and nests: a ``while``
covers the ops of its body, so time per op is *self* time.  The run that
is on the device when the profiler stops is cut short, so the last run of
each device is left out.  Host threads are under ``/host:CPU``."""
from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
SLICE = "bench.slice"


def annotate(name: str):
    """A host span in the profiler's own trace (no-op when none runs)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


class Slice:
    """The traced slice: ``start()`` begins the profile and opens the
    ``bench.slice`` span, ``stop()`` closes both.  The trace is written
    under ``out_dir``, which is inside the checkout."""

    def __init__(self, out_dir: str):
        self.out_dir = out_dir
        self._span = None

    def start(self) -> None:
        import jax

        os.makedirs(self.out_dir, exist_ok=True)
        jax.profiler.start_trace(self.out_dir)
        self._span = annotate(SLICE)
        self._span.__enter__()

    def stop(self) -> str:
        import jax

        self._span.__exit__(None, None, None)
        self._span = None
        jax.profiler.stop_trace()
        return newest_xplane(self.out_dir)


def newest_xplane(out_dir: str) -> str:
    found = glob.glob(os.path.join(out_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {out_dir}")
    return max(found, key=os.path.getmtime)


def module_name(name: str) -> str:
    """``jit_step_fn(123456)`` -> ``jit_step_fn``: the fingerprint changes
    with every compile, the name does not."""
    return re.sub(r"\(\d+\)$", "", name)


_HLO = re.compile(r"^%([\w.\-]+) = .*?\s([a-z][a-z\-]*)\(")


def op_name(text: str) -> str:
    """``%fusion.12 = bf16[..]{..} fusion(...)`` -> ``fusion.12 fusion``:
    the op's name and kind out of its HLO text."""
    m = _HLO.match(text)
    return f"{m.group(1)} {m.group(2)}" if m else text[:80]


def extract(path: str) -> dict:
    """The events this benchmark reads, as plain data (JSON-serialisable)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, host = [], []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {}
            for line in plane.lines:
                if line.name in (MODULE_LINE, OP_LINE):
                    short = module_name if line.name == MODULE_LINE \
                        else op_name
                    lines[line.name] = [
                        [short(e.name), e.start_ns / 1e9,
                         e.duration_ns / 1e9] for e in line.events]
            devices.append({"name": plane.name, "lines": lines})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        host.append([e.name, e.start_ns / 1e9,
                                     e.duration_ns / 1e9])
    devices.sort(key=lambda d: d["name"])
    host.sort(key=lambda e: e[1])
    return {"devices": devices, "host": host}


def _clip(events, lo, hi):
    out = []
    for name, start, dur in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            out.append((name, a, b))
    return out


def _union(intervals):
    """Merged (start, end) intervals, sorted."""
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def _self_times(clipped) -> dict:
    """{op: seconds not covered by an op nested inside it}."""
    out: dict = {}
    stack = []                                   # (end, name) of open ops
    for name, a, b in sorted(clipped, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0] <= a:
            stack.pop()
        if stack:
            out[stack[-1][1]] -= min(b, stack[-1][0]) - a
        out[name] = out.get(name, 0.0) + (b - a)
        stack.append((b, name))
    return out


def _kernels_in(ops, start, end) -> list:
    """Base names of the custom calls that ran in [start, end]."""
    names = set()
    for name, a, dur in ops:
        if a >= start and a + dur <= end and name.endswith(" custom-call"):
            names.add(re.sub(r"\.\d+$", "", name.split(" ")[0]))
    return sorted(names)


def reduce(ex: dict) -> dict:
    """Numbers of the traced slice, as plain data:

    window_s   length of the ``bench.slice`` span
    busy_s     union of device-op intervals inside it, mean over devices
    modules    [{"name", "s", "kernels"}] for every whole run inside it
    ops        {op: self seconds}, summed over devices
    gaps       [(covering host span or "(no bench span)", seconds)] per
               idle gap of the first device, longest first
    """
    slices = [e for e in ex["host"] if e[0] == SLICE]
    if not slices:
        raise ValueError("trace holds no bench.slice annotation")
    lo = slices[0][1]
    hi = lo + slices[0][2]
    spans = [e for e in _clip(ex["host"], lo, hi) if e[0] != SLICE]
    busy, modules, ops, gaps = [], [], {}, []
    for i, dev in enumerate(ex["devices"]):
        op_events = dev["lines"].get(OP_LINE) or dev["lines"].get(
            MODULE_LINE, [])
        clipped = _clip(op_events, lo, hi)
        merged = _union((a, b) for _, a, b in clipped)
        busy.append(sum(b - a for a, b in merged))
        for name, s in _self_times(clipped).items():
            ops[name] = ops.get(name, 0.0) + s
        runs = sorted(dev["lines"].get(MODULE_LINE, []), key=lambda e: e[1])
        for name, start, dur in runs[:-1]:      # the last one is cut short
            if start >= lo and start + dur <= hi:
                modules.append({"name": name, "s": dur, "kernels":
                                _kernels_in(op_events, start, start + dur)})
        if i == 0:
            edges = [lo] + [t for ab in merged for t in ab] + [hi]
            for a, b in zip(edges[0::2], edges[1::2]):
                if b > a:
                    gaps.append((_covering(spans, a, b), b - a))
    gaps.sort(key=lambda g: -g[1])
    return {"window_s": hi - lo,
            "busy_s": sum(busy) / len(busy) if busy else 0.0,
            "modules": modules, "ops": ops, "gaps": gaps,
            "n_devices": len(ex["devices"])}


def _covering(spans, a, b) -> str:
    """The host span that overlaps [a, b] longest."""
    best, best_s = "(no bench span)", 0.0
    for name, sa, sb in spans:
        s = min(b, sb) - max(a, sa)
        if s > best_s:
            best, best_s = name, s
    return best


def breakdown(red: dict, top: int = 10) -> dict:
    """The optional ``breakdown`` of a ``--trace 1`` line: the device
    operations with most time, and idle seconds by what the host was doing
    (gaps summed by covering span)."""
    ops = sorted(red["ops"].items(), key=lambda kv: -kv[1])[:top]
    by_span: dict = {}
    for name, s in red["gaps"]:
        by_span[name] = by_span.get(name, 0.0) + s
    gaps = sorted(by_span.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in gaps]}
