"""Device self time in ms of one part of a step, per run of the step: the
median over the step's whole runs inside the traced slice (the run that
was on the device when the profiler started is cut short) of the self time
of the ops that ``args`` select.

The step's runs: ``step_scope`` (a pattern on the ``serving.<kind>`` scope
in the paths of a run's ops: a decode step whatever its XLA module or its
kernels are called) or ``module`` (a pattern on the XLA module's name: the
train step, ``jit_step_fn``).  Its ops, all of them where ``args`` select
none (the whole step: the sum is its busy time): ``parts`` (the innermost
named part of the op's path is one of these), ``not_parts`` (is none of these:
what is left of the step, ops the program named no path for included),
``kernel`` (a pattern on the names in the op's path, where a Pallas
kernel's ``name=`` is a scope of its own, and on the HLO op's name less its
number), ``remat`` (true: the path runs through a
rematerialised region, false: it does not).

0.0 where the step ran and no op matched; None where there is no device
trace, no step run, or a program that does not say which op is whose."""
import re

from ..common import median
from . import scope_trace


def step_runs(red: dict, args: dict) -> list:
    if "step_scope" in args:
        want = re.compile(args["step_scope"])
        return [r for r in red["runs"] if r["kind"] and want.search(r["kind"])]
    want = re.compile(args["module"])
    return [r for r in red["runs"]
            if want.search(r["module"]) and r["executable"]]


def selects(args: dict):
    """A predicate on (op name, path) from the metric's ``args``."""
    kernel = re.compile(args["kernel"]) if "kernel" in args else None

    def yes(name, path) -> bool:
        if kernel is not None and not any(
                kernel.search(c) for c in
                (re.sub(r"\.\d+$", "", name),
                 *scope_trace.components(path))):
            return False
        part = scope_trace.part_of(path)
        if "parts" in args and part not in args["parts"]:
            return False
        if "not_parts" in args and part in args["not_parts"]:
            return False
        if "remat" in args:
            inside = any(c in scope_trace.REMAT
                         for c in scope_trace.components(path))
            if inside != bool(args["remat"]):
                return False
        return True

    return yes


def read(run: dict, args: dict):
    red = scope_trace.load(run)
    if red is None:
        return None
    runs = step_runs(red, args)
    if not runs:
        return None
    yes = selects(args)
    return 1e3 * median([sum(s for n, _, path, s in r["ops"] if yes(n, path))
                         for r in runs])
