"""Device time in ms of one execution of an XLA module: the median over
its whole runs inside the traced slice.

The module is chosen by ``module`` (a pattern on its name: the train
step, ``jit_step_fn``).  Every step the program's Engine builds is called
``jit__lambda``; ``with_kernel``, where given, tells them apart by a custom
call that ran inside.  No accepted metric does (a kernel renamed would
blank it): a decode step is found by its scope, ``scope_time``."""
import re

from ..common import median


def read(run: dict, args: dict):
    if not run.get("trace"):
        return None
    name = re.compile(args["module"])
    yes = re.compile(args["with_kernel"]) if "with_kernel" in args else None
    ds = [m["s"] for m in run["trace"]["modules"]
          if name.search(m["name"])
          and (yes is None or any(yes.search(k) for k in m["kernels"]))]
    return median(ds) * 1e3 if ds else None
