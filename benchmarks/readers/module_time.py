"""Device time in ms of one execution of an XLA module: the median over
its whole runs inside the traced slice.

The module is chosen by ``module`` (a pattern on its name) and, because
every step the program's Engine builds is called ``jit__lambda``, by a
kernel that ran inside it: ``with_kernel``, where given, must match one of
the run's custom calls."""
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
