#!/usr/bin/env python3
"""One process, one cell, one run.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in BENCHMARK.json.  Everything that
belongs to it is found by name: ``configs/<config>.json`` (whose ``family``
names ``families/<family>.py``, the one place that knows the architecture,
its plain reference and what a step of it must do, and whose
``entry_point.call`` names what is driven in the program),
``traffic/<mix>.json`` (whose ``kind`` names ``generators/<kind>.py``) and,
for a traced run, ``layer_metrics/<metric>.json`` (whose ``reader`` names
``readers/<reader>.py``).  A new cell, mix, configuration, family or
per-layer metric is new files and new entries; no file here needs an edit.
A new end-to-end metric does: the generator kind computes it.

The run needs a TPU and fails without one.  ``--rehearse`` asks for the CPU
instead, at the tiny size in each file's ``rehearse`` block, for the tests:
it proves the control flow, never the chip, and prints its numbers under
``cpu_rehearsal.*``.  The last line of stdout is the result as one JSON
object; earlier lines are information."""
from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, tiny size; for the tests, never a result")
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    if not os.path.isdir(os.path.join(ROOT, "paddle_tpu")):
        raise SystemExit("benchmark: no paddle_tpu package beside "
                         "benchmarks/: nothing to measure")
    from benchmarks import common

    cell = common.cell(args.workload)
    if args.rehearse:
        from paddle_tpu.framework.platform import force_cpu

        force_cpu(cell["chips"])
        for part in ("config", "traffic"):
            cell[part] = common.merged(cell[part],
                                       cell[part].get("rehearse", {}))
    devices = common.require_devices(cell["chips"], args.rehearse)
    common.log(f"[device] platform={devices[0].platform} "
               f"kind={devices[0].device_kind} count={len(devices)}; "
               f"importing jax and reaching the device took "
               f"{time.perf_counter() - T_PROCESS_START:.2f}s")

    # traces of this run, inside the checkout, emptied before each run
    scratch = os.path.join(ROOT, "benchmarks", "_run", args.workload)
    shutil.rmtree(scratch, ignore_errors=True)
    generator = importlib.import_module(
        "benchmarks.generators." + cell["traffic"]["kind"])
    generator.run({
        "cell": cell, "args": args, "scratch": scratch, "devices": devices,
        "t_process_start": T_PROCESS_START,
    })


if __name__ == "__main__":
    main()
