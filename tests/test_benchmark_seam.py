"""The seam between the program and the benchmark that measures it.

``benchmarks/`` reads the program through its spans, counters, scopes
and the compile log (``PERF.md`` section 3), and the driver refuses a
PR whose last line lacks a metric of its cell: a span renamed in
``paddle_tpu/`` costs a PR on the chip.  So every cell of
``BENCHMARK.json`` is rehearsed here, on the CPU at a tiny size, with
and without the trace, and its last line held to what the driver's
check refuses on.  The helpers are the benchmark's own
(``benchmarks/tests/test_benchmark.py``, which tier-1 does not
collect); a child process runs each case, with a time limit of its own
(``rehearse``: 600 s).
"""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import common  # noqa: E402
from benchmarks.tests.test_benchmark import check_line, rehearse  # noqa: E402


@pytest.mark.parametrize("trace_flag", [0, 1])
@pytest.mark.parametrize("workload",
                         [w["name"] for w in common.manifest()["workloads"]])
def test_cell_rehearses_to_a_line_the_driver_accepts(workload, trace_flag):
    listed = common.cell(workload)["per_layer" if trace_flag
                                   else "end_to_end"]
    line, out = rehearse(ROOT, workload, trace_flag)
    got = check_line(line, [m["name"] for m in listed])
    if trace_flag:
        # a device trace and the chip's peaks are not to be had here; what
        # is read from the program's own spans and counters is
        want = {m["name"] for m in listed
                if m["source"].startswith("program_")}
        assert all(v["value"] == 0.0 for k, v in line["metrics"].items()
                   if "compiles_in_window" in k), out[-1500:]
    else:
        want = {m["name"] for m in listed}
    assert got >= want, (sorted(want - got), out[-1500:])
