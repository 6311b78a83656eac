"""What every cell needs: files found by name (the configuration's family
and its entry point among them), the device and its peaks, percentiles,
compile counting, and the last line.

Copies of sound pieces of the program live here so that a later PR cannot
move the yardstick: ``compile_count`` (chip_smoke.py), the peaks table
(framework/platform.DEVICE_PEAKS, as ``peaks.json``)."""
from __future__ import annotations

import importlib
import json
import math
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def log(msg: str) -> None:
    """An earlier line of the run: information, never the result."""
    print(msg, flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts), encoding="utf-8") as f:
        return json.load(f)


def merged(base: dict, over: dict) -> dict:
    """``base`` with ``over`` laid on top, dict by dict (the ``rehearse``
    block of a configuration or a mix)."""
    out = dict(base)
    for k, v in over.items():
        out[k] = merged(out[k], v) if isinstance(v, dict) and isinstance(
            out.get(k), dict) else v
    return out


def manifest() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        return json.load(f)


def cell(name: str) -> dict:
    """One workload of BENCHMARK.json with everything found by its names:
    the configuration file, the mix file, the end-to-end metric names and
    the per-layer metric entries this cell reports."""
    man = manifest()
    by_name = {w["name"]: w for w in man["workloads"]}
    if name not in by_name:
        raise SystemExit(f"benchmark: no workload {name!r} in BENCHMARK.json "
                         f"(have {sorted(by_name)})")
    w = by_name[name]
    cfg_entry = next(c for c in man["configs"] if c["name"] == w["config"])
    with open(os.path.join(ROOT, cfg_entry["file"]), encoding="utf-8") as f:
        config = json.load(f)
    here = lambda m: "workloads" not in m or name in m["workloads"]  # noqa: E731
    return {
        "name": name, "chips": int(w["chips"]), "config": config,
        "traffic": load_json("traffic", w["traffic"] + ".json"),
        "end_to_end": [m for m in man["end_to_end"] if here(m)],
        "per_layer": [m for m in man["per_layer"] if here(m)],
    }


def family(config: dict):
    """The module that knows the configuration's architecture:
    ``families/<family>.py`` (``families/__init__.py`` has what it gives).
    A file that states no family, or one that has no module, is an error,
    not a default."""
    name = config.get("family")
    if not name:
        raise SystemExit("benchmark: the configuration file states no "
                         "\"family\" (benchmarks/families/<family>.py)")
    if not os.path.isfile(os.path.join(HERE, "families", name + ".py")):
        raise SystemExit(f"benchmark: no module for family {name!r}: "
                         f"benchmarks/families/{name}.py is not there")
    return importlib.import_module("benchmarks.families." + name)


def entry_point(config: dict):
    """What the configuration's ``entry_point.call`` names in the program
    (``paddle_tpu.text.serving.DecodeServer``), imported."""
    module, _, name = config["entry_point"]["call"].rpartition(".")
    return getattr(importlib.import_module(module), name)


# --------------------------------------------------------------------------
# device
# --------------------------------------------------------------------------


def require_devices(chips: int, rehearse: bool):
    """The devices this cell runs on, or no result at all: a measurement
    path that finds no chip fails, it does not fall back to the CPU."""
    import jax

    devs = jax.devices()
    want = "cpu" if rehearse else "tpu"
    if devs[0].platform != want:
        raise SystemExit(f"benchmark: need a {want} device, jax found "
                         f"{devs[0].platform!r} ({devs[0].device_kind}); "
                         f"nothing measured")
    if len(devs) < chips:
        raise SystemExit(f"benchmark: the cell asks for {chips} chip(s), "
                         f"jax found {len(devs)}; nothing measured")
    return devs[:chips]


def peaks(device_kind: str) -> dict:
    """Published peaks of the device, from peaks.json.  A device that is
    not in the table is an error, not a default."""
    table = load_json("peaks.json")["peaks"]
    kind = (device_kind or "").lower()
    for key, row in table.items():
        if key in kind:
            return row
    raise SystemExit(f"benchmark: no peaks for device_kind {device_kind!r} "
                     f"in benchmarks/peaks.json; add it with its source")


def device_report(devs) -> dict:
    """The ``device`` object of the last line, as jax reports it."""
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def compile_count() -> int:
    """Executables compiled so far: the builds telemetry logged, plus the
    jit-cache entries under every executable the Engine holds (a call with
    a new argument type compiles again without a new build).  Copied from
    chip_smoke.compile_count."""
    from paddle_tpu import telemetry
    from paddle_tpu.text import engine

    n = len(telemetry.snapshot()["compiles"])
    for cache in (engine.ENGINE._steps, engine.ENGINE._gen):
        for key in cache.keys():
            n += jit_entries(cache.get(key))
    return n


def jit_entries(fn) -> int:
    """Entries in the jit cache of one (possibly instrumented) jitted
    function: 1 after its first compile, more after a retrace."""
    fn = getattr(fn, "_telemetry_inner", fn)
    return fn._cache_size() if hasattr(fn, "_cache_size") else 0


def jax_seed(seed: int) -> int:
    """``--seed`` may need more than 32 signed bits; jax keys take 31."""
    return int(seed) % (2 ** 31 - 1)


# --------------------------------------------------------------------------
# arithmetic
# --------------------------------------------------------------------------


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) with linear interpolation between
    order statistics (numpy's default rule), on plain floats."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of no samples")
    k = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def median(values) -> float:
    return percentile(values, 50.0)


# --------------------------------------------------------------------------
# the last line
# --------------------------------------------------------------------------


def read_layers(cell_: dict, run_data: dict) -> dict:
    """Every per-layer metric of the cell through its own reader
    (``layer_metrics/<metric>.json`` names ``readers/<reader>.py``).  A
    reader that finds nothing to read returns None."""
    out = {}
    for m in cell_["per_layer"]:
        spec = load_json("layer_metrics", m["name"] + ".json")
        reader = importlib.import_module(
            "benchmarks.readers." + spec["reader"])
        out[m["name"]] = reader.read(run_data, spec["args"])
    return out


def emit(ctx: dict, values: dict, compared: dict, attempted: int,
         failed: int, device: dict, run_data: dict | None = None) -> None:
    """Print the result as the last line of stdout.  ``values`` holds
    every number the run produced by metric name; the line carries the
    cell's end-to-end metrics (``--trace 0``) or its per-layer metrics
    (``--trace 1``, read from ``run_data`` by the metrics' own readers),
    and leaves out a per-layer metric whose reader found nothing.  A
    rehearsal on the CPU prints under ``cpu_rehearsal.*``, never under a
    device metric's name.

    ``compared`` decides ``correct``: {name: (number, limit)}, each number
    correct where it is at most its limit.  Every one is printed beside its
    limit as the last lines of stderr and under the line's last key."""
    correct = bool(compared) and all(
        math.isfinite(v) and v <= limit for v, limit in compared.values())
    cell_, trace, rehearse = (ctx["cell"], bool(ctx["args"].trace),
                              ctx["args"].rehearse)
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": {}, "device": device}
    if trace:
        from . import trace as trace_

        reduced = run_data["trace"]
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        line["breakdown"] = trace_.breakdown(reduced)
        run_data = dict(run_data, values=values, peaks=None if rehearse
                        else peaks(device["kind"]),
                        family=family(cell_["config"]))
        values = dict(values, **read_layers(cell_, run_data))
    for m in cell_["per_layer"] if trace else cell_["end_to_end"]:
        v = values.get(m["name"])
        if v is None:
            if not trace:
                raise SystemExit(f"benchmark: end-to-end metric "
                                 f"{m['name']!r} was not measured")
            continue
        name = ("cpu_rehearsal." + m["name"]) if rehearse else m["name"]
        line["metrics"][name] = {"value": float(v), "unit": m["unit"]}
    # a number that is not finite is null in the line (JSON has no nan)
    line["compared"] = {
        k: {"value": float(v) if math.isfinite(v) else None,
            "limit": float(limit)} for k, (v, limit) in compared.items()}
    sys.stdout.flush()
    for k, (v, limit) in compared.items():
        print(f"[compared] {k} {float(v)!r} limit {float(limit)!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
