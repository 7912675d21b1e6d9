#!/usr/bin/env python3
"""Run one cell of the chip benchmark and print its result line.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``) names a configuration file and a traffic
mix; the mix's ``kind`` picks the driver (``bench/train.py`` or
``bench/serve.py``).  With ``--trace 0`` the result holds the cell's
end-to-end metrics.  With ``--trace 1`` the same untraced window is
followed by the mix's ``trace_seconds`` under the profiler (traces are
large and tracing slows the host), and the result holds the per-layer
metrics, each read by ``metrics/<name>.py``: those on the host clock
from the untraced window, those from the trace from the traced part,
whose ``busy_s``, ``window_s`` and ``breakdown`` it also gives.
``--keep <file>`` keeps a copy of the trace.

Every run compares what the timed path produced with the plain
reference once the window has closed; ``correct`` is true when every
compared number is within its limit (``limits/<cell>.json``).  The
numbers and limits are the result line's last key and the last lines on
standard error.  Off a TPU, or with fewer chips than the cell asks for,
the run prints no result and exits with 2.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)), "src"))


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep", help="with --trace 1: copy the .xplane.pb here")
    return ap.parse_args(argv)


def setup_jax():
    from repro.launch.env import enable_compile_cache
    enable_compile_cache()
    import jax
    # cache every program, however fast it compiles, so that a second run
    # of a cell compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def per_layer(cell, res) -> tuple[dict, dict]:
    """The cell's per-layer metrics from the trace and the host records,
    and the device's busy and window seconds with the breakdown."""
    from bench import xtrace
    ctx = dict(res["ctx"])
    summary = xtrace.reduce(ctx["trace_path"], ctx["devices"])
    ctx["trace"] = summary
    metrics = {}
    for m in cell.metrics("per_layer"):
        value = cell.reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return metrics, summary


def result_line(cell, res, devices, trace: bool) -> dict:
    checks = {k: {"value": res["numbers"][k], "limit": lim}
              for k, lim in cell.limits.items()}
    correct = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
                  for c in checks.values())
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices),
              "memory_peak_bytes": res["memory_peak_bytes"]}
    line = {"correct": correct, "attempted": res["attempted"],
            "failed": res["failed"]}
    if trace:
        metrics, summary = per_layer(cell, res)
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        line["breakdown"] = summary["breakdown"]
    else:
        metrics = {m["name"]: {"value": res["e2e"][m["name"]],
                               "unit": m["unit"]}
                   for m in cell.metrics("end_to_end")}
    line["metrics"] = metrics
    line["device"] = device
    line["window_compiles"] = res["window_compiles"]
    line["notes"] = res["notes"]
    line["checks"] = checks
    return line


def main(argv=None, *, root=None, here=None, require_tpu=True,
         drivers=None) -> int:
    """``root``, ``here``, ``require_tpu`` and ``drivers`` let the tests
    run a cell of their own on the CPU; a run of the benchmark leaves
    them as they are."""
    args = parse(argv)
    from bench import peaks, spec
    cell = spec.Cell(args.workload, root or spec.ROOT, here or spec.HERE)
    jax = setup_jax()
    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu"
                        or len(devices) < cell.chips):
        print(f"run.py: {cell.name} needs {cell.chips} TPU chip(s); JAX "
              f"finds {len(devices)} {devices[0].platform} device(s) "
              f"({devices[0].device_kind})", file=sys.stderr)
        return 2
    peak = peaks.peak_for(devices[0].device_kind) if require_tpu \
        else peaks.PEAKS["TPU v5 lite"]
    if drivers is None:
        from bench import serve, train
        drivers = {"train": train.run, "serve_continuous": serve.run}
    res = drivers[cell.mix["kind"]](
        cell, args, devices, peak, lambda: time.perf_counter() - T_START)
    try:
        if args.keep and res["profile"].path:
            os.makedirs(os.path.dirname(os.path.abspath(args.keep)),
                        exist_ok=True)
            shutil.copy(res["profile"].path, args.keep)
        line = result_line(cell, res, devices, bool(args.trace))
    finally:
        res["profile"].cleanup()
    print(json.dumps(line), flush=True)
    for name, c in line["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
