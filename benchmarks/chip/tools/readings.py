#!/usr/bin/env python3
"""Readings that set a cell's limits for ``correct``, many seeds in one
process: the program's and the reference's compiled programs are built
once and reused for every seed, and the persistent compile cache is the
one ``run.py`` keeps.

    python3 benchmarks/chip/tools/readings.py --workload <cell> \
        --seeds 11,12,13 [--control] [--faults half_batch,no_exchange] \
        [--seconds 8] [--out readings.jsonl]

For every seed it prints one JSON line with the program's numbers (the
timed path against the reference, as a run compares them) and, where
asked, the control's (the reference computed in fp8) and each planted
fault's, all against the same float32 reference.  Training needs no
window; serving runs a short one at the cell's own load.
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)), "src"))


def train_rows(cell, seeds, control, faults, devices):
    from bench import reference, train
    tc = train.TrainCell(cell, devices)
    ref = reference.Reference(cell.arch, cell.model, cell.mix)
    ctl = reference.Reference(cell.arch, cell.model, cell.mix, "fp8")
    steps = cell.mix["checked_steps"]
    for seed in seeds:
        t0 = time.perf_counter()
        state, prog = tc.first_steps(seed)
        del state
        t1 = time.perf_counter()
        want = ref.train_readings(seed, tc.devices, steps=steps)
        row = {"seed": seed, "program": train.compare(prog, want),
               "losses": {"program": prog["losses"],
                          "reference": want["losses"]},
               "program_s": t1 - t0, "reference_s": time.perf_counter() - t1}
        if control:
            row["control"] = train.compare(
                ctl.train_readings(seed, tc.devices, steps=steps), want)
        for fault in faults:
            row[fault] = train.compare(
                ref.train_readings(seed, tc.devices, steps=steps,
                                   fault=fault), want)
        row["all_s"] = time.perf_counter() - t0
        yield row


def serve_rows(cell, seeds, control, seconds, devices):
    from bench import reference, serve
    sc = serve.ServeCell(cell, devices)
    ref = reference.Reference(cell.arch, cell.model, cell.mix)
    ctl = reference.Reference(cell.arch, cell.model, cell.mix, "fp8") \
        if control else None
    for seed in seeds:
        rec, served, _, prompts = serve.serve_once(sc, seed, seconds)
        t1 = time.perf_counter()
        nums, checked = serve.compare(ref, cell.mix, seed, prompts, served,
                                      control=ctl)
        yield {"seed": seed, "numbers": nums, "checked": checked,
               "window_s": rec.t_close - rec.t_open,
               "reference_s": time.perf_counter() - t1}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--faults", default="")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    import run
    from bench import spec
    cell = spec.Cell(args.workload)
    jax = run.setup_jax()
    devices = jax.devices()
    seeds = [int(s) for s in args.seeds.split(",")]
    faults = [f for f in args.faults.split(",") if f]
    if cell.mix["kind"] == "train":
        rows = train_rows(cell, seeds, args.control, faults, devices)
    else:
        rows = serve_rows(cell, seeds, args.control, args.seconds, devices)
    out = open(args.out, "a") if args.out else None
    try:
        for row in rows:
            row["workload"] = cell.name
            line = json.dumps(row)
            print(line, flush=True)
            if out:
                out.write(line + "\n")
                out.flush()
    finally:
        if out:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
