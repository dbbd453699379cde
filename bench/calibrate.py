#!/usr/bin/env python3
"""Readings that the limits of `correct` are set from, for one cell.

    python3 bench/calibrate.py --workload <name> --seeds 12 --controls 3 \\
        [--first-seed N] [--out readings.json]

In one process, for each seed: the program's check jobs against the
reference (the lower readings); and on the first ``--controls`` seeds the
control -- the reference computed in the precision below the one the
configuration states (``CONTROL_DTYPE`` of its reference file) -- and
each fault planted in the reference put in the program's place (the upper
readings).  Prints one JSON line per reading and writes them all to
``--out``.  The benchmark's own runs never run this.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def as_program(ref_readings: dict) -> dict:
    """Reference readings in the shape of the program's."""
    import numpy as np
    out = dict(ref_readings)
    out["evals"] = [(r, float(np.mean(s)), float(np.min(s)))
                    for r, s in sorted(ref_readings["scores"].items())]
    return out


def readings(cell, seed: int, controls: bool) -> list:
    import jax
    from bench import harness, reference
    job = harness.Job(cell, seed)
    t0 = time.perf_counter()
    prog = harness.program_readings(job)
    t1 = time.perf_counter()
    ref = harness.reference_readings(job)
    t2 = time.perf_counter()
    rows = [{"seed": seed, "kind": "program",
             "numbers": harness.compare(prog, ref),
             "program_s": t1 - t0, "reference_s": t2 - t1}]
    if controls:
        cast = reference.rounding(cell.ref.CONTROL_DTYPE)
        ctrl = harness.reference_readings(job, cast=cast)
        rows.append({"seed": seed, "kind": "control:" + cell.ref.CONTROL_DTYPE,
                     "numbers": harness.compare(as_program(ctrl), ref)})
        for fault in reference.FAULTS:
            bad = harness.reference_readings(job, fault=fault)
            rows.append({"seed": seed, "kind": "fault:" + fault,
                         "numbers": harness.compare(as_program(bad), ref)})
    del job
    jax.clear_caches()
    return rows


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, default=12)
    p.add_argument("--controls", type=int, default=3)
    p.add_argument("--first-seed", type=int, default=5_000_000_000)
    p.add_argument("--out", default=None)
    args = p.parse_args(argv)
    from bench import harness
    cell = harness.load_cell(args.workload)
    harness.enable_cache()
    harness.require_chips(cell.chips)
    rows = []
    for i in range(args.seeds):
        for row in readings(cell, args.first_seed + 7919 * i,
                            i < args.controls):
            print(json.dumps(row), flush=True)
            rows.append(row)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
