#!/usr/bin/env python3
"""Split a cell's federated round into its layers, and time what tracing
costs.

    python3 bench/tools/layer_split.py <workload> [--seed N] [--jobs J] \\
        [--out FILE]

Drives whole jobs of the cell through the benchmark's own call
(`bench.harness.Job.run`): one that compiles, ``J`` timed with the
profiler off, then one timed inside a profiler trace.  Prints the traced
job's milliseconds per round of each layer (`bench.scopes.split`), the
ops that took most device time with their module and layer, the longest
idle gaps named by the innermost ``bench.*``/``job.*``/``fl.*`` span, and
the job's seconds traced and untraced.  With ``--out`` it writes the same
as one JSON object; the trace stays under `bench/.out/layer_split/`.  Needs
the cell's TPU chips, as `bench/run.py` does.
"""
import argparse
import json
import shutil
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def timed(job, rounds: int) -> float:
    t0 = time.perf_counter()
    job.run(rounds)
    return time.perf_counter() - t0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("workload")
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--jobs", type=int, default=3)
    p.add_argument("--out")
    args = p.parse_args(argv)
    import jax
    from bench import harness, scopes, trace
    cell = harness.load_cell(args.workload)
    harness.enable_cache()
    harness.require_chips(cell.chips)
    job = harness.Job(cell, args.seed)
    rounds = cell.rounds
    compile_s = timed(job, rounds)
    untraced = [timed(job, rounds) for _ in range(args.jobs)]
    log_dir = harness.OUT_DIR / "layer_split" / cell.name
    shutil.rmtree(log_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        traced = timed(job, rounds)
    jax.profiler.stop_trace()
    tr = scopes.load(str(log_dir))
    summary = trace.summarize(tr, chips=cell.chips)
    split = scopes.split(tr, rounds, cell.chips)
    modules = sorted({m for ms in tr["modules"].values() for m, _, _ in ms})
    result = {
        "workload": cell.name, "seed": args.seed, "rounds": rounds,
        "device": harness.device_info(cell.chips),
        "compile_job_s": compile_s, "untraced_job_s": untraced,
        "traced_job_s": traced,
        "round_s": {"untraced": statistics.median(untraced) / rounds,
                    "traced": traced / rounds},
        "split_ms_per_round": split,
        "busy_ms_per_round": summary["busy_s"] * 1e3 / rounds,
        "window_ms_per_round": summary["window_s"] * 1e3 / rounds,
        "modules": {m: m in tr["hlo"] for m in modules},
        "top_ops": scopes.top_ops(tr, cell.chips),
        "top_unscoped": scopes.top_ops(tr, cell.chips, 15, scopes.UNSCOPED),
        "idle_gaps": summary["idle_gaps"],
    }
    print(f"{cell.name}: {rounds} rounds a job on "
          f"{result['device']['kind']}")
    print(f"job seconds: compiling {compile_s:.3f}, untraced "
          f"{', '.join(f'{t:.4f}' for t in untraced)}, traced {traced:.4f}")
    print("ms per round: " + ", ".join(f"{k} {v:.4f}"
                                       for k, v in split.items()))
    print(f"busy {result['busy_ms_per_round']:.4f} ms of "
          f"{result['window_ms_per_round']:.4f} ms a round")
    for m, has in result["modules"].items():
        print(f"module {m}: {'HLO' if has else 'no HLO'} in the trace")
    for rows in ("top_ops", "top_unscoped"):
        print(rows)
        for m, n, lab, t in result[rows]:
            op_name = tr["hlo"].get(m, {}).get(n, "")
            print(f"  {t * 1e3:10.3f} ms  {lab:10s} {n}  ({m}) {op_name}")
    for n, t in result["idle_gaps"]:
        print(f"  gap {t * 1e3:8.3f} ms under {n}")
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(result, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
