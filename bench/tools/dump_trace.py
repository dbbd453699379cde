#!/usr/bin/env python3
"""Trace one job of a cell and print what the trace holds, to read by hand.

    python3 bench/tools/dump_trace.py <workload> [out.json]

Prints the planes and lines of the profiler trace, and the device ops that
took most time with the stats each event carries (the names the per-layer
metrics match are read from here).  With ``out.json`` it also writes the
reduced trace (`bench.trace.load`) of that job there.
"""
import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv):
    import jax
    from jax.profiler import ProfileData
    from bench import harness, trace
    cell = harness.load_cell(argv[0])
    harness.enable_cache()
    harness.require_chips(cell.chips)
    job = harness.Job(cell, 1)
    harness.program_readings(job)
    out = harness.OUT_DIR / "dump_trace"
    shutil.rmtree(out, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(out), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        job.run(cell.rounds)
    jax.profiler.stop_trace()
    path = max(out.rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    print("trace file", path.stat().st_size, "bytes")
    pd = ProfileData.from_file(str(path))
    for plane in pd.planes:
        lines = [(l.name, sum(1 for _ in l.events)) for l in plane.lines]
        print("PLANE", plane.name, lines[:12])
        if plane.name.startswith(trace.DEVICE_PREFIX):
            for l in plane.lines:
                tot = {}
                ex = {}
                for e in l.events:
                    tot[e.name] = tot.get(e.name, 0) + e.duration_ns
                    if e.name not in ex:
                        ex[e.name] = {k: str(v)[:80] for k, v in e.stats}
                top = sorted(tot.items(), key=lambda kv: -kv[1])[:25]
                print("  LINE", l.name)
                for n, t in top:
                    print("    ", round(t / 1e6, 3), "ms", n, ex[n])
    reduced = trace.load(str(out))
    print(json.dumps(trace.summarize(reduced), default=str)[:3000])
    if len(argv) > 1:
        Path(argv[1]).parent.mkdir(parents=True, exist_ok=True)
        with open(argv[1], "w") as f:
            json.dump(reduced, f)
    shutil.rmtree(out, ignore_errors=True)


if __name__ == "__main__":
    main(sys.argv[1:])
