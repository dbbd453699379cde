"""Reduction of a profiler trace to the numbers the metrics read.

`load` turns the JAX profiler's ``.xplane.pb`` into a plain structure of
planes, lines and events ``[name, start_ns, duration_ns]``, which is also
what a recorded trace under ``bench/tests/`` holds.  `summarize` reduces
that structure for one traced window:

  * device busy time: the union of the op intervals on each device's
    ``XLA Ops`` line, clipped to the window and averaged over the chips
    used; the idle share is 1 - busy / window;
  * device time by op name;
  * the idle gaps between device ops, each named by the innermost host
    span of the benchmark (`TraceAnnotation`) that covers its middle.

The window is the host span ``bench.window`` that the harness opens
around the traced jobs.
"""
from __future__ import annotations

import glob
import os
from typing import List, Optional, Sequence, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
# ops that hold other ops (a scan's loop): they count towards busy time,
# and their children, not they, towards the time by op
CONTAINERS = ("while.", "conditional.", "call.")
WINDOW_SPAN = "bench.window"
SPAN_PREFIXES = ("bench.", "job.")


def load(log_dir: str) -> dict:
    """The newest ``.xplane.pb`` under ``log_dir`` as planes of lines of
    events; host lines keep only the benchmark's own spans."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    planes = []
    for plane in pd.planes:
        device = plane.name.startswith(DEVICE_PREFIX)
        lines = []
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            events = [[op_name(e.name) if device else e.name,
                       float(e.start_ns), float(e.duration_ns)]
                      for e in line.events
                      if device or e.name.startswith(SPAN_PREFIXES)]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def op_name(hlo: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def _union(intervals: Sequence[Tuple[float, float]]) -> List[List[float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _spans(trace: dict) -> List[Tuple[str, float, float]]:
    return [(n, s, s + d)
            for p in trace["planes"] if not p["name"].startswith(DEVICE_PREFIX)
            for l in p["lines"] for n, s, d in l["events"]]


def window(trace: dict) -> Tuple[float, float]:
    spans = [(s, e) for n, s, e in _spans(trace) if n == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    return min(s for s, _ in spans), max(e for _, e in spans)


def summarize(trace: dict, chips: int = 1, top: int = 10) -> dict:
    """busy_s, window_s, op seconds by name, and the longest idle gaps."""
    w0, w1 = window(trace)
    devices = [p for p in trace["planes"]
               if p["name"].startswith(DEVICE_PREFIX)]
    devices = sorted(devices, key=lambda p: p["name"])[:chips]
    busy_total, by_name = 0.0, {}
    gaps: List[Tuple[float, float]] = []
    for p in devices:
        iv = []
        for l in p["lines"]:
            for n, s, d in l["events"]:
                s0, e0 = max(s, w0), min(s + d, w1)
                if e0 <= s0:
                    continue
                iv.append((s0, e0))
                if not n.startswith(CONTAINERS):
                    by_name[n] = by_name.get(n, 0.0) + (e0 - s0)
        merged = _union(iv)
        busy_total += sum(e - s for s, e in merged)
        edges = [w0] + [x for s, e in merged for x in (s, e)] + [w1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    n_dev = max(len(devices), 1)
    spans = _spans(trace)

    def label(t: float) -> str:
        cover = [(e - s, n) for n, s, e in spans
                 if s <= t < e and n != WINDOW_SPAN]
        return min(cover)[1] if cover else WINDOW_SPAN

    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    ops = sorted(by_name.items(), key=lambda kv: -kv[1])
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy_total / n_dev * 1e-9,
        "devices": len(devices),
        "op_s": {n: v / n_dev * 1e-9 for n, v in ops},
        "device_ops": [[n, v / n_dev * 1e-9] for n, v in ops[:top]],
        "idle_gaps": [[label((s + e) / 2), (e - s) * 1e-9]
                      for s, e in longest],
    }


def idle_pct(summary: Optional[dict]) -> Optional[float]:
    """100 x (1 - busy / window), or None where the trace saw no device."""
    if not summary or summary["devices"] == 0 or summary["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - summary["busy_s"] / summary["window_s"])
