"""The split of a traced window into the federated round's layers.

The program names its layers itself (DESIGN.md §3h): device work carries
the named scopes ``local_update/batch``, ``local_update/loss``,
``local_update/optimizer``, ``aggregate`` and ``eval`` in the ``op_name``
metadata of its HLO, and the superstep engine's host steps are the
profiler spans ``fl.*``.  A device op event in the trace names only its
HLO instruction (``%fusion.12 = ...``), so the op's scope is read from
the instruction of that name in the module that ran it:

  * the module is the event on the device's ``XLA Modules`` line that
    covers the op (``jit_superstep(<program id>)``);
  * its HLO is the ``Hlo Proto`` the profiler stores for that program in
    the same ``.xplane.pb``, on the ``/host:metadata`` plane, which the
    Python API does not expose: `hlo_op_names` reads it from the file's
    protobuf encoding;
  * a fusion takes the ``op_name`` of the convolution or dot it holds,
    else that of its fused computation's root (`_module_op_names`).

`load` returns `bench.trace.load`'s structure (so `bench.trace.summarize`
reads it, and names idle gaps by the ``fl.*`` spans too) with two keys
more: ``modules``, the module events of each device, and ``hlo``, the
``op_name`` of each instruction of each module.  `split` reduces it to
milliseconds per round of each layer.
"""
from __future__ import annotations

import bisect
import glob
import heapq
import os
import re
from typing import Dict, Iterator, List, Optional, Tuple

from bench import trace

MODULES_LINE = "XLA Modules"
METADATA_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"
# the ops a fusion is built around: XLA fuses a weight gradient's
# convolution into the momentum update that consumes it, whose add is the
# fusion's root
HEROES = ("convolution", "dot")
SPAN_PREFIXES = trace.SPAN_PREFIXES + ("fl.",)
ENGINE_SPANS = "fl."
LAYERS = ("batch", "forward", "backward", "optimizer", "aggregate", "eval")
UNSCOPED = "unscoped"
# the program's scopes; the innermost one in an op_name decides
_SCOPE = re.compile(r"(?:^|[/(])(local_update/batch|local_update/loss|"
                    r"local_update/optimizer|aggregate|eval)(?=[/)]|$)")
_BY_SCOPE = {"local_update/batch": "batch",
             "local_update/optimizer": "optimizer",
             "aggregate": "aggregate", "eval": "eval"}


def layer(op_name: Optional[str]) -> str:
    """The layer of an HLO ``op_name``: its innermost program scope; in
    ``local_update/loss`` the backward pass (remat's recompute included)
    is what JAX's transpose emitted, ``transpose(`` in the path."""
    found = list(_SCOPE.finditer(op_name or ""))
    if not found:
        return UNSCOPED
    scope = found[-1].group(1)
    if scope == "local_update/loss":
        return "backward" if "transpose(" in op_name else "forward"
    return _BY_SCOPE[scope]


# ---------------------------------------------------------------------------
# the HLO the profiler stores: protobuf encoding, read without its schema
# (field numbers of tsl/profiler/protobuf/xplane.proto and xla/hlo.proto)

XSPACE_PLANES = 1
XPLANE_NAME, XPLANE_EVENT_METADATA, XPLANE_STAT_METADATA = 2, 4, 5
MAP_VALUE = 2
XEVENT_METADATA_NAME, XEVENT_METADATA_STATS = 2, 5
XSTAT_METADATA_ID, XSTAT_METADATA_NAME = 1, 2
XSTAT_METADATA_ID_REF, XSTAT_BYTES = 1, 6
HLO_PROTO_MODULE = 1
MODULE_COMPUTATIONS = 3
COMPUTATION_INSTRUCTIONS, COMPUTATION_ID, COMPUTATION_ROOT_ID = 2, 5, 6
INSTR_NAME, INSTR_OPCODE, INSTR_METADATA = 1, 2, 7
INSTR_ID, INSTR_OPERANDS, INSTR_CALLED = 35, 36, 38
OP_METADATA_OP_NAME = 2


def _varint(buf: bytes, pos: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, pos
        shift += 7


def _fields(buf: bytes, lo: int = 0, hi: Optional[int] = None
            ) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message in ``buf[lo:hi]``: an int for
    a varint, a ``(start, end)`` slice for a length-delimited field."""
    pos, hi = lo, len(buf) if hi is None else hi
    while pos < hi:
        key, pos = _varint(buf, pos)
        num, wire = key >> 3, key & 7
        if wire == 0:
            value, pos = _varint(buf, pos)
        elif wire == 2:
            n, pos = _varint(buf, pos)
            value, pos = (pos, pos + n), pos + n
        elif wire == 1:
            value, pos = None, pos + 8
        elif wire == 5:
            value, pos = None, pos + 4
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {pos}")
        yield num, value


def _str(buf: bytes, span: Tuple[int, int]) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _ints(buf: bytes, value) -> List[int]:
    """A repeated integer field's entry: one varint, or a packed run."""
    if isinstance(value, int):
        return [value]
    out, pos = [], value[0]
    while pos < value[1]:
        v, pos = _varint(buf, pos)
        out.append(v)
    return out


def _module_op_names(buf: bytes, lo: int, hi: int) -> Dict[str, str]:
    """``{instruction: op_name}`` of one serialized ``HloProto``.  What an
    instruction stands for decides, as its scope is the work it does:

      * a fusion takes the op_name of the convolution or dot it holds,
        else its fused computation's root's; a root that carries none (a
        tuple of a multi-output fusion, a bitcast or copy XLA added) takes
        that of its operands, in order;
      * then the instruction's own op_name;
      * an op with none, a layout copy XLA added around a loop, takes that
        of the first of its users that has one."""
    comps = {}      # computation id -> (its instructions by id, root id)
    for num, mod in _fields(buf, lo, hi):
        if num != HLO_PROTO_MODULE:
            continue
        for cnum, comp in _fields(buf, *mod):
            if cnum != MODULE_COMPUTATIONS:
                continue
            cid = root = None
            instrs = {}
            for f, v in _fields(buf, *comp):
                if f == COMPUTATION_ID:
                    cid = v
                elif f == COMPUTATION_ROOT_ID:
                    root = v
                elif f == COMPUTATION_INSTRUCTIONS:
                    ins = {"called": [], "operands": [], "users": [],
                           "op_name": ""}
                    for g, w in _fields(buf, *v):
                        if g == INSTR_NAME:
                            ins["name"] = _str(buf, w)
                        elif g == INSTR_OPCODE:
                            ins["opcode"] = _str(buf, w)
                        elif g == INSTR_ID:
                            ins["id"] = w
                        elif g == INSTR_OPERANDS:
                            ins["operands"] += _ints(buf, w)
                        elif g == INSTR_CALLED:
                            ins["called"] += _ints(buf, w)
                        elif g == INSTR_METADATA:
                            for h, x in _fields(buf, *w):
                                if h == OP_METADATA_OP_NAME:
                                    ins["op_name"] = _str(buf, x)
                    instrs[ins.get("id")] = ins
            for ins in instrs.values():
                for o in ins["operands"]:
                    if o in instrs:
                        instrs[o]["users"].append(ins)
            comps[cid] = (instrs, root)

    def fused(ins, depth):
        """A fusion's op_name: that of a convolution or dot inside it, the
        work the fusion is built around, else its root's, or where that
        has none its operands', nearest the root first."""
        if ins.get("opcode") != "fusion" or not ins["called"] or depth > 8:
            return ""
        instrs, root = comps.get(ins["called"][0], ({}, None))
        order, todo, seen = [], [root], set()
        while todo:
            i = todo.pop(0)
            if i in seen or i not in instrs:
                continue
            seen.add(i)
            order.append(instrs[i])
            todo += instrs[i]["operands"]
        names = [(x.get("opcode") in HEROES, fused(x, depth + 1)
                  or x["op_name"]) for x in order]
        return next((n for hero, n in names if hero and n), "") or next(
            (n for _, n in names if n), "")

    memo: Dict[int, str] = {}

    def named(ins):
        if id(ins) not in memo:
            memo[id(ins)] = fused(ins, 0) or ins["op_name"]
        return memo[id(ins)]

    out = {}
    for instrs, _ in comps.values():
        for ins in instrs.values():
            name = named(ins)
            if not name:
                name = next((n for n in map(named, ins["users"]) if n), "")
            out[ins.get("name", "")] = name
    out.pop("", None)
    return out


def hlo_op_names(path: str) -> Dict[str, Dict[str, str]]:
    """``{module: {instruction: op_name}}`` for every program whose HLO
    the profiler stored in the ``.xplane.pb`` at ``path``; the module is
    named as the device's ``XLA Modules`` events name it."""
    with open(path, "rb") as f:
        buf = f.read()
    out: Dict[str, Dict[str, str]] = {}
    for num, plane in _fields(buf):
        if num != XSPACE_PLANES:
            continue
        name, metas, hlo_stat = None, [], None
        for f, v in _fields(buf, *plane):
            if f == XPLANE_NAME:
                name = _str(buf, v)
                if name != METADATA_PLANE:
                    break
            elif f == XPLANE_EVENT_METADATA:
                metas.append(v)
            elif f == XPLANE_STAT_METADATA:
                sid = sname = None
                for g, w in _fields(buf, *v):
                    if g == MAP_VALUE:
                        for h, x in _fields(buf, *w):
                            if h == XSTAT_METADATA_ID:
                                sid = x
                            elif h == XSTAT_METADATA_NAME:
                                sname = _str(buf, x)
                if sname == HLO_STAT:
                    hlo_stat = sid
        if name != METADATA_PLANE or hlo_stat is None:
            continue
        for entry in metas:
            for g, w in _fields(buf, *entry):
                if g != MAP_VALUE:
                    continue
                module, proto = None, None
                for h, x in _fields(buf, *w):
                    if h == XEVENT_METADATA_NAME:
                        module = _str(buf, x)
                    elif h == XEVENT_METADATA_STATS:
                        sid = data = None
                        for k, y in _fields(buf, *x):
                            if k == XSTAT_METADATA_ID_REF:
                                sid = y
                            elif k == XSTAT_BYTES:
                                data = y
                        if sid == hlo_stat and data is not None:
                            proto = data
                if module is not None and proto is not None:
                    out[module] = _module_op_names(buf, *proto)
    return out


# ---------------------------------------------------------------------------
# the trace, and its split


def load(log_dir: str) -> dict:
    """The newest ``.xplane.pb`` under ``log_dir``: `bench.trace.load`'s
    planes (host spans ``bench.*``, ``job.*`` and ``fl.*``), the module
    events of each device, and the HLO ``op_name`` table of each
    module."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    path = max(paths, key=os.path.getmtime)
    planes, modules = [], {}
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith(trace.DEVICE_PREFIX)
        lines = []
        for line in plane.lines:
            if device and line.name == MODULES_LINE:
                modules[plane.name] = sorted(
                    ([e.name, float(e.start_ns), float(e.duration_ns)]
                     for e in line.events), key=lambda e: e[1])
                continue
            if device and line.name != trace.OPS_LINE:
                continue
            events = [[trace.op_name(e.name) if device else e.name,
                       float(e.start_ns), float(e.duration_ns)]
                      for e in line.events
                      if device or e.name.startswith(SPAN_PREFIXES)]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes, "modules": modules, "hlo": hlo_op_names(path)}


def _innermost(intervals: List[Tuple[float, float, str]]
               ) -> Dict[str, float]:
    """Time of the union of ``intervals``, each instant given to the label
    of the latest-starting (then the shortest) interval that covers it:
    nested ops leave their parent only the parent's own time."""
    points = sorted([(s, 1, i) for i, (s, e, _) in enumerate(intervals)]
                    + [(e, 0, i) for i, (s, e, _) in enumerate(intervals)])
    out: Dict[str, float] = {}
    heap: List[Tuple[float, float, int]] = []
    ended = set()
    last = None
    for t, opening, i in points:
        while heap and heap[0][2] in ended:
            heapq.heappop(heap)
        if heap and t > last:
            lab = intervals[heap[0][2]][2]
            out[lab] = out.get(lab, 0.0) + (t - last)
        last = t
        if opening:
            s, e, _ = intervals[i]
            heapq.heappush(heap, (-s, e, i))
        else:
            ended.add(i)
    return out


def _overlap(a: List[List[float]], b: List[List[float]]) -> float:
    """Length of the intersection of two sorted disjoint interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(0.0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _ops(tr: dict, plane: dict, w0: float, w1: float
         ) -> Iterator[Tuple[float, float, str, str, str]]:
    """(start, end, module, op, layer) of each op of a device plane,
    clipped to the window; containers, and ops of no module or of a
    module whose HLO the trace lacks, are unscoped."""
    mods = tr.get("modules", {}).get(plane["name"], [])
    starts = [s for _, s, _ in mods]
    for l in plane["lines"]:
        for n, s, d in l["events"]:
            s0, e0 = max(s, w0), min(s + d, w1)
            if e0 <= s0:
                continue
            mod, lab = "", UNSCOPED
            k = bisect.bisect_right(starts, s) - 1
            if k >= 0 and s < mods[k][1] + mods[k][2]:
                mod = mods[k][0]
                if not n.startswith(trace.CONTAINERS):
                    lab = layer(tr.get("hlo", {}).get(mod, {}).get(n))
            yield s0, e0, mod, n, lab


def _devices(tr: dict, chips: int) -> List[dict]:
    return sorted((p for p in tr["planes"]
                   if p["name"].startswith(trace.DEVICE_PREFIX)),
                  key=lambda p: p["name"])[:chips]


def split(tr: dict, rounds: int, chips: int = 1) -> Dict[str, float]:
    """Milliseconds per round, averaged over the chips, of each layer in
    the traced window (``<layer>_ms``; ``unscoped_ms`` for device time
    under no scope, container ops' own time among it), ``busy_ms`` (their
    sum) and ``host_idle_ms``: time in which the device ran nothing
    while the host was inside an ``fl.*`` span."""
    w0, w1 = trace.window(tr)
    devices = _devices(tr, chips)
    engine = trace._union(
        [(max(s, w0), min(s + d, w1))
         for p in tr["planes"] if not p["name"].startswith(trace.DEVICE_PREFIX)
         for l in p["lines"] for n, s, d in l["events"]
         if n.startswith(ENGINE_SPANS) and min(s + d, w1) > max(s, w0)])
    total: Dict[str, float] = {}
    host_idle = 0.0
    for p in devices:
        iv = [(s, e, lab) for s, e, _, _, lab in _ops(tr, p, w0, w1)]
        for lab, t in _innermost(iv).items():
            total[lab] = total.get(lab, 0.0) + t
        busy = trace._union([(s, e) for s, e, _ in iv])
        edges = [w0] + [x for s, e in busy for x in (s, e)] + [w1]
        idle = [[edges[i], edges[i + 1]] for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        host_idle += _overlap(idle, engine)
    scale = 1e-6 / (max(len(devices), 1) * rounds)
    out = {f"{k}_ms": total.get(k, 0.0) * scale for k in LAYERS + (UNSCOPED,)}
    out["busy_ms"] = sum(total.values()) * scale
    out["host_idle_ms"] = host_idle * scale
    return out


def top_ops(tr: dict, chips: int = 1, top: int = 25,
            only: Optional[str] = None) -> List[list]:
    """The ops that took most device time in the window, containers left
    out, of layer ``only`` if given: ``[module, op, layer, seconds]``,
    averaged over the chips."""
    w0, w1 = trace.window(tr)
    devices = _devices(tr, chips)
    acc: Dict[Tuple[str, str, str], float] = {}
    for p in devices:
        for s, e, mod, n, lab in _ops(tr, p, w0, w1):
            if not n.startswith(trace.CONTAINERS) and only in (None, lab):
                acc[mod, n, lab] = acc.get((mod, n, lab), 0.0) + (e - s)
    rows = sorted(acc.items(), key=lambda kv: -kv[1])[:top]
    return [[m, n, lab, t / max(len(devices), 1) * 1e-9]
            for (m, n, lab), t in rows]
