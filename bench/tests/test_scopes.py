"""The per-layer split (`bench/scopes.py`): the join of device ops to the
program's named scopes, on a hand-made trace and on a CPU trace of a
jitted function; and the existing reduction (`bench/trace.py`), whose
numbers it must leave as they are."""
import json
from pathlib import Path

import pytest

from bench import scopes, trace

TESTS = Path(__file__).resolve().parent


@pytest.mark.parametrize("op_name,want", [
    ("jit(superstep)/while/body/closed_call/local_update/batch/gather",
     "batch"),
    ("jit(superstep)/while/body/local_update/loss/jvp()/dot_general",
     "forward"),
    ("jit(superstep)/while/body/local_update/loss/transpose(jvp())/mul",
     "backward"),
    # remat's recompute runs inside the transpose
    ("jit(superstep)/while/body/local_update/loss/transpose(jvp("
     "local_update/loss))/jvp()/checkpoint/rematted_computation/cos",
     "backward"),
    ("jit(superstep)/while/body/local_update/optimizer/sub", "optimizer"),
    ("jit(superstep)/while/body/aggregate/dot_general", "aggregate"),
    # the innermost scope decides: an eval inside an aggregate is eval
    ("jit(superstep)/while/body/aggregate/vmap()/eval/log_softmax", "eval"),
    ("jit(superstep)/eval/reduce_sum", "eval"),
    ("jit(superstep)/while", "unscoped"),
    ("jit(superstep)/evaluate/add", "unscoped"),
    ("", "unscoped"),
    (None, "unscoped"),
])
def test_layer_of_op_name(op_name, want):
    assert scopes.layer(op_name) == want


# ---------------------------------------------------------------------------
# a hand-made .xplane.pb: the protobuf encoding the profiler writes


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _msg(*fields) -> bytes:
    """A message from (field number, int | str | bytes) pairs."""
    out = b""
    for num, v in fields:
        if isinstance(v, int):
            out += _varint(num << 3) + _varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += _varint(num << 3 | 2) + _varint(len(v)) + v
    return out


def _plane(name, lines=(), metadata=(), stat_metadata=()):
    """An XPlane; ``lines`` is [(line name, [(event id, start ns, dur ns)])],
    ``metadata`` [(event id, name, stats)]."""
    fields = [(1, 1), (2, name)]
    for k, (lname, events) in enumerate(lines):
        ev = [(4, _msg((1, i), (2, s * 1000), (3, d * 1000)))
              for i, s, d in events]
        fields.append((3, _msg((1, k), (2, lname), (3, 0), *ev)))
    for i, n, stats in metadata:
        meta = _msg((1, i), (2, n), *[(5, s) for s in stats])
        fields.append((4, _msg((1, i), (2, meta))))
    for i, n in stat_metadata:
        fields.append((5, _msg((1, i), (2, _msg((1, i), (2, n))))))
    return _msg(*fields)


def _instr(iid, name, opcode, op_name="", called=(), operands=()):
    fields = [(1, name), (2, opcode), (35, iid)]
    if op_name:
        fields.append((7, _msg((1, opcode), (2, op_name))))
    if operands:
        fields.append((36, b"".join(_varint(c) for c in operands)))
    if called:
        fields.append((38, b"".join(_varint(c) for c in called)))
    return _msg(*fields)


def _computation(cid, name, instrs, root):
    return _msg((1, name), *[(2, i) for i in instrs], (5, cid), (6, root))


P = "jit(superstep)/while/body/closed_call/"
SUPERSTEP_HLO = _msg((1, _msg((1, "jit_superstep"), *[(3, c) for c in [
    # a dot fused into an add of another scope: the dot decides
    _computation(4, "fused_computation", [
        _instr(40, "param_0", "parameter"),
        _instr(41, "dot.1", "dot", P + "local_update/loss/jvp()/dot_general"),
        _instr(42, "add.1", "add", P + "local_update/optimizer/add",
               operands=(41, 40)),
    ], 42),
    # a multi-output fusion: its root, a tuple, has no op_name
    _computation(5, "fused_computation.1", [
        _instr(51, "subtract.1", "subtract",
               P + "local_update/optimizer/sub"),
        _instr(52, "tuple.1", "tuple", operands=(51, 51))], 52),
    _computation(6, "fused_computation.2", [
        _instr(61, "dot.2", "dot", "jit(superstep)/while/body/aggregate/"
               "dot_general")], 61),
    _computation(7, "fused_computation.3", [
        _instr(71, "reduce.1", "reduce", "jit(superstep)/eval/reduce_sum"),
    ], 71),
    _computation(1, "main", [
        _instr(10, "while.3", "while", "jit(superstep)/while", (2, 3)),
        # the fusion's own op_name says eval, its root optimizer; its dot
        # says forward
        _instr(11, "fusion.1", "fusion", "jit(superstep)/eval/convert", (4,)),
        _instr(12, "convolution.2", "convolution",
               P + "local_update/loss/transpose(jvp())/conv_general_dilated"),
        _instr(13, "fusion.3", "fusion", "", (5,)),
        # a layout copy XLA added has no op_name: its user's decides
        _instr(16, "copy.9", "copy"),
        _instr(14, "fusion.4", "fusion", "", (6,), operands=(16,)),
        _instr(15, "fusion.5", "fusion", "", (7,)),
    ], 10),
]])))


def _hand_xspace() -> bytes:
    """A window of 10 us; times in ns.

    host: bench.window [0, 10000), fl.superstep [1000, 2000),
      fl.readback [6000, 7500), fl.replay [7500, 8000)
    device modules: jit_superstep(7) [2000, 6000), jit__mean(9) [7000,
      7200) (no HLO stored for it)
    device ops: while.3 [2000, 6000) holds fusion.1 [2000, 3000) forward
      (by its dot), convolution.2 [3000, 4000) backward, fusion.3 [4000,
      4500) optimizer (by its tuple root's operand), fusion.4 [4500,
      5000) aggregate, copy.9 [5000, 5100) aggregate (by its user),
      fusion.5 [5200, 5800) eval, and 300 ns of its own; reduce.6 [7000,
      7200) in the
      module without HLO; copy.7 [9800, 10300) in no module, 200 ns of
      it in the window."""
    host = _plane("/host:CPU", lines=[("python3", [
        (1, 0, 10000), (2, 1000, 1000), (3, 6000, 1500), (4, 7500, 500)])],
        metadata=[(1, "bench.window", ()), (2, "fl.superstep", ()),
                  (3, "fl.readback", ()), (4, "fl.replay", ())])
    ops = ["%while.3 = (f32[4]) while(...)", "%fusion.1 = f32[4] fusion(...)",
           "%convolution.2 = f32[4] convolution(...)", "%fusion.3 = f32[4]",
           "%fusion.4 = f32[4]", "%fusion.5 = f32[4]", "%reduce.6 = f32[]",
           "%copy.7 = f32[4] copy(...)", "%copy.9 = f32[4] copy(...)"]
    device = _plane("/device:TPU:0", lines=[
        ("XLA Modules", [(101, 2000, 4000), (102, 7000, 200)]),
        ("XLA Ops", [(1, 2000, 4000), (2, 2000, 1000), (3, 3000, 1000),
                     (4, 4000, 500), (5, 4500, 500), (6, 5200, 600),
                     (7, 7000, 200), (8, 9800, 500), (9, 5000, 100)])],
        metadata=[(101, "jit_superstep(7)", ()), (102, "jit__mean(9)", ())]
        + [(i + 1, n, ()) for i, n in enumerate(ops)])
    hlo = _msg((1, 1), (6, SUPERSTEP_HLO))
    meta = _plane("/host:metadata", metadata=[(7, "jit_superstep(7)",
                                                (hlo,))],
                  stat_metadata=[(1, "Hlo Proto")])
    return _msg(*[(1, p) for p in (meta, host, device)])


def test_join_on_hand_made_trace(tmp_path):
    d = tmp_path / "plugins" / "profile" / "0"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(_hand_xspace())
    tr = scopes.load(str(tmp_path))
    hlo = tr["hlo"]["jit_superstep(7)"]
    assert hlo["fusion.1"] == P + "local_update/loss/jvp()/dot_general"
    assert hlo["fusion.3"] == P + "local_update/optimizer/sub"
    assert hlo["copy.9"] == "jit(superstep)/while/body/aggregate/dot_general"
    assert tr["modules"]["/device:TPU:0"] == [
        ["jit_superstep(7)", 2000.0, 4000.0], ["jit__mean(9)", 7000.0, 200.0]]
    got = scopes.split(tr, rounds=2)
    ns = {k: round(v * 2e6, 6) for k, v in got.items()}
    assert ns == {"batch_ms": 0, "forward_ms": 1000, "backward_ms": 1000,
                  "optimizer_ms": 500, "aggregate_ms": 600, "eval_ms": 600,
                  "unscoped_ms": 700, "busy_ms": 4400, "host_idle_ms": 2800}
    # the existing reduction reads the same structure, and its busy time
    # is the layers' sum; a gap is named by the engine's span over its
    # middle, where there is one
    s = trace.summarize(tr)
    assert s["busy_s"] * 1e9 == pytest.approx(ns["busy_ms"])
    assert [(n, round(v * 1e9)) for n, v in s["idle_gaps"]] == [
        ("bench.window", 2600), ("fl.superstep", 2000),
        ("fl.readback", 1000)]


def test_hlo_of_a_cpu_trace(tmp_path):
    """The HLO the profiler stores for a jitted function holds the scopes
    of its ops, fusions included."""
    import jax
    import jax.numpy as jnp

    def f(x):
        with jax.named_scope("aggregate"):
            y = jnp.sin(x) * 2 + 1
        with jax.named_scope("local_update/loss"):
            return jax.grad(lambda a: jnp.sum(jnp.tanh(a @ a)))(y)

    jf = jax.jit(f)
    x = jnp.ones((8, 8))
    jf(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("fl.superstep"):
            jf(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = scopes.load(str(tmp_path))
    (table,) = [t for m, t in tr["hlo"].items() if m.startswith("jit_f(")]
    layers = {scopes.layer(v) for v in table.values()}
    assert {"aggregate", "forward", "backward"} <= layers
    fusions = [k for k in table if "fusion" in k]
    assert fusions and all(table[k] for k in fusions)
    spans = {n for p in tr["planes"] for l in p["lines"]
             for n, _, _ in l["events"]}
    assert {"bench.window", "fl.superstep"} <= spans


# ---------------------------------------------------------------------------
# the existing reduction is untouched


@pytest.mark.parametrize("name,busy_s,window_s,idle,ops,gap", [
    ("hand_trace.json", 5.600000000000001e-06, 1e-05, 43.99999999999999, 6,
     ["job.run_federated", 1.5e-06]),
    ("recorded_trace.json", 0.06707064700000001, 0.12000000000000001,
     44.10779416666666, 929, ["job.run_federated", 0.008666517]),
])
def test_existing_reduction_unchanged(name, busy_s, window_s, idle, ops,
                                      gap):
    t = json.loads((TESTS / name).read_text())
    s = trace.summarize(t)
    assert (s["busy_s"], s["window_s"], trace.idle_pct(s)) == \
        (busy_s, window_s, idle)
    assert len(s["op_s"]) == ops and s["idle_gaps"][0] == gap
    # a trace with no module events or HLO: every op is unscoped, and the
    # split's layers still add up to the busy time
    got = scopes.split(t, rounds=1)
    assert got["unscoped_ms"] == pytest.approx(busy_s * 1e3, rel=1e-12)
    assert got["busy_ms"] == pytest.approx(busy_s * 1e3, rel=1e-12)
    assert all(got[f"{k}_ms"] == 0 for k in scopes.LAYERS)
