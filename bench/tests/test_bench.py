"""The benchmark's CPU rehearsal: names resolve, the harness refuses to
measure without a TPU, the trace reduction and the FLOP and byte counts
agree with hand counts, the reference agrees with the program, and the
`correct` comparison passes a sound run and fails its control and every
fault planted under the timed path.

Everything here runs on the CPU at small sizes; nothing measures time.
"""
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
FEDAVG = "lenet-paper.fedavg.plain"


def _harness():
    from bench import harness
    return harness


def small_cell(name=FEDAVG, n=2000, m=8):
    """The cell at a size a test run holds: the image protocol on n
    samples over m clients, jobs of 1 + eval_every rounds."""
    h = _harness()
    cell = h.load_cell(name)
    data = dict(cell.mix["data"], n=n, m=m)
    cell.mix = dict(cell.mix, data=data, rounds=1 + cell.eval_every)
    return cell


# ---------------------------------------------------------------------------
# names


@pytest.mark.parametrize("workload", [w["name"] for w in SPEC["workloads"]])
def test_workload_resolves(workload):
    h = _harness()
    cell = h.load_cell(workload)
    assert cell.end_to_end and cell.per_layer
    assert "setup_s" in [m["name"] for m in cell.end_to_end]
    for m in cell.per_layer:
        assert m["moves"] in [e["name"] for e in cell.end_to_end]
        reader = h.load_module(BENCH / "metrics" / f"{m['name']}.py")
        assert callable(reader.read)
    for fn in ("program", "train_flops_per_round"):
        assert callable(getattr(cell.model, fn))
    for fn in ("init", "loss", "score"):
        assert callable(getattr(cell.ref, fn))
    assert cell.ref.CONTROL_DTYPE


def test_spec_names_and_files():
    names = [c["name"] for c in SPEC["configs"]]
    for c in SPEC["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert c["file"].startswith("bench/")
    for w in SPEC["workloads"]:
        assert w["config"] in names
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
        assert (BENCH / "limits" / f"{w['name']}.json").is_file()
    for m in SPEC["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
    peaks = json.loads((BENCH / "peaks.json").read_text())
    assert peaks["TPU v5 lite"]["bf16_flops_per_s"] == 197e12
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        _harness().peak("no such chip", "bf16_flops_per_s")


# ---------------------------------------------------------------------------
# refusing to measure


def _run(cwd, timeout=240):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", FEDAVG, "--seed", "1",
         "--seconds", "1", "--trace", "0"], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=timeout)


def _printed_result(stdout):
    lines = stdout.strip().splitlines()
    if not lines:
        return False
    try:
        return "correct" in json.loads(lines[-1])
    except ValueError:
        return False


def test_run_refuses_without_tpu():
    out = _run(ROOT)
    assert out.returncode != 0
    assert not _printed_result(out.stdout)
    assert "needs a TPU" in out.stderr


def test_run_refuses_without_the_program(tmp_path):
    (tmp_path / "BENCHMARK.json").write_text(
        (ROOT / "BENCHMARK.json").read_text())
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".out", "__pycache__"))
    out = _run(tmp_path)
    assert out.returncode != 0
    assert not _printed_result(out.stdout)


# ---------------------------------------------------------------------------
# the trace reduction


def test_trace_reduction_by_hand():
    """hand_trace.json: a window of 10 us; device ops cover 5.6 us of it
    after clipping; three gaps under job.run_federated are 1.5, 1.0 and
    0.6 us, one under job.model_init 0.5 us, one under job.between
    0.3 us; a loop op (while.7) lies over ops it holds."""
    from bench import trace
    t = json.loads((BENCH / "tests" / "hand_trace.json").read_text())
    s = trace.summarize(t)
    assert s["window_s"] == pytest.approx(10e-6)
    assert s["busy_s"] == pytest.approx(5.6e-6)
    assert trace.idle_pct(s) == pytest.approx(44.0)
    assert s["op_s"]["fusion.1"] == pytest.approx(1.5e-6)
    assert sum(v for n, v in s["op_s"].items()
               if n.startswith(("rowwise_absmax", "qsgd_"))) == \
        pytest.approx(3.0e-6)
    # a loop op covers its children: busy, but not time of its own
    assert "while.7" not in s["op_s"]
    got = [(n, round(v * 1e9)) for n, v in s["idle_gaps"]]
    assert got == [("job.run_federated", 1500), ("job.run_federated", 1000),
                   ("job.run_federated", 600), ("job.model_init", 500),
                   ("job.run_federated", 500), ("job.between", 300)]


def _brute_force(t, step_ns):
    """Busy share of the window on a grid of ``step_ns``: an independent
    count of the same union."""
    from bench import trace
    w0, w1 = trace.window(t)
    grid = np.arange(w0, w1, step_ns) + step_ns / 2
    busy = np.zeros_like(grid, bool)
    for p in t["planes"]:
        if p["name"].startswith(trace.DEVICE_PREFIX):
            for l in p["lines"]:
                for _, s, d in l["events"]:
                    busy |= (grid >= s) & (grid < s + d)
    return busy.mean()


def test_trace_reduction_on_recorded_trace():
    from bench import trace
    t = json.loads((BENCH / "tests" / "recorded_trace.json").read_text())
    s = trace.summarize(t)
    assert 0 < s["busy_s"] < s["window_s"]
    assert s["busy_s"] / s["window_s"] == pytest.approx(
        _brute_force(t, 100.0), abs=2e-3)
    ops = sum(v for v in s["op_s"].values())
    assert ops >= 0.9 * s["busy_s"]     # loops count only by their ops
    idle = s["window_s"] - s["busy_s"]
    assert sum(v for _, v in s["idle_gaps"]) <= idle * (1 + 1e-9)
    assert {n for n, _ in s["idle_gaps"]} <= {
        "bench.window", "job.run_federated", "job.between",
        "job.model_init", "job.strategy_setup"}


# ---------------------------------------------------------------------------
# FLOP and byte counts


def test_lenet_flops_by_hand():
    cell = _harness().load_cell(FEDAVG)
    # conv1 24*24*6*25, conv2 8*8*16*150, dense 256*120 + 120*84 + 84*47
    macs = 86_400 + 153_600 + 30_720 + 10_080 + 3_948
    assert cell.model.forward_flops(cell.config) == 2 * macs
    per_round = 3 * 2 * macs * 100 * 5 * 32
    assert cell.model.train_flops_per_round(cell.config, cell.mix) == \
        per_round


def test_mamba2_flops_by_hand():
    h = _harness()
    mod = h.load_module(BENCH / "configs" / "mamba2-780m.py")
    c = {"d_model": 4, "expand": 2, "headdim": 2, "ngroups": 1,
         "d_state": 3, "d_conv": 2, "chunk_size": 5, "vocab_size": 6,
         "pad_vocab_size_multiple": 7, "n_layer": 2}
    # d_inner 8, heads 4, conv_dim 14, d_proj 2*8 + 2*3 + 4 = 26
    macs = 4 * 26 + 14 * 2 + 5 * 3 + 4 * 5 * 2 + 2 * 4 * 2 * 3 + 8 * 4
    assert mod.layer_forward_flops(c) == 2 * macs
    assert mod.head_forward_flops(c) == 2 * 4 * 7
    mix = {"data": {"seq": 10, "m": 3}, "local_steps": 1, "batch_size": 2}
    per_seq = 10 * 2 * 2 * macs + 9 * 2 * 4 * 7
    assert mod.train_flops_per_round(c, mix) == 3 * per_seq * 3 * 2


def test_op_names_from_hlo_text():
    from bench import trace
    assert trace.op_name("%qsgd_quantize.9 = s32[104,49152]{1,0} "
                         "custom-call(f32[104,49152] %pad.37)") == \
        "qsgd_quantize.9"
    assert trace.op_name("fusion.3") == "fusion.3"


# ---------------------------------------------------------------------------
# the data and the reference


def test_traffic_same_shapes_every_seed():
    from bench.traffic import generate
    data = dict(small_cell().mix["data"])
    a, b = generate.make(data, {}, 1), generate.make(data, {}, 2 ** 33 + 1)
    for x, y in zip(a, b):
        assert x.shape == y.shape and x.dtype == y.dtype
    assert np.array_equal(np.sort(np.asarray(a.n)), np.sort(np.asarray(b.n)))
    assert not np.array_equal(np.asarray(a.x), np.asarray(b.x))
    again = generate.make(data, {}, 1)
    assert np.array_equal(np.asarray(a.x), np.asarray(again.x))
    assert np.array_equal(np.asarray(a.group), np.arange(8) % 4)


def test_lm_traffic_one_rule_per_group():
    from bench.traffic import generate
    data = {"kind": "markov_lm", "m": 4, "pool": 3, "n_val": 2, "seq": 16,
            "concept_groups": 2, "markov_order": 2}
    fed = generate.make(data, {"vocab_size": 97}, 5)
    assert fed.x.shape == (4, 3, 16) and fed.x_val.shape == (4, 2, 16)
    assert np.array_equal(np.asarray(fed.group), [0, 1, 0, 1])
    assert int(fed.x.max()) < 97


def test_mamba2_reference_matches_program_at_small_widths():
    """The plain quadratic-form reference against the program's chunked
    scan (3 chunks), both in float32: loss and every gradient leaf."""
    import dataclasses
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.configs.base import SSMConfig
    from repro.models import scan
    h = _harness()
    ref = h.load_module(BENCH / "configs" / "mamba2-780m_ref.py")
    c = dict(h.load_json(BENCH / "configs" / "mamba2-780m.json"),
             d_model=64, d_state=16, headdim=16, chunk_size=32,
             vocab_size=100, pad_vocab_size_multiple=16, n_layer=2,
             param_dtype="float32",
             compute_dtype="float32")
    cfg = dataclasses.replace(
        get_config("mamba2-780m"), n_layers=2, d_model=64, vocab_size=112,
        ssm=SSMConfig(d_state=16, d_conv=4, expand=2, head_dim=16,
                      n_groups=1, chunk_size=32),
        param_dtype="float32", compute_dtype="float32")
    p = ref.init(c, jax.random.PRNGKey(0))
    p = jax.tree_util.tree_map(
        lambda a: a + 0.01 * jax.random.normal(jax.random.PRNGKey(3),
                                               a.shape), p)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 96), 0, 100)
    with jax.default_matmul_precision("highest"):
        lp, gp = jax.value_and_grad(lambda q: scan.loss_fn(
            q, cfg, {"tokens": toks}, remat=True)[0])(p)
        lr, gr = jax.value_and_grad(lambda q: ref.loss(
            c, q, toks, None, lambda a: a))(p)
    assert float(lp) == pytest.approx(float(lr), rel=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(gp),
                    jax.tree_util.tree_leaves(gr)):
        assert float(jnp.linalg.norm(a - b)) <= 1e-4 * float(
            jnp.linalg.norm(b))


# ---------------------------------------------------------------------------
# the comparison: sound, control, faults


@pytest.fixture(scope="module")
def sound_run():
    """One run of the small fedavg cell through the harness, the chip look
    skipped; the window's length is a token second."""
    h = _harness()
    cell = small_cell()
    return cell, h.run_cell(cell, 2 ** 33 + 17, 0.5, False,
                            time.perf_counter(), lambda s: None)


def test_sound_run_is_correct(sound_run):
    cell, res = sound_run
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"round_s", "setup_s"}
    assert res["checks"]["window_compiles"]["value"] == 0
    assert list(res)[-1] == "checks"
    limits = cell.limits["limits"]
    assert limits and set(limits) <= set(res["checks"])


def test_control_fails_the_limits():
    """The reference computed one precision below the configuration's
    (bfloat16 for LeNet's float32) reads above at least one limit."""
    from bench import calibrate, harness, reference
    cell = small_cell()
    job = harness.Job(cell, 2 ** 33 + 17)
    ref = harness.reference_readings(job)
    ctrl = harness.reference_readings(
        job, cast=reference.rounding(cell.ref.CONTROL_DTYPE))
    checks = harness.judge(harness.compare(calibrate.as_program(ctrl), ref),
                           cell.limits)
    assert not harness.is_correct(checks), checks


def _broken_update(kind):
    """`HostVmap.build_update` with the timed path broken underneath."""
    from repro.fl.placement import host

    def build_update(self, loss_fn, fl, *, donate=False):
        bs = fl.batch_size // 2 if kind == "half_batch" else fl.batch_size
        opt, step = host.cached_update(loss_fn, fl.local_steps, bs, fl.lr,
                                       fl.momentum, fl.opt_state_dtype,
                                       donate)
        if kind == "unchanged":
            return opt, lambda s, o, *a: (s, o)
        return opt, step
    return build_update


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "no_mix"])
def test_fault_under_the_timed_path_is_not_correct(fault, monkeypatch):
    from repro.fl import simulator
    from repro.fl.placement.host import HostVmap
    h = _harness()
    # compiled supersteps are cached by what they close over; a patched
    # method is not part of that key
    monkeypatch.setattr(simulator, "_SUPERSTEP_FNS", {})
    if fault == "no_mix":
        monkeypatch.setattr(HostVmap, "mix_traced", lambda self, s, w: s)
    else:
        monkeypatch.setattr(HostVmap, "build_update", _broken_update(fault))
    cell = small_cell()
    res = h.run_cell(cell, 2 ** 33 + 17, 0.2, False, time.perf_counter(),
                     lambda s: None)
    assert not res["correct"], res["checks"]


def test_reference_plan_matches_the_program_on_a_clear_split():
    """The reference's Eq. 6 and k-means, for the UCFL cells that section
    7 of PERF.md brings back, against the program's on gradients of two
    well-separated groups."""
    import jax
    import jax.numpy as jnp
    from bench import reference
    from repro.core import kmeans, mixing_matrix
    from repro.core.similarity import delta_matrix
    rng = np.random.default_rng(0)
    centers = rng.normal(size=(2, 50)) * 3.0
    g = centers[np.arange(8) % 2] + 0.3 * rng.normal(size=(8, 50))
    n = np.arange(8, dtype=np.float64) + 20.0
    sig2 = rng.uniform(1.0, 2.0, size=8)
    delta = ((g[:, None] - g[None]) ** 2).sum(-1)
    w_ref = reference.mixing_matrix(delta, sig2, n)
    g32 = jnp.asarray(g, jnp.float32)
    w_prog = np.asarray(mixing_matrix(delta_matrix(g32),
                                      jnp.asarray(sig2, jnp.float32),
                                      jnp.asarray(n, jnp.float32)))
    assert np.allclose(w_ref, w_prog, atol=1e-4)
    first = int(jax.random.randint(jax.random.PRNGKey(8), (), 0, 8))
    _, a_ref = reference.kmeans(w_ref, 2, first)
    plan = kmeans(jnp.asarray(w_prog), 2, key=jax.random.PRNGKey(8))
    from bench.harness import plan_pairs
    assert plan_pairs(list(a_ref), list(np.asarray(plan.assignment))) == 0
    assert plan_pairs(list(a_ref), list(np.arange(8) % 2)) == 0
