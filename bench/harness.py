"""The benchmark harness: one cell, one run, one result line.

Everything a cell needs is found by name from ``BENCHMARK.json``:

  bench/configs/<config>.json      the configuration as it is run
  bench/configs/<config>.py        how the program runs it (model_init,
                                   loss and eval functions, FLOP counts)
  bench/configs/<config>_ref.py    its plain reference model
  bench/traffic/<traffic>.json     the federated job and its data
  bench/limits/<workload>.json     the limits of the `correct` comparison
  bench/metrics/<metric>.py        one reader per per-layer metric

A run builds the job from the seed, drives two short jobs through
`repro.fl.run_federated` (the check: one round, then 1 + eval_every
rounds; they compile every program the window uses), then repeats whole
jobs of ``rounds`` rounds until ``--seconds`` have passed, ending at a job
boundary.  After the window it follows the check jobs with the plain
reference and compares.  ``--trace 1`` profiles a window of at most
`TRACE_SECONDS` and reports the per-layer metrics instead of the
end-to-end ones.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import shutil
import time
from pathlib import Path
from typing import Any, Callable, Dict

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"
OUT_DIR = BENCH / ".out"
TRACE_SECONDS = 5.0
# leaves whose reference gradient is under this share of the median
# leaf's move by rounding alone and are left out of the change numbers
DEAD_LEAF = 1e-3


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


# ---------------------------------------------------------------------------
# finding things by name


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    if not path.is_file():
        raise FileNotFoundError(path)
    name = "bench_" + path.stem.replace("-", "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with everything it names, loaded."""
    name: str
    chips: int
    config: dict            # bench/configs/<config>.json
    mix: dict               # bench/traffic/<traffic>.json
    model: Any              # bench/configs/<config>.py
    ref: Any                # bench/configs/<config>_ref.py
    limits: dict            # bench/limits/<workload>.json
    end_to_end: list        # the spec's metrics this cell reports
    per_layer: list

    @property
    def rounds(self) -> int:
        return int(self.mix["rounds"])

    @property
    def eval_every(self) -> int:
        return int(self.mix["eval_every"])


def reports(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    spec = load_json(root / "BENCHMARK.json")
    found = [w for w in spec["workloads"] if w["name"] == name]
    if not found:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"have {[w['name'] for w in spec['workloads']]}")
    w = found[0]
    entry = [c for c in spec["configs"] if c["name"] == w["config"]][0]
    bench = root / "bench"
    cell = Cell(
        name=name, chips=int(w["chips"]),
        config=load_json(root / entry["file"]),
        mix=load_json(bench / "traffic" / f"{w['traffic']}.json"),
        model=load_module(bench / "configs" / f"{w['config']}.py"),
        ref=load_module(bench / "configs" / f"{w['config']}_ref.py"),
        limits=load_json(bench / "limits" / f"{name}.json"),
        end_to_end=[m for m in spec["end_to_end"] if reports(m, name)],
        per_layer=[m for m in spec["per_layer"] if reports(m, name)])
    r, e = cell.rounds, cell.eval_every
    if r < 1 + e or (r - 1) % e:
        # only two superstep lengths, 1 and eval_every, may exist
        raise ValueError(f"{name}: rounds {r} is not 1 + j x eval_every {e}")
    return cell


# ---------------------------------------------------------------------------
# the device, the cache, compilations


def enable_cache() -> str:
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return str(CACHE_DIR)


def require_chips(chips: int) -> None:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found platform {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"needs {chips} chips; JAX found {len(devs)}")


class CompileCounter:
    """Counts compilations (and loads from the persistent cache) while
    ``active``.  Eager ops re-trace on every call and are not counted."""
    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        from jax import monitoring
        self.active = False
        self.count = 0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_: Any) -> None:
        if self.active and event in self.EVENTS:
            self.count += 1


def peak(kind: str, what: str) -> float:
    """A published peak of the device kind from ``bench/peaks.json``; a
    kind that is not in the table is an error, never a default."""
    table = load_json(BENCH / "peaks.json")
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return float(table[kind][what])


def device_info(chips: int) -> dict:
    import jax
    devs = jax.devices()
    peak = 0
    for d in devs[:max(chips, 1)]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


# ---------------------------------------------------------------------------
# the job


def run_seed(seed: int) -> int:
    """The 31-bit seed the engine's PRNGKey takes, from any seed."""
    return int(np.random.SeedSequence([int(seed), 2]).generate_state(1)[0]
               >> 1)


def setup_once(strategy, timings: dict):
    """The strategy as a subclass whose one-time `setup` (UCFL's
    similarity round, Eq. 6 and k-means plan) runs once per process: every
    job of a run has the same data and seed, so its result is the same."""
    import jax
    base = type(strategy)

    def setup(self, ctx):
        if self._bench_state is None:
            with jax.profiler.TraceAnnotation("job.strategy_setup"):
                t0 = time.perf_counter()
                state = base.setup(self, ctx)
                jax.block_until_ready(state)
                timings["strategy_setup_s"] = time.perf_counter() - t0
            self._bench_state = state
        return self._bench_state

    cls = type(f"Once{base.__name__}", (base,), {"setup": setup})
    obj = cls.__new__(cls)
    obj.__dict__.update(strategy.__dict__)
    obj._bench_state = None
    return obj


class Job:
    """The cell's federated job, built once per run from the seed."""

    def __init__(self, cell: Cell, seed: int):
        import jax
        from repro.fl import (Channel, FLConfig, HostVmap, MeshShardMap,
                              get_strategy)
        from bench.traffic import generate
        self.cell, self.seed = cell, seed
        self.run_seed = run_seed(seed)
        self.timings: Dict[str, float] = {}
        t0 = time.perf_counter()
        self.fed = generate.make(cell.mix["data"], cell.config, seed)
        jax.block_until_ready(self.fed)
        self.timings["data_s"] = time.perf_counter() - t0
        fns = cell.model.program(cell.config, cell.ref)
        init = fns["model_init"]

        def model_init(key):
            with jax.profiler.TraceAnnotation("job.model_init"):
                return init(key)

        self.model_init = model_init
        self.loss_fn, self.acc_fn = fns["loss_fn"], fns["acc_fn"]
        c, mix = cell.config, cell.mix
        self.fl = FLConfig(rounds=cell.rounds,
                           local_steps=int(mix["local_steps"]),
                           batch_size=int(mix["batch_size"]),
                           lr=float(c["lr"]), momentum=float(c["momentum"]),
                           opt_state_dtype=c.get("opt_state_dtype"),
                           eval_every=cell.eval_every,
                           sigma_batches=int(mix.get("sigma_batches", 5)))
        self.strategy = setup_once(get_strategy(mix["strategy"]),
                                   self.timings)
        self.placement = (HostVmap() if c["placement"] == "host_vmap" else
                          MeshShardMap(schedule=c["schedule"]))
        self.channel = (Channel(codec=mix["codec"],
                                error_feedback=bool(mix["error_feedback"]))
                        if mix.get("codec") else None)

    def params0(self):
        """The common initialisation every job starts from (the engine's
        key derivation: ``split(PRNGKey(seed))[1]``)."""
        import jax
        return self.model_init(jax.random.split(
            jax.random.PRNGKey(self.run_seed))[1])

    def run(self, rounds: int):
        import jax
        from repro.fl import run_federated
        with jax.profiler.TraceAnnotation("job.run_federated"):
            hist = run_federated(
                strategy=self.strategy, fed=self.fed,
                fl=dataclasses.replace(self.fl, rounds=rounds),
                model_init=self.model_init, loss_fn=self.loss_fn,
                acc_fn=self.acc_fn, placement=self.placement,
                channel=self.channel, keep_state=True, superstep=True,
                seed=self.run_seed)
            jax.block_until_ready(hist.final_params)
        return hist


# ---------------------------------------------------------------------------
# the check: readings of the program, the reference, and their comparison


def leaf_norms(stack, base=None) -> np.ndarray:
    """(clients, leaves) norms of each client's leaves, minus ``base``."""
    import jax
    import jax.numpy as jnp
    cols = []
    base_leaves = (None if base is None else jax.tree_util.tree_leaves(base))
    for i, l in enumerate(jax.tree_util.tree_leaves(stack)):
        d = l.astype(jnp.float32)
        if base_leaves is not None:
            d = d - base_leaves[i].astype(jnp.float32)[None]
        cols.append(jnp.sqrt(jnp.sum(jnp.square(d.reshape(d.shape[0], -1)),
                                     axis=1)))
    return np.asarray(jnp.stack(cols, axis=1), np.float64)


def elementwise(cell: Cell) -> bool:
    """Whether the cell's limits compare the first round's change element
    by element (``change_diff``), which needs both changes kept."""
    return "change_diff" in cell.limits.get("limits", {})


def program_readings(job: Job) -> dict:
    """Drive the check jobs through the window's own call and read them:
    one round (its optimizer state and change), then 1 + eval_every
    rounds (its change), with the fused evals of both."""
    first = job.run(1)
    p0 = job.params0()      # made after the job, so that set-up has room
    out = {"mom_norms": leaf_norms(first.final_opt_state["mu"]),
           "change_norms": leaf_norms(first.final_params, p0),
           "evals": [(r, a, w) for r, a, w in zip(first.rounds,
                                                  first.mean_acc,
                                                  first.worst_acc)]}
    if elementwise(job.cell):
        from bench.reference import leaf_changes
        out["change"] = leaf_changes(first.final_params, p0)
    extras = first.extras
    if extras is not None and getattr(extras, "assignment", None) is not None:
        out["plan_assignment"] = np.asarray(extras.assignment).tolist()
    del first
    longer = job.run(1 + job.cell.eval_every)
    out["change_norms_last"] = leaf_norms(longer.final_params, p0)
    out["evals"] += [(r, a, w) for r, a, w in zip(longer.rounds,
                                                  longer.mean_acc,
                                                  longer.worst_acc)]
    return out


def _gap(prog: np.ndarray, ref: np.ndarray, keep: np.ndarray) -> float:
    """Worst (client, leaf) gap between two norms, over the reference's
    norm of that leaf or of the client's median leaf, the larger."""
    med = np.median(ref, axis=1, keepdims=True)
    rel = np.abs(prog - ref) / np.maximum(np.maximum(ref, med), 1e-30)
    return float(np.max(np.where(keep, rel, 0.0)))


def _median_gap(prog: np.ndarray, ref: np.ndarray,
                keep: np.ndarray) -> float:
    """Worst client's gap between the median leaf norms of the two."""
    a = np.array([np.median(p[k]) for p, k in zip(prog, keep)])
    b = np.array([np.median(r[k]) for r, k in zip(ref, keep)])
    return float(np.max(np.abs(a - b) / np.maximum(b, 1e-30)))


def _diff(prog: list, ref: list) -> float:
    """Worst client's |prog - ref| / |ref| over its whole change, compared
    element by element: unbiased rounding of the stored values, which a
    gap of norms sees only in second order, shows here in first."""
    a = np.concatenate(prog, axis=1).astype(np.float64)
    b = np.concatenate(ref, axis=1).astype(np.float64)
    return float(np.max(np.linalg.norm(a - b, axis=1)
                        / np.maximum(np.linalg.norm(b, axis=1), 1e-30)))


def plan_pairs(a: list, b: list) -> int:
    """Client pairs that one plan puts in one stream and the other not."""
    a, b = np.asarray(a), np.asarray(b)
    same = lambda x: x[:, None] == x[None, :]
    return int(np.sum(np.triu(same(a) != same(b), 1)))


def compare(prog: dict, ref: dict) -> Dict[str, float]:
    """The numbers compared, by name."""
    mom = ref["mom_norms"]
    keep = mom >= DEAD_LEAF * np.median(mom, axis=1, keepdims=True)
    eval_gap = 0.0
    for rnd, mean, worst in prog["evals"]:
        s = ref["scores"][rnd]
        scale = abs(float(np.mean(s)))
        eval_gap = max(eval_gap, abs(mean - float(np.mean(s))) / scale,
                       abs(worst - float(np.min(s))) / scale)
    out = {"eval_gap": eval_gap,
           "grad_gap": _gap(prog["mom_norms"], mom, keep),
           "change_gap": _gap(prog["change_norms"], ref["change_norms"],
                              keep),
           "change_gap_last": _gap(prog["change_norms_last"],
                                   ref["change_norms_last"], keep),
           "change_median_gap": _median_gap(prog["change_norms"],
                                            ref["change_norms"], keep),
           "change_median_gap_last": _median_gap(
               prog["change_norms_last"], ref["change_norms_last"], keep)}
    if "change" in prog and "change" in ref:
        out["change_diff"] = _diff(prog["change"], ref["change"])
    if "plan_assignment" in prog:
        out["plan_pairs"] = float(plan_pairs(prog["plan_assignment"],
                                             ref["plan_assignment"]))
    return out


def reference_readings(job: Job, cast=None, fault=None) -> dict:
    from bench import reference
    cell = job.cell
    fl = {"local_steps": job.fl.local_steps, "batch_size": job.fl.batch_size,
          "lr": job.fl.lr, "momentum": job.fl.momentum}
    import jax
    with jax.default_matmul_precision("highest"):
        return reference.run(cell.ref, cell.config, fl, cell.mix, job.fed,
                             job.run_seed, cast=cast or reference.identity,
                             fault=fault, changes=elementwise(cell))


def judge(numbers: Dict[str, float], limits: dict) -> Dict[str, dict]:
    """Each number beside its limit; a number with no limit is reported
    and not judged (it is named in PERF.md with its readings)."""
    lim = limits.get("limits", {})
    return {k: {"value": v, "limit": lim.get(k)} for k, v in numbers.items()}


def is_correct(checks: Dict[str, dict]) -> bool:
    return all(math.isfinite(c["value"]) and
               (c["limit"] is None or c["value"] <= c["limit"])
               for c in checks.values())


# ---------------------------------------------------------------------------
# one run


def window(job: Job, seconds: float, counter: CompileCounter) -> dict:
    """Whole jobs until ``seconds`` have passed; ends at a job boundary."""
    import jax
    rounds = job.cell.rounds
    jobs = nonfinite = 0
    counter.active = True
    with jax.profiler.TraceAnnotation("bench.window"):
        t0 = time.perf_counter()
        while True:
            hist = None
            hist = job.run(rounds)
            jobs += 1
            if not all(math.isfinite(a) for a in hist.mean_acc
                       + hist.worst_acc):
                nonfinite += 1
            with jax.profiler.TraceAnnotation("job.between"):
                if time.perf_counter() - t0 >= seconds:
                    break
        window_s = time.perf_counter() - t0
    counter.active = False
    del hist
    return {"window_s": window_s, "jobs": jobs, "rounds": jobs * rounds,
            "failed_rounds": nonfinite * rounds,
            "window_compiles": counter.count}


def end_to_end(cell: Cell, win: dict, setup_s: float) -> Dict[str, float]:
    data = cell.mix["data"]
    per_round = (data["m"] * int(cell.mix["local_steps"])
                 * int(cell.mix["batch_size"]) * data.get("seq", 1))
    values = {"setup_s": setup_s,
              "round_s": win["window_s"] / win["rounds"],
              "client_tokens_per_s": per_round * win["rounds"]
              / win["window_s"]}
    return {m["name"]: values[m["name"]] for m in cell.end_to_end}


def per_layer(cell: Cell, ctx: dict) -> Dict[str, float]:
    out = {}
    for m in cell.per_layer:
        reader = load_module(BENCH / "metrics" / f"{m['name']}.py")
        v = reader.read(ctx)
        if v is not None:
            out[m["name"]] = float(v)
    return out


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, log: Callable[[str], None]) -> dict:
    """Set up, measure, check.  Returns the result object."""
    import jax
    counter = CompileCounter()
    job = Job(cell, seed)
    prog = program_readings(job)
    setup_s = time.perf_counter() - t_start
    log(f"setup {setup_s:.3f} s: data {job.timings['data_s']:.3f} s, "
        f"strategy set-up {job.timings.get('strategy_setup_s', 0):.3f} s")
    trace_dir = OUT_DIR / "trace" / cell.name
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    win = window(job, min(seconds, TRACE_SECONDS) if trace else seconds,
                 counter)
    summary = None
    if trace:
        jax.profiler.stop_trace()
        from bench import trace as trace_mod
        summary = trace_mod.summarize(trace_mod.load(str(trace_dir)),
                                      chips=cell.chips)
        shutil.rmtree(trace_dir, ignore_errors=True)
    device = device_info(cell.chips)
    log(f"window {win['window_s']:.3f} s, {win['jobs']} jobs, "
        f"{win['rounds']} rounds, {win['window_compiles']} compiles; "
        f"peak {device['memory_peak_bytes']} bytes")
    ctx = {"cell": cell, "trace": summary, "device": device,
           "timings": dict(job.timings), "setup_s": setup_s, **win}
    # the reference runs once the window and its last job are over
    ref = reference_readings(job)
    checks = judge(compare(prog, ref), cell.limits)
    checks["window_compiles"] = {"value": float(win["window_compiles"]),
                                 "limit": 0.0}
    metrics = (per_layer(cell, ctx) if trace
               else end_to_end(cell, win, setup_s))
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    result = {"correct": is_correct(checks), "attempted": win["rounds"],
              "failed": win["failed_rounds"],
              "metrics": {k: {"value": v, "unit": units[k]}
                          for k, v in metrics.items()},
              "device": device}
    if summary is not None:
        result["device"]["busy_s"] = summary["busy_s"]
        result["device"]["window_s"] = summary["window_s"]
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    result["checks"] = checks
    return result
