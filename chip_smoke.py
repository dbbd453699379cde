#!/usr/bin/env python3
"""Run the system's main path once on a TPU and check what comes out.

    python chip_smoke.py             # phases A-D, one chip, one process
    python chip_smoke.py --chips 4   # only the mesh round, on four chips

Phases on one chip:

  A  the paper's round at paper scale: `run_federated` with ucfl_k4 and a
     qsgd:4 uplink (the fused superstep, with the Pallas QSGD kernels
     compiled into it), then fedavg with no channel, on the paper's
     covariate-shift scenario (n = 100,000 samples, m = 100 clients,
     LeNet at its own width);
  B  the codec kernels and the aggregation against their references, at
     phase A's shapes;
  C  the serving plane: a qsgd:4 `DeltaStore` built from phase A, eight
     users through `ServeEngine`, and its parity anchor;
  D  mamba2-780m at published widths, one client, through
     `repro.launch.train.main`.

With ``--chips 4`` the script runs phase A's ucfl_k4 round with the
identity codec on `MeshShardMap` over four chips, once per mixing
schedule, and compares each with the same round on `HostVmap` on one chip.

Every earlier line is a report; the last line of standard output is one
JSON object naming the device.  A phase that fails raises, so the script
exits non-zero before that line.  Without a TPU it exits non-zero before
any phase.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from dataclasses import dataclass
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import stream_aggregate  # noqa: E402
from repro.core.distributed import MIX_SCHEDULES  # noqa: E402
from repro.core.streams import StreamPlan  # noqa: E402
from repro.data.federated import scenario_covariate_shift  # noqa: E402
from repro.fl import (Channel, DeltaStore, FLConfig, HostVmap,  # noqa: E402
                      MeshShardMap, ServeEngine, check_parity, get_codec,
                      run_federated)
from repro.fl.channel import (stacked_ravel, uplink_roundtrip,  # noqa: E402
                              zeros_like_stack)
from repro.kernels import ops, ref  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import lenet  # noqa: E402

# phase A's federated configuration (paper §IV-A.2 protocol, 6 rounds)
FL_A = FLConfig(rounds=6, local_steps=5, batch_size=32, eval_every=3)

# --chips 4: mesh vs HostVmap.  The two placements run the same round in
# different programs (25 clients per chip against 100 on one), so XLA may
# tile the local update's convolutions and reduce the mix in another
# order; over 6 rounds x 5 SGD steps such float32 reassociations grow but
# stay orders of magnitude below the change the rounds themselves make.
# Accuracies: the repo's own host-vs-mesh bound
# (tests/test_placement.py::test_mesh_matches_host).  Params: relative
# L2 distance of the final client stacks.
MESH_ACC_ATOL = 2e-2
MESH_PARAM_RTOL = 1e-2


@dataclass(frozen=True)
class Sizes:
    """Phase sizes; the defaults are the ones the chip runs."""
    n: int = 100_000            # samples (paper §IV-A.2)
    m: int = 100                # clients
    lm_args: tuple = ("--arch", "mamba2-780m", "--preset", "full",
                      "--algorithm", "fedavg", "--clients", "1",
                      "--batch", "1", "--seq", "256", "--steps", "2",
                      "--local-steps", "1", "--eval-every", "1")


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def require_tpu(chips: int) -> None:
    devs = jax.devices()
    platform = devs[0].platform
    if platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found platform {platform!r}")
    if chips > 1 and len(devs) != chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} devices, JAX "
                 f"found {len(devs)}")
    print(f"device_kind={devs[0].device_kind} count={len(devs)} "
          f"jax={jax.__version__}", flush=True)


def memory() -> dict:
    """Bytes on the first device as its backend reports them."""
    stats = jax.devices()[0].memory_stats()
    return {k: stats[k] for k in ("bytes_in_use", "peak_bytes_in_use")}


def run_phase(name: str, fn, *args):
    t0 = time.perf_counter()
    out = fn(*args)
    print(f"PASS {name} seconds={time.perf_counter() - t0:.3f} "
          f"memory={memory()}", flush=True)
    return out


def check_history(label: str, hist) -> None:
    accs = list(zip(hist.rounds, hist.mean_acc, hist.worst_acc))
    print(f"  {label}: (round, mean, worst) = {accs}", flush=True)
    check(all(math.isfinite(a) for a in hist.mean_acc + hist.worst_acc),
          f"{label}: non-finite evaluation {accs}")
    check(hist.mean_acc[-1] > hist.mean_acc[0],
          f"{label}: last mean accuracy {hist.mean_acc[-1]} is not above "
          f"round {hist.rounds[0]}'s {hist.mean_acc[0]}")


def check_kernels_compiled(hlo: str) -> None:
    check("tpu_custom_call" in hlo,
          "the compiled uplink holds no tpu_custom_call: the QSGD kernels "
          "were not compiled for the chip")


# ---------------------------------------------------------------------------
# one chip


def phase_a(sizes: Sizes):
    fed = scenario_covariate_shift(jax.random.PRNGKey(0), n=sizes.n,
                                   m=sizes.m)
    hist = run_federated("ucfl_k4", fed, fl=FL_A,
                         channel=Channel(codec="qsgd:4"), keep_state=True)
    check_history("ucfl_k4 qsgd:4", hist)
    check_history("fedavg", run_federated("fedavg", fed, fl=FL_A))

    # the uplink as the fused round traces it, compiled on its own
    codec = get_codec("qsgd:4")
    stacked = hist.final_params
    uplink = jax.jit(lambda s, p, e, k: uplink_roundtrip(
        codec, s, p, e, k, None, backend=HostVmap.codec_backend))
    hlo = uplink.lower(stacked, stacked, zeros_like_stack(stacked),
                       jax.random.PRNGKey(2)).compile().as_text()
    check_kernels_compiled(hlo)
    print(f"  uplink program holds {hlo.count('tpu_custom_call')} "
          f"tpu_custom_call sites", flush=True)
    return fed, hist


def phase_b(m: int, d: int) -> None:
    kx, kn, kw = jax.random.split(jax.random.PRNGKey(1), 3)
    x = jax.random.normal(kx, (m, d), jnp.float32)
    noise = jax.random.uniform(kn, (m, d), jnp.float32)

    # QSGD: the kernel and the jnp reference may round a row's scale, or
    # x/scale, one ulp apart (Mosaic and XLA lower the division apart),
    # and that can flip a stochastic-rounding level by one.  So each
    # element agrees within one quantisation step of its row, plus the
    # levels * ulp(step) a one-ulp scale difference adds to a level.
    bits = 4
    levels = 2 ** (bits - 1) - 1
    got = np.asarray(ops.qsgd_roundtrip(x, noise, bits=bits), np.float64)
    want = np.asarray(ref.qsgd_roundtrip_ref(x, noise, bits), np.float64)
    step = np.max(np.abs(np.asarray(x)), axis=1, keepdims=True) / levels
    tol = step + levels * np.spacing(step.astype(np.float32))
    err = np.abs(got - want)
    check(bool(np.all(err <= tol)),
          f"qsgd_roundtrip: max |kernel - ref| / step = "
          f"{float(np.max(err / step))}")
    print(f"  qsgd_roundtrip: exact share {float(np.mean(err == 0))}, "
          f"max |kernel - ref| / step {float(np.max(err / step))}",
          flush=True)

    # top-k threshold: 30 halvings of [0, max|x|] leave lo within
    # max|x| * 2^-30 of the k-th magnitude, but the float32 midpoint of
    # two neighbouring floats is one of them, so the bisection cannot
    # close below one float32 spacing at the k-th magnitude.  It keeps
    # count(|x| >= t) >= k by construction.
    absx = jnp.abs(x)
    amax = np.asarray(jnp.max(absx, axis=1)).astype(np.float64)
    for k in (1, math.ceil(0.01 * d), math.ceil(0.25 * d)):
        t = np.asarray(ops.topk_threshold(absx, k=k))[:, 0]
        kth = np.asarray(ref.topk_threshold_ref(absx, k))[:, 0]
        kept = np.asarray(jnp.sum(absx >= t[:, None], axis=1))
        check(bool(np.all(kept >= k)),
              f"topk_threshold k={k}: a row keeps {int(kept.min())} < k")
        gap = np.abs(t.astype(np.float64) - kth)
        width = amax * 2.0 ** -30 + np.spacing(kth)
        check(bool(np.all(gap <= width)),
              f"topk_threshold k={k}: max gap / width "
              f"{float(np.max(gap / width))}")
        print(f"  topk_threshold k={k}: exact share "
              f"{float(np.mean(gap == 0))}, max gap / width "
              f"{float(np.max(gap / width))}", flush=True)

    # the k-stream mix against float64: a float32 contraction over m
    # clients, so within m float32 roundings of the largest term
    k = 4
    cent = jax.random.uniform(kw, (k, m), jnp.float32)
    cent = cent / jnp.sum(cent, axis=1, keepdims=True)
    plan = StreamPlan(cent, jnp.arange(m, dtype=jnp.int32) % k,
                      jnp.float32(0.0))
    mixed = np.asarray(stream_aggregate({"w": x}, plan)["w"], np.float64)
    c64, x64 = np.asarray(cent, np.float64), np.asarray(x, np.float64)
    want = (c64 @ x64)[np.asarray(plan.assignment)]
    bound = m * np.finfo(np.float32).eps * (np.abs(c64) @ np.abs(x64))
    err = np.abs(mixed - want)
    check(bool(np.all(err <= bound[np.asarray(plan.assignment)])),
          f"stream_aggregate: max error / bound "
          f"{float(np.max(err / bound[np.asarray(plan.assignment)]))}")
    print(f"  stream_aggregate: max |mix - float64| "
          f"{float(np.max(err))}", flush=True)


def phase_c(fed, hist) -> None:
    store = DeltaStore.from_history(hist, codec="qsgd:4")
    engine = ServeEngine(store, lambda p, x: lenet.apply(p, x[None])[0],
                         max_batch=4)
    users = np.arange(8)
    xs = np.asarray(fed.x_val[users, 0])
    for u, x in zip(users, xs):
        engine.submit(int(u), x)
    outs = engine.flush()
    check(len(outs) == 8 and all(o.shape == (store.template["out_b"].shape[0],)
                                 and np.all(np.isfinite(o)) for o in outs),
          "flush: expected 8 finite logit rows")
    stats = engine.last_stats
    check(stats["batches"] == 2, f"flush: {stats['batches']} batches for 8 "
          f"requests at max_batch=4")
    check_parity(engine, users, xs)
    print(f"  store {store.summary()}; flush latencies_s "
          f"{stats['latency_s']}; check_parity passed", flush=True)


def phase_d(sizes: Sizes) -> None:
    from repro.launch.train import main as train_main
    loss = train_main(list(sizes.lm_args))
    check(math.isfinite(loss), f"LM loss {loss} is not finite")
    print(f"  final loss {loss}; peak_bytes_in_use "
          f"{jax.devices()[0].memory_stats()['peak_bytes_in_use']}",
          flush=True)


def run_one_chip(sizes: Sizes) -> None:
    fed, hist = run_phase("A paper round", phase_a, sizes)
    run_phase("B kernels vs references", phase_b, sizes.m,
              int(stacked_ravel(hist.final_params).shape[1]))
    run_phase("C serving plane", phase_c, fed, hist)
    del fed, hist
    run_phase("D mamba2-780m published widths", phase_d, sizes)


# ---------------------------------------------------------------------------
# four chips


def check_client_sharding(stacked, m: int, n_dev: int) -> None:
    for leaf in jax.tree_util.tree_leaves(stacked):
        devs = {s.device for s in leaf.addressable_shards}
        rows = {s.data.shape[0] for s in leaf.addressable_shards}
        check(len(devs) == n_dev and rows == {m // n_dev},
              f"client stack leaf {leaf.shape} is on {len(devs)} devices "
              f"in rows of {rows}, not {n_dev} x {m // n_dev}")


def mesh_round(sizes: Sizes) -> None:
    fed = scenario_covariate_shift(jax.random.PRNGKey(0), n=sizes.n,
                                   m=sizes.m)
    kw = dict(fl=FL_A, channel=Channel(codec="identity"), keep_state=True)
    host = run_federated("ucfl_k4", fed, placement=HostVmap(), **kw)
    check_history("host_vmap", host)
    host_devs = {d for leaf in jax.tree_util.tree_leaves(host.final_params)
                 for d in leaf.devices()}
    check(len(host_devs) == 1, f"HostVmap stack spans {host_devs}")
    ref_flat = np.asarray(stacked_ravel(host.final_params), np.float64)
    n_dev = len(jax.devices())
    for schedule in MIX_SCHEDULES:
        t0 = time.perf_counter()
        placement = MeshShardMap(schedule=schedule)
        hist = run_federated("ucfl_k4", fed, placement=placement, **kw)
        check_history(f"mesh {schedule}", hist)
        check_client_sharding(hist.final_params, sizes.m, n_dev)
        some = jax.tree_util.tree_leaves(hist.final_params)[0]
        print(f"  {schedule}: mesh {dict(placement.mesh.shape)}, "
              f"client stack {some.sharding}", flush=True)
        acc_gap = max(np.max(np.abs(np.subtract(hist.mean_acc,
                                                host.mean_acc))),
                      np.max(np.abs(np.subtract(hist.worst_acc,
                                                host.worst_acc))))
        flat = np.asarray(stacked_ravel(hist.final_params), np.float64)
        rel = np.linalg.norm(flat - ref_flat) / np.linalg.norm(ref_flat)
        check(acc_gap <= MESH_ACC_ATOL,
              f"{schedule}: accuracy gap to HostVmap {acc_gap}")
        check(rel <= MESH_PARAM_RTOL,
              f"{schedule}: params relative L2 gap to HostVmap {rel}")
        print(f"PASS mesh {schedule} vs host_vmap seconds="
              f"{time.perf_counter() - t0:.3f} acc_gap={acc_gap} "
              f"param_rel_l2={rel} memory={memory()}", flush=True)


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--chips", type=int, default=1, choices=(1, 4),
                   help="4: run only the mesh round and its HostVmap "
                        "comparison, on four chips")
    args = p.parse_args(argv)
    enable_compile_cache()
    require_tpu(args.chips)
    sizes = Sizes()
    if args.chips == 4:
        run_phase("mesh round over 4 chips", mesh_round, sizes)
    else:
        run_one_chip(sizes)
    d = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
