"""The federated round's layer names (DESIGN.md §3h): named scopes in the
compiled superstep's HLO, and the superstep engine's profiler spans.

The scopes are metadata only; `tests/test_superstep.py`'s bit-parity
tests, unchanged, show that they move no number."""
import glob
import os
import re

import jax
import pytest

from repro.data.federated import scenario_label_shift
from repro.fl import FLConfig, HostVmap, MeshShardMap, run_federated
from repro.fl.simulator import _eval_rounds

KEY = jax.random.PRNGKey(0)
SCOPES = ("local_update/batch", "local_update/loss", "local_update/optimizer",
          "aggregate", "eval")


def _lenet():
    fed = scenario_label_shift(KEY, n=240, m=4)
    return fed, HostVmap(), {}


def _ssm():
    """A 1-layer small-width Mamba-2 with each layer recomputed in the
    backward pass, on the mesh placement's gspmd schedule."""
    from repro.configs import get_config, reduced
    from repro.launch.train import lm_federated_data
    from repro.models import scan
    from repro.models import transformer
    cfg = reduced(get_config("mamba2-780m"), n_layers=1, d_model=64,
                  vocab=64)
    fed = lm_federated_data(KEY, 2, pool=4, n_val=2, seq=32, vocab=64)

    def loss_fn(p, b):
        return scan.loss_fn(p, cfg, {"tokens": b["x"]}, remat=True)

    def acc_fn(p, b):
        return -loss_fn(p, b)[0]

    def model_init(k):
        return scan.stack_layer_params(transformer.init_params(k, cfg), cfg)

    return fed, MeshShardMap(schedule="gspmd"), {
        "loss_fn": loss_fn, "acc_fn": acc_fn, "model_init": model_init}


class _Compiled(Exception):
    """The superstep was compiled; the run stops there."""


@pytest.mark.parametrize("build", [_lenet, _ssm], ids=["lenet-host",
                                                       "ssm-mesh-remat"])
def test_superstep_hlo_carries_the_layer_scopes(build):
    fed, placement, fns = build()
    texts = []

    def compile_only(round_fn, carry, data, consts, length, *, cache,
                     donate=True, eval_fn=None, eval_data=None):
        fn = placement.build_round(round_fn, length=length, donate=donate,
                                   eval_fn=eval_fn)
        texts.append(fn.lower(carry, data, consts, eval_data)
                     .compile().as_text())
        raise _Compiled

    placement.run_supersteps = compile_only
    fl = FLConfig(rounds=1, local_steps=1, batch_size=2, eval_every=1)
    with pytest.raises(_Compiled):
        run_federated("fedavg", fed, fl=fl, placement=placement,
                      superstep=True, **fns)
    names = set(re.findall(r'op_name="([^"]*)"', texts[0]))
    for scope in SCOPES:
        assert any(f"/{scope}/" in n for n in names), scope
    loss = [n for n in names if "/local_update/loss/" in n]
    backward = [n for n in loss if "transpose(" in n]
    assert backward and len(backward) < len(loss)
    if fns:
        # remat's recompute is part of the backward pass
        recompute = [n for n in loss if "rematted_computation" in n
                     or "/checkpoint/" in n]
        assert recompute and all("transpose(" in n for n in recompute)


def _spans(log_dir):
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                        recursive=True)
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for p in ProfileData.from_file(path).planes
            if p.name.startswith("/host:")
            for l in p.lines for e in l.events
            if e.name.startswith(("fl.", "caller"))]


def test_superstep_engine_spans(tmp_path):
    fed = scenario_label_shift(KEY, n=240, m=4)
    fl = FLConfig(rounds=6, local_steps=1, batch_size=4, eval_every=2)
    run_federated("fedavg", fed, fl=fl, superstep=True)   # compiles
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("caller"):
        run_federated("fedavg", fed, fl=fl, superstep=True)
    jax.profiler.stop_trace()
    spans = _spans(str(tmp_path))
    names = [n for n, _, _ in spans]
    chunks = len(list(_eval_rounds(fl.rounds, fl.eval_every)))
    assert chunks == 4
    for name, count in [("caller", 1), ("fl.init", 1), ("fl.model_init", 1),
                        ("fl.stack", 1), ("fl.place_data", 1),
                        ("fl.strategy_setup", 1), ("fl.superstep", chunks),
                        ("fl.readback", chunks), ("fl.replay", chunks),
                        ("fl.finalize", 1)]:
        assert names.count(name) == count, name
    (_, c0, c1), = [s for s in spans if s[0] == "caller"]
    (_, i0, i1), = [s for s in spans if s[0] == "fl.init"]
    for n, s, e in spans:
        assert c0 <= s <= e <= c1, n
        if n in ("fl.model_init", "fl.stack", "fl.place_data",
                 "fl.strategy_setup"):
            assert i0 <= s <= e <= i1, n
    # per chunk: enqueue, then the reads, then the replay
    steps = [n for n, _, _ in sorted(spans, key=lambda s: s[1])
             if n in ("fl.superstep", "fl.readback", "fl.replay")]
    assert steps == ["fl.superstep", "fl.readback", "fl.replay"] * chunks
