"""FL runtime: data partitioners, simulator rounds, baselines, comm model."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data.federated import (scenario_concept_shift,
                                  scenario_covariate_shift,
                                  scenario_label_shift)
from repro.data.synthetic import synthetic_emnist, synthetic_lm_tokens
from repro.fl import (FLConfig, SYSTEMS, downlink_cost, harmonic,
                      run_federated)
from repro.fl.comm import SystemModel

KEY = jax.random.PRNGKey(0)
SMALL = FLConfig(rounds=3, local_steps=2, batch_size=16, eval_every=1,
                 cfl_min_rounds=1)


def _tiny_fed(m=6, n=600):
    return scenario_label_shift(KEY, n=n, m=m)


def test_synthetic_emnist_shapes():
    d = synthetic_emnist(KEY, 100)
    assert d["x"].shape == (100, 28, 28, 1)
    assert int(jnp.max(d["y"])) < 47


def test_lm_tokens_learnable_structure():
    toks = synthetic_lm_tokens(KEY, 4, 128, 97)
    assert toks.shape == (4, 128)
    assert int(jnp.max(toks)) < 97
    # deterministic rule => repeated contexts repeat targets (mostly)
    assert len(np.unique(np.asarray(toks))) > 5


def test_label_shift_partition_heterogeneous():
    fed = _tiny_fed()
    assert fed.x.shape[0] == 6
    # Dirichlet(0.4): client label histograms should differ
    h = [np.bincount(np.asarray(fed.y[i]), minlength=47) for i in range(6)]
    corr = np.corrcoef(np.stack(h))
    assert corr.min() < 0.9


def test_covariate_shift_groups_rotate():
    fed = scenario_covariate_shift(KEY, n=800, m=8)
    assert set(np.asarray(fed.group)) == {0, 1, 2, 3}


def test_concept_shift_permutes_labels():
    fed = scenario_concept_shift(KEY, n=600, m=8)
    assert fed.x.shape[-1] == 3
    assert set(np.asarray(fed.group)) == {0, 1, 2, 3}


@pytest.mark.parametrize("alg", ["fedavg", "local", "ucfl", "ucfl_k2",
                                 "oracle", "cfl", "fedfomo"])
def test_all_algorithms_run(alg):
    fed = _tiny_fed()
    h = run_federated(alg, fed, fl=SMALL, system=SYSTEMS["wired"])
    assert len(h.mean_acc) == 3
    assert all(0.0 <= a <= 1.0 for a in h.mean_acc)
    assert h.time[-1] > 0


def test_ucfl_mixing_matrix_recorded():
    fed = _tiny_fed()
    h = run_federated("ucfl", fed, fl=SMALL)
    w = h.extra["mixing_matrix"]
    assert w.shape == (6, 6)
    np.testing.assert_allclose(w.sum(1), np.ones(6), rtol=1e-4)


@pytest.mark.parametrize("stat", ["full_client_gradients",
                                  "sigma2_estimates"])
def test_client_stats_chunked_match_whole_stack(stat, monkeypatch):
    """Taking the clients a chunk at a time (2 per chunk, 5 clients: two
    chunks and a remainder) gives each client the statistic it gets when
    the whole stack goes through at once, up to float32 reassociation of
    the sums over a client's samples."""
    from repro.fl import stats
    from repro.models import lenet
    fed = _tiny_fed(m=5)
    params = lenet.init_params(KEY, lenet.LeNetConfig())
    args = (lenet.loss_fn, params, fed) + (
        (3,) if stat == "sigma2_estimates" else ())
    fn = getattr(stats, stat).__wrapped__     # traced anew on each call
    whole = np.asarray(fn(*args))
    monkeypatch.setattr(stats, "CHUNK_SAMPLES", 2 * fed.x.shape[1])
    chunked = np.asarray(fn(*args))
    assert chunked.shape == whole.shape and whole.shape[0] == 5
    bound = fed.x.shape[1] * np.finfo(np.float32).eps * np.abs(whole).max()
    np.testing.assert_allclose(chunked, whole, rtol=0, atol=bound)


def test_training_improves_over_init():
    fed = _tiny_fed(m=4, n=500)
    fl = FLConfig(rounds=8, local_steps=5, batch_size=32, eval_every=7)
    h = run_federated("fedavg", fed, fl=fl)
    assert h.mean_acc[-1] > h.mean_acc[0] + 0.05


# ---------------------------------------------------------------------------
# comm model (paper §IV-C)


def test_harmonic_and_compute_time():
    assert abs(harmonic(3) - (1 + 0.5 + 1 / 3)) < 1e-9
    s = SystemModel(rho=4.0, t_min=1.0, inv_mu=1.0)
    assert s.compute_time(3) == pytest.approx(1.0 + harmonic(3))
    r = SystemModel(rho=2.0, t_min=1.0, inv_mu=0.0)
    assert r.compute_time(100) == 1.0


def test_harmonic_asymptotic_matches_exact_at_crossover():
    """Above the cutoff H_m switches to ln(m)+γ+1/(2m)−1/(12m²); the two
    forms must agree to 1e-6 where they meet (and well beyond)."""
    from repro.fl.comm import _HARMONIC_EXACT_MAX as cut
    for m in (cut - 1, cut, cut + 1, cut + 9, 10 * cut):
        exact = sum(1.0 / i for i in range(1, m + 1))
        assert abs(harmonic(m) - exact) < 1e-6, m
    # monotone through the crossover
    assert harmonic(cut) < harmonic(cut + 1) < harmonic(cut + 2)


def test_round_time_orderings():
    """FedAvg round < UCFL-k round < UCFL-full round < FedFOMO round."""
    m = 20
    s = SYSTEMS["wired"]
    t = {}
    for alg, ns in [("fedavg", 1), ("ucfl_k4", 4), ("ucfl", m)]:
        streams, uni = downlink_cost(alg.split("_k")[0], m, n_streams=ns)
        t[alg] = s.round_time(m, n_streams=streams, n_unicasts=uni)
    streams, uni = downlink_cost("fedfomo", m)
    t["fedfomo"] = s.round_time(m, n_streams=streams, n_unicasts=uni)
    assert t["fedavg"] < t["ucfl_k4"] < t["ucfl"] < t["fedfomo"]


def test_asymmetric_ul_dl_shrinks_personalization_penalty():
    """Paper Fig.3: with slow UL (rho=4) + stragglers the extra DL streams
    are relatively cheaper than in the wired system."""
    m = 20
    slow, wired = SYSTEMS["wireless_slow"], SYSTEMS["wired"]
    def rel_penalty(sys_):
        t1 = sys_.round_time(m, n_streams=1)
        tm = sys_.round_time(m, n_streams=m)
        return (tm - t1) / t1
    assert rel_penalty(slow) < rel_penalty(wired)
