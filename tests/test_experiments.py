"""Compiled sweep engine: parity with the reference loop + sweep semantics.

The acceptance bar for the engine is *bitwise* agreement with the legacy
path: one engine step must equal ``round_simulated`` + a manual ADAM
update, and a vmapped grid must reproduce the per-point looped runs.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import OTAConfig
from repro.core.schemes import get_scheme, round_simulated
from repro.data.synthetic import federated_split, make_classification
from repro.experiments import (
    CompiledExperiment, Experiment, eval_indices, round_keys, run_compiled,
    run_sweep,
)
from repro.optim.optim import Optimizer
from repro.train.paper_repro import device_grads, run_federated

STEPS, EVERY, M, B = 6, 2, 4, 64


@pytest.fixture(scope="module")
def data():
    (xtr, ytr), (xte, yte) = make_classification(
        n_train=800, n_test=300, dim=48, noise=2.0, seed=3)
    xd, yd = federated_split(xtr, ytr, m=M, b=B, iid=True, seed=0)
    return (xd, yd), (xte, yte)


def _adsgd(**kw):
    base = dict(scheme="a_dsgd", s_frac=0.5, k_frac=0.25, p_avg=500.0,
                total_steps=STEPS, projection="dense", amp_iters=6,
                mean_removal_steps=2)
    base.update(kw)
    return OTAConfig(**base)


# ---------------------------------------------------------------------------
# bitwise parity with the reference implementation
# ---------------------------------------------------------------------------


def test_engine_step_bitwise_equals_round_simulated_plus_adam(data):
    """One scan step == round_simulated + a manual ADAM update (fixed seed)."""
    (xd, yd), (xte, yte) = data
    cfg = _adsgd()
    exp = Experiment(cfg=cfg, steps=1, lr=1e-3, eval_every=1)
    ce = CompiledExperiment(xd, yd, xte, yte, exp)
    keys = round_keys(1)
    eng = jax.jit(ce.run)({}, keys)

    scheme = get_scheme(cfg, ce.d, M)
    opt = Optimizer(name="adam", lr=1e-3)

    @jax.jit
    def reference(params, t, key):
        deltas = jnp.zeros((M, ce.d), jnp.float32)
        momenta = jnp.zeros((M, ce.d), jnp.float32)
        grads, _ = device_grads(params, ce.unravel, jnp.asarray(xd),
                                jnp.asarray(yd), momenta)
        ghat, deltas, _ = round_simulated(scheme, grads, deltas, t, key)
        params, _ = opt.apply(params, ce.unravel(ghat), opt.init(params))
        return params

    params_ref = reference(ce.params0, 0, jax.random.PRNGKey(1000))
    for leaf_e, leaf_r in zip(jax.tree.leaves(eng["params"]),
                              jax.tree.leaves(params_ref)):
        np.testing.assert_array_equal(np.asarray(leaf_e), np.asarray(leaf_r))


@pytest.mark.parametrize("scheme", ["ideal", "a_dsgd", "d_dsgd"])
def test_run_compiled_matches_run_federated(data, scheme):
    """Full compiled scan == the looped reference, entry for entry."""
    (xd, yd), (xte, yte) = data
    cfg = (_adsgd() if scheme == "a_dsgd"
           else OTAConfig(scheme=scheme, s_frac=0.5, p_avg=500.0,
                          total_steps=STEPS))
    ref = run_federated(xd, yd, xte, yte, cfg, steps=STEPS, lr=1e-3,
                        eval_every=EVERY)
    eng = run_compiled(xd, yd, xte, yte, cfg, steps=STEPS, lr=1e-3,
                       eval_every=EVERY)
    assert eng.accs == ref.accs
    assert eng.losses == ref.losses
    for me, mr in zip(eng.metrics, ref.metrics):
        assert me == mr


def test_sweep_vmapped_p_grid_matches_looped_runs(data):
    """The vmapped P-bar axis reproduces per-point looped runs bitwise —
    for the analog scheme (traced power schedule) and the digital scheme
    (traced q schedule under the shared static q_max)."""
    (xd, yd), (xte, yte) = data
    for base in (_adsgd(), OTAConfig(scheme="d_dsgd", s_frac=0.5,
                                     total_steps=STEPS)):
        res = run_sweep((xd, yd), (xte, yte), base,
                        {"p_avg": [50.0, 500.0]}, steps=STEPS,
                        eval_every=EVERY)
        for p in (50.0, 500.0):
            loop = run_federated(xd, yd, xte, yte,
                                 dataclasses.replace(base, p_avg=p),
                                 steps=STEPS, lr=1e-3, eval_every=EVERY)
            assert res.record(p_avg=p)["accs"] == loop.accs


def test_sweep_fading_axes_vmapped_match_looped(data):
    """The channel-model scalars ride the vmapped path (one XLA program per
    static combo, never re-jitted per point) and reproduce per-point looped
    runs bitwise — including the csi_err_var = 0 point, which must equal a
    looped run of the *perfect-CSI* fading scheme (zero estimation error
    degrades bitwise, per the golden)."""
    (xd, yd), (xte, yte) = data
    base = _adsgd(scheme="a_dsgd_csi_err", fading_threshold=0.2)
    res = run_sweep((xd, yd), (xte, yte), base,
                    {"csi_err_var": [0.0, 0.4],
                     "fading_threshold": [0.2, 0.6]},
                    steps=STEPS, eval_every=EVERY)
    assert len(res.records) == 4
    for ev in (0.0, 0.4):
        for thr in (0.2, 0.6):
            loop = run_federated(
                xd, yd, xte, yte,
                dataclasses.replace(base, csi_err_var=ev,
                                    fading_threshold=thr),
                steps=STEPS, lr=1e-3, eval_every=EVERY)
            assert res.record(csi_err_var=ev,
                              fading_threshold=thr)["accs"] == loop.accs
    perfect = run_federated(
        xd, yd, xte, yte,
        dataclasses.replace(base, scheme="a_dsgd_fading",
                            fading_threshold=0.6),
        steps=STEPS, lr=1e-3, eval_every=EVERY)
    assert res.record(csi_err_var=0.0,
                      fading_threshold=0.6)["accs"] == perfect.accs


def test_sweep_fading_rho_axis_gauss_markov(data):
    """fading_rho vmaps over the windowed-MA weights of the gauss_markov
    process; each point still equals its looped run bitwise."""
    (xd, yd), (xte, yte) = data
    base = _adsgd(scheme="a_dsgd_fading", fading_process="gauss_markov",
                  fading_window=16, fading_threshold=0.3)
    res = run_sweep((xd, yd), (xte, yte), base,
                    {"fading_rho": [0.2, 0.95]}, steps=STEPS,
                    eval_every=EVERY)
    r_lo, r_hi = res.record(fading_rho=0.2), res.record(fading_rho=0.95)
    assert r_lo["accs"] != r_hi["accs"]
    loop = run_federated(xd, yd, xte, yte,
                         dataclasses.replace(base, fading_rho=0.95),
                         steps=STEPS, lr=1e-3, eval_every=EVERY)
    assert r_hi["accs"] == loop.accs


def test_sweep_power_schedule_axis(data):
    """power_schedule vmaps through the same (T,) schedule array."""
    (xd, yd), (xte, yte) = data
    base = OTAConfig(scheme="d_dsgd", s_frac=0.5, p_avg=200.0,
                     total_steps=STEPS)
    res = run_sweep((xd, yd), (xte, yte), base,
                    {"power_schedule": ["constant", "hl_steps"]},
                    steps=STEPS, eval_every=EVERY)
    loop = run_federated(xd, yd, xte, yte,
                         dataclasses.replace(base, power_schedule="hl_steps"),
                         steps=STEPS, lr=1e-3, eval_every=EVERY)
    assert res.record(power_schedule="hl_steps")["accs"] == loop.accs


# ---------------------------------------------------------------------------
# padded device-count sweeps
# ---------------------------------------------------------------------------


def test_m_active_full_mask_matches_unmasked(data):
    (xd, yd), (xte, yte) = data
    cfg = _adsgd()
    res = run_sweep((xd, yd), (xte, yte), cfg, {"m_active": [3, M]},
                    steps=STEPS, eval_every=EVERY)
    full = run_federated(xd, yd, xte, yte, cfg, steps=STEPS, lr=1e-3,
                         eval_every=EVERY)
    assert res.record(m_active=M)["accs"] == full.accs
    assert res.record(m_active=3)["accs"] != full.accs


def test_m_active_ideal_mask_equals_true_subset(data):
    """Ideal scheme has no encode RNG, so masking M_pad -> 2 devices must
    reproduce a genuine 2-device run bitwise (decode divides by the traced
    effective device count)."""
    (xd, yd), (xte, yte) = data
    cfg = OTAConfig(scheme="ideal", total_steps=STEPS)
    res = run_sweep((xd, yd), (xte, yte), cfg, {"m_active": [2]},
                    steps=STEPS, eval_every=EVERY)
    two = run_federated(xd[:2], yd[:2], xte, yte, cfg, steps=STEPS, lr=1e-3,
                        eval_every=EVERY)
    assert res.record(m_active=2)["accs"] == two.accs


# ---------------------------------------------------------------------------
# kernel threading, seeds, schema
# ---------------------------------------------------------------------------


def test_use_kernel_runs_inside_scan(data):
    """OTAConfig.use_kernel routes the blocked projection + fused AMP
    through Pallas (interpret mode off-TPU) inside the scanned loop."""
    (xd, yd), (xte, yte) = data
    cfg = _adsgd(projection="blocked", block_size=64, amp_iters=4)
    jnp_run = run_compiled(xd, yd, xte, yte, cfg, steps=3, eval_every=1)
    krn_run = run_compiled(xd, yd, xte, yte,
                           dataclasses.replace(cfg, use_kernel=True),
                           steps=3, eval_every=1)
    np.testing.assert_allclose(jnp_run.all_accs, krn_run.all_accs, atol=1e-3)


def test_seed_axis_changes_channel_noise(data):
    (xd, yd), (xte, yte) = data
    res = run_sweep((xd, yd), (xte, yte), _adsgd(), {"seed": [0, 1]},
                    steps=STEPS, eval_every=EVERY)
    r0, r1 = res.record(seed=0), res.record(seed=1)
    assert r0["accs"] != r1["accs"]           # different AWGN draws
    # seed 0 is the reference key stream
    loop = run_federated(xd, yd, xte, yte, _adsgd(), steps=STEPS, lr=1e-3,
                         eval_every=EVERY)
    assert r0["accs"] == loop.accs


def test_sweep_result_schema(data):
    (xd, yd), (xte, yte) = data
    res = run_sweep((xd, yd), (xte, yte), _adsgd(),
                    {"scheme": ["a_dsgd", "d_dsgd"], "p_avg": [500.0]},
                    steps=STEPS, eval_every=EVERY)
    assert len(res.records) == 2
    n_evals = len(eval_indices(STEPS, EVERY))
    for rec in res.records:
        assert rec["scheme"] in ("a_dsgd", "d_dsgd")
        assert len(rec["accs"]) == n_evals
        assert rec["final_acc"] == rec["accs"][-1]
        assert rec["us_per_call"] > 0
        assert len(rec["metrics"]) == n_evals
    with pytest.raises(KeyError):
        res.record(scheme="qsgd")


def test_sweep_unknown_axis_raises(data):
    (xd, yd), (xte, yte) = data
    with pytest.raises(KeyError, match="unknown sweep axis"):
        run_sweep((xd, yd), (xte, yte), _adsgd(), {"warp_factor": [9]},
                  steps=2)
