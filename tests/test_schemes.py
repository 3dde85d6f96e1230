"""Scheme registry + refactor-parity tests.

The goldens in tests/golden/simulated_parity.npz were generated from the
pre-registry implementation (the ``Aggregator.encode`` if/elif chain) at a
fixed seed; asserting bitwise equality here proves the ``Scheme`` registry
refactor changed no numerics (see tests/golden/make_golden.py).
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import OTAConfig
from repro.core import schemes
from repro.core.schemes import (
    MACContext, PAPER_SCHEMES, SCHEME_REGISTRY, SCHEMES, Scheme, get_scheme,
    register_scheme, round_simulated,
)

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from tests.golden.parity_cases import PARITY_CASES  # noqa: E402

D, M = 256, 6

_GOLDEN = np.load(os.path.join(os.path.dirname(__file__), "golden",
                               "simulated_parity.npz"))


# ---------------------------------------------------------------------------
# registry round-trip
# ---------------------------------------------------------------------------


def test_registry_contains_all_paper_schemes_plus_fading():
    for name in PAPER_SCHEMES:
        assert name in SCHEME_REGISTRY
    assert "a_dsgd_fading" in SCHEME_REGISTRY
    assert set(SCHEMES) == set(SCHEME_REGISTRY)


@pytest.mark.parametrize("name", sorted(SCHEME_REGISTRY))
def test_get_scheme_roundtrip(name):
    cfg = OTAConfig(scheme=name, total_steps=10)
    sch = get_scheme(cfg, D, M)
    assert isinstance(sch, SCHEME_REGISTRY[name])
    assert sch.name == name
    assert sch.d == D and sch.m == M
    state = sch.init_state()
    assert state.shape == (D,)
    assert int(sch.channel_dim()) > 0


def test_get_scheme_unknown_name_raises():
    with pytest.raises(KeyError, match="unknown scheme"):
        get_scheme(OTAConfig(scheme="carrier_pigeon"), D, M)


def test_legacy_fading_flag_promotes_to_fading_scheme():
    cfg = OTAConfig(scheme="a_dsgd", fading="rayleigh", projection="dense",
                    total_steps=10)
    assert type(get_scheme(cfg, D, M)).__name__ == "ADSGDFadingScheme"


def test_register_custom_scheme_runs_on_generic_driver():
    """The ~10-line extension from the README, end to end."""

    @register_scheme("_test_half")
    class HalfScheme(Scheme):
        def channel_dim(self, d=None):
            return self.d

        def encode(self, g, state, step, key, ctx=None):
            return 0.5 * g.astype(jnp.float32), state, {}

    try:
        sch = get_scheme(OTAConfig(scheme="_test_half", total_steps=5), D, M)
        grads = jnp.ones((M, D))
        ghat, _, _ = round_simulated(sch, grads, jnp.zeros((M, D)), 0,
                                     jax.random.PRNGKey(0))
        np.testing.assert_allclose(np.asarray(ghat), 0.5, rtol=1e-6)
    finally:
        del SCHEME_REGISTRY["_test_half"]


# ---------------------------------------------------------------------------
# fixed-seed parity with the pre-refactor implementation
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("case", sorted(PARITY_CASES))
def test_simulated_driver_bitwise_parity(case):
    cfg = PARITY_CASES[case]
    grads = jnp.asarray(_GOLDEN["grads"])
    sch = get_scheme(cfg, D, M)
    ghat, nd, _ = round_simulated(sch, grads, jnp.zeros((M, D)), 0,
                                  jax.random.PRNGKey(11))
    np.testing.assert_array_equal(np.asarray(ghat), _GOLDEN[f"{case}__ghat"])
    np.testing.assert_array_equal(np.asarray(nd), _GOLDEN[f"{case}__deltas"])


def test_csi_err_zero_is_fading_golden():
    """a_dsgd_csi_err at zero estimation error degrades *bitwise* to
    a_dsgd_fading: the estimate h_hat = h + 0*e is IEEE-exact and the
    misalignment gain is exactly 1.0, so the two goldens must be the same
    arrays (acceptance criterion of the fading-suite PR)."""
    np.testing.assert_array_equal(_GOLDEN["a_dsgd_csi_err0__ghat"],
                                  _GOLDEN["a_dsgd_rayleigh__ghat"])
    np.testing.assert_array_equal(_GOLDEN["a_dsgd_csi_err0__deltas"],
                                  _GOLDEN["a_dsgd_rayleigh__deltas"])


# ---------------------------------------------------------------------------
# driver parity: ideal scheme, simulated == sharded (single host)
# ---------------------------------------------------------------------------


def test_ideal_simulated_matches_sharded_single_host():
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import auto_mesh

    cfg = OTAConfig(scheme="ideal", total_steps=10)
    n_dev = jax.device_count()
    mesh = auto_mesh((n_dev,), ("dev",))
    grads = jnp.asarray(_GOLDEN["grads"][:n_dev])
    deltas = jnp.zeros((n_dev, D))
    sch = get_scheme(cfg, D, n_dev)
    ghat_sim, _, _ = schemes.round_simulated(sch, grads, deltas, 0,
                                             jax.random.PRNGKey(3))

    ctx = MACContext(m=n_dev, device_axes=("dev",))

    def body(g, dl):
        ghat, nd, _ = schemes.round_sharded(sch, g.reshape(-1),
                                            dl.reshape(-1), 0,
                                            jax.random.PRNGKey(3), ctx)
        return ghat

    ghat_sh = jax.shard_map(
        body, mesh=mesh, in_specs=(P("dev"), P("dev")),
        out_specs=P(), axis_names={"dev"},
        check_vma=False)(grads, deltas)
    np.testing.assert_allclose(np.asarray(ghat_sim), np.asarray(ghat_sh),
                               rtol=1e-6, atol=1e-7)


def test_fading_reaches_sharded_drivers():
    """a_dsgd_fading is live on round_sharded and the slice driver: with an
    impossible fade threshold every device is silent, so the whole update
    accumulates into the error state (truncated inversion, follow-up [34])."""
    from jax.sharding import PartitionSpec as P
    from repro.core import distributed
    from repro.launch.mesh import auto_mesh

    n_dev = jax.device_count()
    mesh = auto_mesh((n_dev,), ("dev",))
    grads = jnp.asarray(_GOLDEN["grads"][:n_dev])
    deltas = jnp.zeros((n_dev, D))
    cfg = OTAConfig(scheme="a_dsgd_fading", fading_threshold=1e9,
                    s_frac=0.5, k_frac=0.25, p_avg=500.0, total_steps=10,
                    projection="blocked", block_size=64, amp_iters=5)
    sch = get_scheme(cfg, D, n_dev)
    ctx = MACContext(m=n_dev, device_axes=("dev",), d_pad=D,
                     fading="rayleigh")

    def slice_body(g, dl):
        _, nd, _ = distributed.sharded_round(sch, g.reshape(-1),
                                             dl.reshape(-1), 0,
                                             jax.random.PRNGKey(5), ctx)
        return nd.reshape(1, -1)

    nd = jax.shard_map(
        slice_body, mesh=mesh, in_specs=(P("dev"), P("dev")),
        out_specs=P("dev"), axis_names={"dev"},
        check_vma=False)(grads, deltas)
    # silent device: Delta' = g + Delta (here Delta = 0)
    np.testing.assert_allclose(np.asarray(nd), np.asarray(grads), rtol=1e-6)

    def psum_body(g, dl):
        _, nd, _ = schemes.round_sharded(sch, g.reshape(-1), dl.reshape(-1),
                                         0, jax.random.PRNGKey(5), ctx)
        return nd.reshape(1, -1)

    nd2 = jax.shard_map(
        psum_body, mesh=mesh, in_specs=(P("dev"), P("dev")),
        out_specs=P("dev"), axis_names={"dev"},
        check_vma=False)(grads, deltas)
    np.testing.assert_allclose(np.asarray(nd2), np.asarray(grads), rtol=1e-6)


def test_ideal_slice_driver_matches_mean():
    """The generic slice driver (distributed.sharded_round) on one host."""
    from jax.sharding import PartitionSpec as P
    from repro.core import distributed
    from repro.launch.mesh import auto_mesh

    cfg = OTAConfig(scheme="ideal", total_steps=10)
    n_dev = jax.device_count()
    mesh = auto_mesh((n_dev,), ("dev",))
    grads = jnp.asarray(_GOLDEN["grads"][:n_dev])
    deltas = jnp.zeros((n_dev, D))
    sch = get_scheme(cfg, D, n_dev)
    ctx = MACContext(m=n_dev, device_axes=("dev",), d_pad=D)

    def body(g, dl):
        ghat, nd, _ = distributed.sharded_round(sch, g.reshape(-1),
                                                dl.reshape(-1), 0,
                                                jax.random.PRNGKey(3), ctx)
        return ghat

    ghat = jax.shard_map(
        body, mesh=mesh, in_specs=(P("dev"), P("dev")),
        out_specs=P(), axis_names={"dev"},
        check_vma=False)(grads, deltas)
    np.testing.assert_allclose(np.asarray(ghat),
                               np.asarray(grads.mean(0)), rtol=1e-5)
