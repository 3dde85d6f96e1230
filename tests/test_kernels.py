"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

SHAPES = [  # (n_blocks, c, s_block)
    (1, 128, 32),
    (3, 256, 64),
    (4, 512, 128),
    (2, 384, 96),
    (5, 64, 16),
]


@pytest.mark.parametrize("nb,c,sb", SHAPES)
@pytest.mark.parametrize("rademacher", [True, False])
def test_project_forward_matches_oracle(nb, c, sb, rademacher):
    x = jax.random.normal(jax.random.PRNGKey(nb), (nb, c), jnp.float32)
    yk = ops.ota_project(x, seed=11, s_block=sb, rademacher=rademacher,
                         use_kernel=True)
    yr = ops.ota_project(x, seed=11, s_block=sb, rademacher=rademacher,
                         use_kernel=False)
    assert yk.shape == (nb, sb)
    np.testing.assert_allclose(yk, yr, rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("nb,c,sb", SHAPES)
@pytest.mark.parametrize("rademacher", [True, False])
def test_fused_amp_matches_scan(nb, c, sb, rademacher):
    """The fused AMP kernel, whose adjoint is the only one a kernel path
    runs, against amp_blocked_core's jnp scan: odd shapes, Gaussian A, and
    chunks of 2 blocks (several programs, the last one padded)."""
    from repro.core.amp import amp_blocked_core
    kx, km, kn = jax.random.split(jax.random.PRNGKey(nb + c), 3)
    keep = jax.random.uniform(km, (nb, c)) < sb / (4 * c)
    x = jnp.where(keep, jax.random.normal(kx, (nb, c)), 0.0)
    y = ref.ota_project_ref(x, 11, sb, rademacher)
    y = y + 0.01 * jnp.std(y) * jax.random.normal(kn, y.shape)
    xk = ops.amp_decode_fused(y, seed=11, c=c, iters=10,
                              rademacher=rademacher, nb_tile=2)
    xs = amp_blocked_core(y, 11, c, iters=10, chunk_blocks=2,
                          rademacher=rademacher)
    assert xk.shape == (nb, c)
    assert float(jnp.max(jnp.abs(xs))) > 0.1        # a nontrivial decode
    np.testing.assert_allclose(xk, xs, rtol=1e-4, atol=1e-5)


def test_projection_adjoint():
    """<A x, y> == <x, A^T y> for the generated operator."""
    nb, c, sb = 3, 256, 64
    x = jax.random.normal(jax.random.PRNGKey(0), (nb, c))
    y = jax.random.normal(jax.random.PRNGKey(1), (nb, sb))
    ax = ops.ota_project(x, seed=5, s_block=sb)
    aty = ref.ota_project_t_ref(y, 5, c)
    np.testing.assert_allclose(float(jnp.vdot(ax, y)),
                               float(jnp.vdot(x, aty)), rtol=1e-4)


@pytest.mark.parametrize("n,tile", [(1024, 256), (4096, 1 << 16), (999, 7)])
def test_ef_sparsify_kernel(n, tile):
    g = jax.random.normal(jax.random.PRNGKey(0), (n,))
    d = jax.random.normal(jax.random.PRNGKey(1), (n,))
    tau = 0.7
    sk, dk = ops.ef_sparsify(g, d, tau, use_kernel=True)
    sr, dr = ops.ef_sparsify(g, d, tau, use_kernel=False)
    np.testing.assert_allclose(sk, sr)
    np.testing.assert_allclose(dk, dr)
    # EF conservation: g_sp + delta' == g + delta exactly
    np.testing.assert_allclose(sk + dk, g + d, rtol=1e-6, atol=1e-6)


def test_hash_statistics():
    A = ref.block_matrix_ref(0, jnp.uint32(3), 256, 512, rademacher=False)
    assert abs(float(A.mean())) < 5e-3
    np.testing.assert_allclose(float(A.var() * 256), 1.0, rtol=5e-2)
    Ar = ref.block_matrix_ref(0, jnp.uint32(3), 256, 512, rademacher=True)
    assert set(np.unique(np.abs(np.asarray(Ar)))) == {np.float32(1 / 16.0)}


def test_blocks_are_decorrelated():
    a = ref.block_matrix_ref(0, jnp.uint32(1), 64, 128)
    b = ref.block_matrix_ref(0, jnp.uint32(2), 64, 128)
    corr = float(jnp.abs(jnp.vdot(a, b)) / (jnp.linalg.norm(a) * jnp.linalg.norm(b)))
    assert corr < 0.1


@pytest.mark.parametrize("n,tile", [(10007, 1 << 10),   # prime n
                                    (97, 8), (5, 16), (1023, 256)])
def test_ef_sparsify_pads_odd_lengths(n, tile):
    """Prime/odd n must pad up to the tile (ceil(n/tile) programs), not
    degenerate to tile=1 (n programs); outputs sliced back, value-exact."""
    from repro.kernels.ef_sparsify import ef_sparsify_pallas
    g = jax.random.normal(jax.random.PRNGKey(2), (n,))
    d = jax.random.normal(jax.random.PRNGKey(3), (n,))
    tau = jnp.float32(0.5)
    sp, nd = ef_sparsify_pallas(g, d, tau, tile=tile)
    sr, dr = ref.ef_sparsify_ref(g, d, tau)
    assert sp.shape == (n,) and nd.shape == (n,)
    np.testing.assert_array_equal(np.asarray(sp), np.asarray(sr))
    np.testing.assert_array_equal(np.asarray(nd), np.asarray(dr))


def test_ef_sparsify_lazy_interpret_default():
    """interpret=None resolves per call from the live backend (CPU here),
    matching ops.interpret_default — not a hardcoded import-time value."""
    from repro.kernels.ef_sparsify import ef_sparsify_pallas
    g = jax.random.normal(jax.random.PRNGKey(4), (64,))
    d = jnp.zeros((64,))
    sp, nd = ef_sparsify_pallas(g, d, jnp.float32(0.3))   # default None
    sr, dr = ref.ef_sparsify_ref(g, d, jnp.float32(0.3))
    np.testing.assert_array_equal(np.asarray(sp), np.asarray(sr))
    assert ops.interpret_default() is True  # CPU test env


# ---------------------------------------------------------------------------
# the forward projection over a stack of devices: one A tile for all
# ---------------------------------------------------------------------------


def _per_device(x, seeds, rademacher):
    """Today's per-device kernel, one call a device: the bitwise reference."""
    from repro.kernels.ota_project import ota_project_pallas
    return np.stack([np.asarray(ota_project_pallas(x[d], seeds[d], 128,
                                                   rademacher))
                     for d in range(x.shape[0])])


@pytest.mark.parametrize("route", ["stack", "stack_grouped",
                                   "vmap_shared_seed", "vmap_batched_seed"])
@pytest.mark.parametrize("m", [1, 2, 3])
@pytest.mark.parametrize("rademacher", [True, False])
def test_stacked_projection_bitwise_per_device(route, m, rademacher,
                                               monkeypatch):
    """Every device's y from the stacked kernel, or from a vmap of
    ``ops.ota_project``, is bitwise its own per-device call's; 13 blocks
    pad to two programs of 8.  A vmap with a shared seed lowers to one
    shared stack, a batched seed falls back to one A per device."""
    from repro.kernels import ota_project
    x = jax.random.normal(jax.random.PRNGKey(m), (m, 13, 256), jnp.float32)
    seeds = [11] * m
    before = ops.projection_lowerings()
    if route.startswith("stack"):
        if route == "stack_grouped":        # one device per group
            monkeypatch.setattr(ota_project, "VMEM_STACK_BYTES", 1)
        y = ota_project.ota_project_pallas(x, 11, 128, rademacher)
        counts = {"shared": 0, "devices": 0, "fallback": 0}
    else:
        jax.clear_caches()                  # count this trace's lowering
        shared = route == "vmap_shared_seed"
        if not shared:
            seeds = [11 + 5 * d for d in range(m)]
        f = jax.vmap(lambda xd, sd: ops.ota_project(
            xd, seed=sd, s_block=128, rademacher=rademacher, use_kernel=True),
            in_axes=(0, None if shared else 0))
        y = f(x, jnp.uint32(11) if shared else jnp.asarray(seeds, jnp.uint32))
        counts = ({"shared": 1, "devices": m, "fallback": 0} if shared
                  else {"shared": 0, "devices": 0, "fallback": 1})
    after = ops.projection_lowerings()
    assert {k: after[k] - before[k] for k in after} == counts
    assert y.shape == (m, 13, 128)
    np.testing.assert_array_equal(np.asarray(y),
                                  _per_device(x, seeds, rademacher))
