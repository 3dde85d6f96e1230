"""AMP reconstruction properties (paper §IV / Lemma 1)."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import projection
from repro.core.amp import amp_decode, amp_decode_blocked, amp_decode_dense
from repro.core.projection import BlockedProjector, DenseProjector


def _sparse_signal(key, d, k, scale=1.0):
    idx = jax.random.choice(key, d, (k,), replace=False)
    vals = jax.random.normal(jax.random.fold_in(key, 1), (k,)) * scale
    return jnp.zeros(d).at[idx].set(vals)


def test_amp_recovers_sparse_dense_matrix():
    d, k, s = 2048, 64, 512
    proj = DenseProjector(d=d, s_tilde=s, seed=3)
    x = _sparse_signal(jax.random.PRNGKey(0), d, k)
    y = proj.project(x)
    xh = amp_decode_dense(y, proj.matrix(), iters=30)
    rel = float(jnp.linalg.norm(xh - x) / jnp.linalg.norm(x))
    assert rel < 0.05, rel


def test_amp_noise_robust():
    d, k, s = 2048, 64, 512
    proj = DenseProjector(d=d, s_tilde=s, seed=3)
    x = _sparse_signal(jax.random.PRNGKey(0), d, k, scale=5.0)
    z = 0.05 * jax.random.normal(jax.random.PRNGKey(2), (s,))
    xh = amp_decode_dense(proj.project(x) + z, proj.matrix(), iters=30)
    rel = float(jnp.linalg.norm(xh - x) / jnp.linalg.norm(x))
    assert rel < 0.15, rel


def test_amp_blocked_recovery_and_scan_equivalence(monkeypatch):
    d, c, sb = 4096, 256, 128
    proj = BlockedProjector(d=d, block_size=c, s_block=sb, seed=5)
    # per-block sparse signal (k_b ~ s_b/4)
    xb = []
    for b in range(d // c):
        xb.append(_sparse_signal(jax.random.PRNGKey(b), c, sb // 4))
    x = jnp.concatenate(xb)
    y = proj.project(x)
    xh = amp_decode(y, proj, iters=30)
    rel = float(jnp.linalg.norm(xh - x) / jnp.linalg.norm(x))
    assert rel < 0.1, rel
    # the chunked-scan decoder matches the batched one: past the A
    # working-set budget (cut to 4 blocks) amp_decode runs the scan
    yb = y.reshape(proj.n_blocks, sb)
    x_batch = amp_decode_blocked(yb, proj, iters=30)
    monkeypatch.setattr(projection, "_chunk_blocks_for", lambda *a, **k: 4)
    assert proj.n_blocks > proj.chunk_blocks
    x_scan = amp_decode(y, proj, iters=30)
    np.testing.assert_allclose(np.asarray(x_scan), np.asarray(x_batch),
                               rtol=1e-4, atol=1e-5)


def test_debias_reduces_shrinkage():
    d, k, s = 2048, 64, 512
    proj = DenseProjector(d=d, s_tilde=s, seed=3)
    x = _sparse_signal(jax.random.PRNGKey(0), d, k)
    y = proj.project(x)
    xh_raw = amp_decode_dense(y, proj.matrix(), iters=15, debias=False)
    xh_db = amp_decode_dense(y, proj.matrix(), iters=15, debias=True)
    err_raw = float(jnp.linalg.norm(xh_raw - x))
    err_db = float(jnp.linalg.norm(xh_db - x))
    assert err_db <= err_raw + 1e-6


def test_effective_noise_contracts_with_iters():
    """Lemma 1: reconstruction error decreases monotonically-ish in iters."""
    d, k, s = 2048, 64, 512
    proj = DenseProjector(d=d, s_tilde=s, seed=3)
    x = _sparse_signal(jax.random.PRNGKey(0), d, k)
    y = proj.project(x)
    errs = [float(jnp.linalg.norm(
        amp_decode_dense(y, proj.matrix(), iters=i) - x)) for i in (2, 8, 30)]
    assert errs[2] < errs[0]


def test_dense_matrix_cache_is_host_side_and_clearable():
    """The dense A cache must hold host (numpy) copies — not pin device
    buffers across sweeps/backends — and regenerate bitwise after clear."""
    from repro.core import projection as projection_mod
    projection_mod.clear_dense_cache()
    m1 = np.asarray(projection_mod._dense_matrix(11, 32, 64))
    cached = projection_mod._DENSE_CACHE[(11, 32, 64)]
    assert isinstance(cached, np.ndarray)          # host-side storage
    m2 = np.asarray(projection_mod._dense_matrix(11, 32, 64))
    np.testing.assert_array_equal(m1, m2)
    projection_mod.clear_dense_cache()
    assert not projection_mod._DENSE_CACHE
    np.testing.assert_array_equal(
        m1, np.asarray(projection_mod._dense_matrix(11, 32, 64)))


def test_dense_matrix_cache_bounded():
    from repro.core import projection as projection_mod
    projection_mod.clear_dense_cache()
    for seed in range(projection_mod._DENSE_CACHE_MAX + 3):
        projection_mod._dense_matrix(seed, 4, 8)
    assert len(projection_mod._DENSE_CACHE) <= projection_mod._DENSE_CACHE_MAX
    projection_mod.clear_dense_cache()
