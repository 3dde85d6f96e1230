"""The sampled threshold's quantile by exact selection.

``ops.sample_quantile(..., use_kernel=True)`` finds the two order
statistics of each row by bisection on the float bits
(kernels/quantile_select.py) and interpolates them as ``jnp.quantile``
does; the result must be bitwise ``jnp.quantile(..., axis=-1)``, jitted,
whatever the row holds.  Off-TPU the kernel runs in interpret mode.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import OTAConfig
from repro.core.schemes import MACContext, encode_round, get_scheme
from repro.kernels import ops


def _rows(m: int, n: int, seed: int) -> np.ndarray:
    """m non-negative float32 rows of n: |normal| at a random scale, with
    zeros, ties, denormals and +inf mixed in."""
    rng = np.random.default_rng(seed)
    x = np.abs(rng.standard_normal((m, n))).astype(np.float32)
    x *= np.float32(10.0) ** rng.uniform(-6, 2, (m, 1)).astype(np.float32)
    kind = rng.integers(0, 10, (m, n))
    x[kind == 0] = 0.0
    x[kind == 1] = x[0, 0]                                  # ties
    denormal = rng.integers(1, 1 << 23, (m, n)).astype(np.uint32)
    x[kind == 2] = denormal.view(np.float32)[kind == 2]
    x[(kind == 3) & (rng.random((m, n)) < 0.3)] = np.inf
    return x


def _assert_bitwise(got, want):
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape and got.dtype == want.dtype == np.float32
    np.testing.assert_array_equal(np.isnan(got), np.isnan(want))
    real = ~np.isnan(want)
    np.testing.assert_array_equal(got[real].view(np.uint32),
                                  want[real].view(np.uint32))


def _jnp_quantile(x, q):
    return jax.jit(lambda s: jnp.quantile(s, q, axis=-1))(x)


@pytest.mark.parametrize("q", [0.875, 0.5, 0.0, 1.0])
@pytest.mark.parametrize("n", [65536, 1000, 7])
@pytest.mark.parametrize("m", [1, 2, 25])
def test_select_is_bitwise_jnp_quantile(m, n, q):
    x = jnp.asarray(_rows(m, n, seed=m * 7919 + n))
    got = ops.sample_quantile(x, q, use_kernel=True)
    _assert_bitwise(got, _jnp_quantile(x, q))
    _assert_bitwise(ops.sample_quantile(x, q), _jnp_quantile(x, q))


@pytest.mark.parametrize("q", [0.875, 0.5, 0.0, 1.0])
def test_row_with_nan_gives_nan(q):
    x = np.minimum(_rows(3, 1000, seed=3), 1e30)   # no inf * 0 at q = 1
    x[1, 17] = np.nan
    got = np.asarray(ops.sample_quantile(jnp.asarray(x), q, use_kernel=True))
    assert np.isnan(got[1]) and not np.isnan(got[[0, 2]]).any()
    _assert_bitwise(got, _jnp_quantile(jnp.asarray(x), q))


@pytest.mark.parametrize("n", [65536, 1000])
def test_vmapped_select_is_one_call_and_bitwise(n):
    """A vmap over devices, as ``encode_round`` makes it: one kernel call
    for the stack, each device's value bitwise its own ``jnp.quantile``."""
    x = jnp.asarray(_rows(2, n, seed=n))
    f = jax.vmap(lambda s: ops.sample_quantile(s, 0.875, use_kernel=True))
    jaxpr = jax.make_jaxpr(f)(x)
    assert str(jaxpr).count("quantile_select") == 1
    _assert_bitwise(jax.jit(f)(x), _jnp_quantile(x, 0.875))


def _scheme(use_kernel: bool):
    cfg = OTAConfig(scheme="a_dsgd", projection="blocked", s_frac=0.25,
                    k_frac=0.5, block_size=256, rademacher=True,
                    use_kernel=use_kernel)
    return get_scheme(cfg, 1 << 14, 2)


def test_encode_round_kernel_threshold_is_bitwise_oracle(monkeypatch):
    """A jitted ``encode_round`` at m = 2 on the kernel path is bitwise the
    same round with the threshold from the oracle ``jnp.quantile``: the
    frames, the error-feedback states and the metrics."""
    scheme = _scheme(use_kernel=True)
    kg, kd, key = jax.random.split(jax.random.PRNGKey(0), 3)
    g = jax.random.normal(kg, (2, scheme.d), jnp.float32)
    dl = 0.1 * jax.random.normal(kd, (2, scheme.d), jnp.float32)
    ctx = MACContext(m=2)

    def run():
        return jax.jit(lambda g, dl: encode_round(scheme, g, dl, 0, key,
                                                  ctx)[:3])(g, dl)

    jax.clear_caches()                      # count this test's lowerings
    before = ops.quantile_lowerings()
    got = run()
    mid = ops.quantile_lowerings()
    oracle = ops.sample_quantile
    monkeypatch.setattr(ops, "sample_quantile",
                        lambda s, q, use_kernel: oracle(s, q,
                                                        use_kernel=False))
    want = run()
    after = ops.quantile_lowerings()
    assert mid["select"] > before["select"] and mid["sort"] == before["sort"]
    assert after["sort"] > mid["sort"] and after["select"] == mid["select"]
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert float(jnp.max(jnp.abs(got[1]))) > 0     # EF banked something


@pytest.mark.parametrize("use_kernel", [True, False])
def test_quantile_lowerings_count_the_path(use_kernel):
    """The streamed encode's threshold lowers to ``select`` with the
    codec's kernels, to ``sort`` (``jnp.quantile``) on the jnp path."""
    scheme = _scheme(use_kernel)
    ctx = MACContext(m=2)
    g = jnp.ones((2, scheme.d), jnp.float32)
    jax.clear_caches()                      # count this trace's lowering
    before = ops.quantile_lowerings()
    jax.make_jaxpr(lambda g: encode_round(scheme, g, g, 0,
                                          jax.random.PRNGKey(1), ctx))(g)
    after = ops.quantile_lowerings()
    want = {"select": 1, "sort": 0} if use_kernel else {"select": 0,
                                                       "sort": 1}
    assert {k: after[k] - before[k] for k in after} == want


def test_slice_threshold_routes_through_sample_quantile(monkeypatch):
    """The slice driver's all-gathered sample takes the same selection."""
    scheme = get_scheme(_scheme(True).cfg, 1 << 14, 1)
    seen = []
    real = ops.sample_quantile

    def spy(s, q, *, use_kernel):
        seen.append(use_kernel)
        return real(s, q, use_kernel=use_kernel)

    monkeypatch.setattr(ops, "sample_quantile", spy)
    g = jax.random.normal(jax.random.PRNGKey(2), (scheme.d,), jnp.float32)
    ctx = MACContext(m=1, d_pad=scheme.d)
    _, _, metrics = jax.jit(lambda g: scheme.encode_slice(
        g, jnp.zeros_like(g), 0, jax.random.PRNGKey(3), ctx))(g)
    assert seen == [True]
    sample = jnp.abs(g[::scheme.d // ctx.sample_per_shard])
    q = 1.0 - max(1, int(0.5 * 0.25 * scheme.d)) / scheme.d
    _assert_bitwise(metrics["tau"], _jnp_quantile(sample, q))
