"""Gradient compression primitives (paper §III, §IV and the §VI baselines).

All functions are pure and jit-friendly.  Top-k selection comes in two
flavours: exact (lax.top_k — paper-scale) and sampled-quantile threshold
(framework-scale, one pass + pointwise mask; see docs/DESIGN.md §4.3).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.tracing import stage

# ---------------------------------------------------------------------------
# sparsification
# ---------------------------------------------------------------------------


@stage("threshold")
def top_k_sparsify(v: jnp.ndarray, k: int) -> jnp.ndarray:
    """Exact sp_k: keep the k largest-magnitude entries of v (paper Alg. 1)."""
    d = v.shape[-1]
    k = min(k, d)
    mag = jnp.abs(v)
    kth = jax.lax.top_k(mag, k)[0][..., -1:]
    keep = mag >= kth
    # guard against ties inflating the support: exact k not required by the
    # algorithm (ties share the same magnitude), but tests check <= k + ties.
    return jnp.where(keep, v, 0.0)


def topk_threshold(v: jnp.ndarray, k: int) -> jnp.ndarray:
    """The k-th largest |v| (exact)."""
    return jax.lax.top_k(jnp.abs(v), min(k, v.shape[-1]))[0][..., -1]


@stage("threshold")
def sampled_topk_threshold(v: jnp.ndarray, k: int, key: jnp.ndarray,
                           n_samples: int = 1 << 16) -> jnp.ndarray:
    """Approximate k-th largest |v| from a strided sample (framework scale).

    Strided sampling (start offset from the key) instead of random gather:
    indices stay int32-safe at d > 2^31 and the read is a cheap slice.  The
    sparsifier then applies the threshold pointwise.
    """
    d = v.shape[-1]
    n = min(n_samples, d)
    stride = d // n
    if stride <= 1:
        sample = jnp.abs(v)
    else:
        sample = jnp.abs(jax.lax.slice_in_dim(v, 0, n * stride, stride,
                                              axis=-1))
    q = 1.0 - (k / d)
    return jnp.quantile(sample, q, axis=-1)


def error_feedback(g: jnp.ndarray, delta: jnp.ndarray) -> jnp.ndarray:
    """g^ec = g + Delta (paper Alg. 1 line 5)."""
    return g + delta


def residual(g_ec: jnp.ndarray, g_sp: jnp.ndarray) -> jnp.ndarray:
    """Delta' = g^ec - g^sp (paper eq. 10)."""
    return g_ec - g_sp


# ---------------------------------------------------------------------------
# D-DSGD quantizer (paper §III, following Sattler et al. [21])
# ---------------------------------------------------------------------------


def sbc_quantize(v: jnp.ndarray, q_t: jnp.ndarray, q_max: int) -> jnp.ndarray:
    """Sparse binary compression with a dynamic budget q_t <= q_max.

    Keep the q_t largest and q_t smallest entries (by value); compute the
    mean of surviving positives (mu+) and negatives (mu-); the side with the
    larger |mean| wins — its entries are set to that mean, the other side is
    zeroed (paper §III).  q_t may be traced (per-step bit budget); q_max is
    the static bound used for top_k.
    """
    assert v.ndim == 1, "sbc_quantize is per-device; vmap for batches"
    d = v.shape[-1]
    q_max = min(q_max, d)
    with stage("threshold"):
        top_vals, _ = jax.lax.top_k(v, q_max)      # descending
        bot_vals, _ = jax.lax.top_k(-v, q_max)     # descending of -v
    qi = jnp.clip(jnp.asarray(q_t, jnp.int32) - 1, 0, q_max - 1)
    # dynamic thresholds: q_t-th largest / q_t-th smallest
    hi_thresh = top_vals[qi]
    lo_thresh = -bot_vals[qi]
    pos_keep = (v >= hi_thresh) & (v > 0)
    neg_keep = (v <= lo_thresh) & (v < 0)
    npos = jnp.maximum(pos_keep.sum(-1), 1)
    nneg = jnp.maximum(neg_keep.sum(-1), 1)
    mu_pos = jnp.where(pos_keep, v, 0.0).sum(-1) / npos
    mu_neg = jnp.where(neg_keep, v, 0.0).sum(-1) / nneg
    pos_wins = mu_pos > jnp.abs(mu_neg)
    out = jnp.where(pos_wins,
                    jnp.where(pos_keep, mu_pos, 0.0),
                    jnp.where(neg_keep, mu_neg, 0.0))
    return jnp.where(jnp.asarray(q_t) > 0, out, jnp.zeros_like(out))


# ---------------------------------------------------------------------------
# digital baselines (paper §VI): SignSGD [16] and QSGD [2] under a bit budget
# ---------------------------------------------------------------------------


def signsgd_compress(v: jnp.ndarray, q_t: jnp.ndarray, q_max: int) -> jnp.ndarray:
    """Top-q_t by magnitude, transmit signs (eq. 43)."""
    assert v.ndim == 1
    d = v.shape[-1]
    q_max = min(q_max, d)
    mags, _ = jax.lax.top_k(jnp.abs(v), q_max)
    qi = jnp.clip(jnp.asarray(q_t, jnp.int32) - 1, 0, q_max - 1)
    tau = mags[qi]
    keep = jnp.abs(v) >= tau
    return jnp.where(keep & (jnp.asarray(q_t) > 0), jnp.sign(v), 0.0)


def qsgd_compress(v: jnp.ndarray, q_t: jnp.ndarray, q_max: int,
                  bits: int, key: jnp.ndarray) -> jnp.ndarray:
    """Top-q_t entries quantized with QSGD stochastic rounding (eq. 44).

    QSGD: q(v_i) = ||v_sel|| * sign(v_i) * xi_i,  xi in {0, 1/L, ..., 1},
    L = 2^bits levels, stochastic rounding unbiased.
    """
    assert v.ndim == 1
    d = v.shape[-1]
    q_max = min(q_max, d)
    mags, _ = jax.lax.top_k(jnp.abs(v), q_max)
    qi = jnp.clip(jnp.asarray(q_t, jnp.int32) - 1, 0, q_max - 1)
    tau = mags[qi]
    keep = (jnp.abs(v) >= tau) & (jnp.asarray(q_t) > 0)
    v_sel = jnp.where(keep, v, 0.0)
    norm = jnp.linalg.norm(v_sel, axis=-1, keepdims=True)
    norm = jnp.maximum(norm, 1e-12)
    L = float(2 ** bits)
    scaled = jnp.abs(v_sel) / norm * L
    floor = jnp.floor(scaled)
    prob = scaled - floor
    u = jax.random.uniform(key, v.shape)
    level = floor + (u < prob)
    return jnp.sign(v_sel) * level / L * norm


# ---------------------------------------------------------------------------
# bit accounting (host-side, numpy)
# ---------------------------------------------------------------------------


def _log2_binom_np(d: int, q: np.ndarray) -> np.ndarray:
    from math import lgamma
    q = np.asarray(q, np.float64)
    out = np.zeros_like(q)
    ln2 = np.log(2.0)
    for i, qq in np.ndenumerate(q):
        qq = float(qq)
        if qq <= 0 or qq >= d:
            out[i] = 0.0
        else:
            out[i] = (lgamma(d + 1) - lgamma(qq + 1) - lgamma(d - qq + 1)) / ln2
    return out


def mac_bit_budget(s: int, m: int, p_t: np.ndarray, sigma2: float) -> np.ndarray:
    """R_t = s/(2M) log2(1 + M P_t / (s sigma^2))  (paper eq. 8)."""
    p_t = np.asarray(p_t, np.float64)
    return s / (2.0 * m) * np.log2(1.0 + m * p_t / (s * sigma2))


def ddsgd_bits(d: int, q: np.ndarray) -> np.ndarray:
    """r_t = log2 C(d, q_t) + 33   (paper eq. 9)."""
    return _log2_binom_np(d, q) + 33.0


def signsgd_bits(d: int, q: np.ndarray) -> np.ndarray:
    """r_t = log2 C(d, q) + q   (paper eq. 43)."""
    return _log2_binom_np(d, q) + np.asarray(q, np.float64)


def qsgd_bits(d: int, q: np.ndarray, l_q: int) -> np.ndarray:
    """r_t = 32 + log2 C(d, q) + (1 + l_Q) q   (paper eq. 44)."""
    return 32.0 + _log2_binom_np(d, q) + (1.0 + l_q) * np.asarray(q, np.float64)


def max_q_for_budget(d: int, budget: float, bits_fn, q_cap: int | None = None) -> int:
    """Largest integer q with bits_fn(d, q) <= budget (paper: choose q_t)."""
    hi = min(d // 2, q_cap) if q_cap else d // 2
    lo = 0
    if bits_fn(d, np.asarray([1.0]))[0] > budget:
        return 0
    while lo < hi:
        mid = (lo + hi + 1) // 2
        if bits_fn(d, np.asarray([float(mid)]))[0] <= budget:
            lo = mid
        else:
            hi = mid - 1
    return lo


def digital_q_schedule(d: int, s: int, m: int, p_ts: np.ndarray, sigma2: float,
                       scheme: str = "d_dsgd", l_q: int = 2,
                       q_cap: int | None = None) -> np.ndarray:
    """Host-precomputed q_t for every step of a digital scheme."""
    budgets = mac_bit_budget(s, m, p_ts, sigma2)
    try:
        fn = functools.partial(BIT_COSTS[scheme], l_q=l_q)
    except KeyError:
        raise ValueError(f"no bit-cost model for scheme {scheme!r}; known: "
                         f"{', '.join(sorted(BIT_COSTS))}") from None
    return np.asarray([max_q_for_budget(d, float(b), fn, q_cap) for b in budgets],
                      np.int32)


#: per-scheme bit-cost models r_t(q) used to size the q_t schedule; digital
#: Scheme subclasses (repro.core.schemes) are looked up here by their
#: registered name.
BIT_COSTS = {
    "d_dsgd": lambda d, q, l_q: ddsgd_bits(d, q),
    "ddsgd": lambda d, q, l_q: ddsgd_bits(d, q),
    "signsgd": lambda d, q, l_q: signsgd_bits(d, q),
    "qsgd": lambda d, q, l_q: qsgd_bits(d, q, l_q),
}
