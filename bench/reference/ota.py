"""Plain reference of the OTA-DSGD round (arXiv 1901.00844, sec. III-IV).

Written from the paper and the system's documented conventions, with no
import from the program: error feedback, the top-k threshold, the blocked
Rademacher and the dense Gaussian projections, the analog frame with its
mean and scale slots, the Gaussian MAC, AMP at the server, the digital
SBC quantizer with its capacity-derived budget, and Adam.

Shared conventions the reference follows, because the program's results
depend on them:

* blocked measurement matrix: entry (row, col) of block b is
  +-1/sqrt(s_block), its sign the top bit of a chained splitmix32 hash of
  (seed ^ b, row, col);
* dense measurement matrix: ``normal(PRNGKey(seed), (s_tilde, d)) /
  sqrt(s_tilde)`` with s_tilde = s - 2 (two slots of the frame);
* per-chunk keys of the streamed round: ``fold_in(fold_in(round_key, 8),
  i)``; in every round key, salt 0 draws the MAC noise;
* the streamed round's synthetic batches: device j of round t draws its
  tokens from ``split(fold_in(round_key, 9), m)[j]``.

``codec`` is the precision of the codec's products: ``"f32"`` (the
reference: float32 at the highest matmul precision) or ``"bf16"`` (the
control: bfloat16 operands, float32 accumulation).
"""
from __future__ import annotations

import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
_GOLDEN, _M1, _M2 = (np.uint32(0x9E3779B9), np.uint32(0x21F0AAAD),
                     np.uint32(0x735A2D97))

SALT_NOISE, SALT_CHUNK, SALT_BATCH = 0, 8, 9


# ---------------------------------------------------------------------------
# measurement matrices
# ---------------------------------------------------------------------------


def _splitmix32(x):
    x = x + _GOLDEN
    x = x ^ (x >> 16)
    x = x * _M1
    x = x ^ (x >> 15)
    x = x * _M2
    return x ^ (x >> 15)


def rademacher_blocks(seed: int, n_blocks: int, s_block: int,
                      c: int) -> jnp.ndarray:
    """(n_blocks, s_block, c) blocks of +-1/sqrt(s_block), in bfloat16
    (every entry is exact there)."""
    rows = jnp.arange(s_block, dtype=jnp.uint32)[:, None]
    cols = jnp.arange(c, dtype=jnp.uint32)[None, :]
    scale = 1.0 / math.sqrt(s_block)

    def one(b):
        h = _splitmix32(jnp.uint32(seed) ^ b)
        h = _splitmix32(h ^ rows)
        h = _splitmix32(h ^ cols)
        return jnp.where(h < jnp.uint32(1 << 31), scale, -scale
                         ).astype(jnp.bfloat16)

    return jax.lax.map(one, jnp.arange(n_blocks, dtype=jnp.uint32))


def gaussian_matrix(seed: int, s_tilde: int, d: int) -> jnp.ndarray:
    """The paper's dense A: N(0, 1/s_tilde) entries, (s_tilde, d)."""
    return (jax.random.normal(jax.random.PRNGKey(seed), (s_tilde, d),
                              jnp.float32) / jnp.sqrt(jnp.float32(s_tilde)))


def matmul(spec: str, a, b, codec: str):
    """``einsum(spec, a, b)`` in float32 at the codec's precision."""
    if codec == "f32":
        return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32),
                          precision=HIGHEST,
                          preferred_element_type=jnp.float32)
    if codec == "bf16":
        return jnp.einsum(spec, a.astype(jnp.bfloat16),
                          b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    raise ValueError(f"unknown codec precision {codec!r}")


def block_matvec(A, x, codec: str):
    """y_b = A_b x_b: A (n_blocks, s, c), x (..., n_blocks, c)."""
    return matmul("bsc,...bc->...bs", A, x, codec)


# ---------------------------------------------------------------------------
# device side
# ---------------------------------------------------------------------------


def sampled_threshold(v: jnp.ndarray, k: int,
                      n_samples: int = 1 << 16) -> jnp.ndarray:
    """The k-th largest |v| along the last axis, estimated as a quantile of
    every (d // n_samples)-th entry."""
    d = v.shape[-1]
    n = min(n_samples, d)
    stride = d // n
    sample = jnp.abs(v[..., : n * stride: stride] if stride > 1 else v)
    return jnp.quantile(sample, 1.0 - k / d, axis=-1)


def top_k_keep(v: jnp.ndarray, k: int) -> jnp.ndarray:
    """v with all but its k largest-magnitude entries zeroed (ties kept)."""
    kth = jax.lax.top_k(jnp.abs(v), k)[0][..., -1:]
    return jnp.where(jnp.abs(v) >= kth, v, 0.0)


def frame(g_tilde: jnp.ndarray, p_t, mean_removal) -> jnp.ndarray:
    """Analog frame [sqrt(a)(g - mu), sqrt(a) mu, sqrt(a)] along the last
    axis, with a = P_t / (|g|^2 - (s-1) mu^2 + 1) and mu the mean of g
    while mean removal is on (paper eq. 21-22)."""
    s = g_tilde.shape[-1]
    use = jnp.asarray(mean_removal, g_tilde.dtype)
    mu = use * jnp.mean(g_tilde, axis=-1, keepdims=True)
    energy = (jnp.sum(g_tilde * g_tilde, axis=-1, keepdims=True)
              - (s - 1) * mu * mu + 1.0)
    ra = jnp.sqrt(p_t / jnp.maximum(energy, 1e-12))
    return jnp.concatenate([ra * (g_tilde - mu), ra * mu, ra], axis=-1)


def server_body(y: jnp.ndarray, mean_removal) -> jnp.ndarray:
    """(body + mu slot) / scale slot; a scale slot at or below 1e-3 reads
    as noise and is not divided by (paper eq. 25)."""
    body, mu, scale = y[..., :-2], y[..., -2:-1], y[..., -1:]
    use = jnp.asarray(mean_removal, y.dtype)
    return (body + use * mu) / jnp.where(scale > 1e-3, scale, 1.0)


# ---------------------------------------------------------------------------
# AMP at the server
# ---------------------------------------------------------------------------


def _soft(x, tau):
    return jnp.sign(x) * jnp.maximum(jnp.abs(x) - tau, 0.0)


def _debias(num, den):
    return jnp.clip(num / jnp.maximum(den, 1e-12), 1.0, 2.0)


def amp_blocks(y: jnp.ndarray, A: jnp.ndarray, iters: int, codec: str,
               mult: float = 1.3) -> jnp.ndarray:
    """Per-block AMP. y (..., n_blocks, s), A (n_blocks, s, c) ->
    (..., n_blocks, c); ends with the clamped least-squares debias.  The
    iterates are float32; products at the codec's precision."""
    s, dt = A.shape[1], y.dtype

    def fwd(x):
        return block_matvec(A, x, codec).astype(dt)

    def adj(z):
        return matmul("bsc,...bs->...bc", A, z, codec).astype(dt)

    def body(_, carry):
        x, z = carry
        sigma = jnp.sqrt(jnp.sum(z * z, -1, keepdims=True) / s)
        x_new = _soft(x + adj(z), mult * sigma)
        nnz = jnp.sum((x_new != 0).astype(dt), -1, keepdims=True)
        return x_new, y - fwd(x_new) + z * (nnz / s)

    x0 = jnp.zeros(y.shape[:-1] + (A.shape[2],), dt)
    x, _ = jax.lax.fori_loop(0, iters, body, (x0, y))
    ax = fwd(x)
    return x * _debias(jnp.sum(ax * y, -1, keepdims=True),
                       jnp.sum(ax * ax, -1, keepdims=True))


def amp_dense(y: jnp.ndarray, A: jnp.ndarray, iters: int, codec: str,
              mult: float = 1.3) -> jnp.ndarray:
    """AMP with one dense A (s, d): y (s,) -> x (d,)."""
    return amp_blocks(y[None], A[None], iters, codec, mult)[0]


# ---------------------------------------------------------------------------
# digital baseline (D-DSGD, paper sec. III)
# ---------------------------------------------------------------------------


def _log2_binom(d: int, q: int) -> float:
    if q <= 0 or q >= d:
        return 0.0
    return (math.lgamma(d + 1) - math.lgamma(q + 1)
            - math.lgamma(d - q + 1)) / math.log(2.0)


def ddsgd_budget(d: int, s: int, m: int, p_t: float, sigma2: float,
                 q_cap: int) -> int:
    """Largest q with log2 C(d, q) + 33 <= s/(2M) log2(1 + M P_t/(s sigma2))
    (paper eq. 8-9), capped at q_cap."""
    budget = s / (2.0 * m) * math.log2(1.0 + m * p_t / (s * sigma2))
    q = 0
    while q < min(d // 2, q_cap) and _log2_binom(d, q + 1) + 33.0 <= budget:
        q += 1
    return q


def sbc(v: jnp.ndarray, q: int) -> jnp.ndarray:
    """Sparse binary compression: of the q largest and q smallest entries,
    keep the side whose mean is larger in magnitude, each entry replaced by
    that mean."""
    if q <= 0:
        return jnp.zeros_like(v)
    hi = jax.lax.top_k(v, q)[0][-1]
    lo = -jax.lax.top_k(-v, q)[0][-1]
    pos = (v >= hi) & (v > 0)
    neg = (v <= lo) & (v < 0)
    mu_p = jnp.sum(jnp.where(pos, v, 0.0)) / jnp.maximum(jnp.sum(pos), 1)
    mu_n = jnp.sum(jnp.where(neg, v, 0.0)) / jnp.maximum(jnp.sum(neg), 1)
    return jnp.where(mu_p > jnp.abs(mu_n), jnp.where(pos, mu_p, 0.0),
                     jnp.where(neg, mu_n, 0.0))


# ---------------------------------------------------------------------------
# the server's optimizer
# ---------------------------------------------------------------------------


def adam_init(params):
    zeros = jax.tree.map(jnp.zeros_like, params)
    return {"m": zeros, "v": jax.tree.map(jnp.zeros_like, params),
            "count": jnp.zeros((), jnp.int32)}


def adam_lr(step, lr: float, warmup: int, total: int):
    """Linear warmup to lr over ``warmup`` steps, then cosine to 0 at
    ``total`` (constant when total is 0)."""
    step = jnp.asarray(step, jnp.float32)
    warm = jnp.minimum(step / warmup, 1.0) if warmup > 0 else 1.0
    if total > 0:
        frac = jnp.clip((step - warmup) / max(total - warmup, 1), 0.0, 1.0)
        return lr * warm * 0.5 * (1.0 + jnp.cos(jnp.pi * frac))
    return lr * warm


def adam_step(params, grads, state, *, lr: float, warmup: int = 0,
              total: int = 0, b1: float = 0.9, b2: float = 0.999,
              eps: float = 1e-8):
    """One Adam step with bias correction; the learning rate of the k-th
    step (k from 0) is ``adam_lr(k)``."""
    count = state["count"] + 1
    rate = adam_lr(state["count"], lr, warmup, total)
    m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g, state["m"], grads)
    v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2) * g * g, state["v"],
                     grads)
    c = count.astype(jnp.float32)
    mh, vh = 1.0 / (1 - b1 ** c), 1.0 / (1 - b2 ** c)
    params = jax.tree.map(
        lambda p, m_, v_: (p - rate * (m_ * mh / (jnp.sqrt(v_ * vh) + eps))
                           ).astype(p.dtype),
        params, m, v)
    return params, {"m": m, "v": v, "count": count}


def leaf_norms(tree) -> Dict[str, float]:
    """{path: L2 norm} of every leaf, in f32 on the device."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(p): jnp.sqrt(jnp.sum(
        jnp.square(x.astype(jnp.float32)))) for p, x in flat}


def batch_tokens(round_key, m: int, batch: int, seq: int, vocab: int,
                 rows: Optional[int] = None):
    """(m, rows, seq) tokens the streamed round's devices train on."""
    keys = jax.random.split(jax.random.fold_in(round_key, SALT_BATCH), m)
    toks = jax.vmap(lambda k: jax.random.randint(k, (batch, seq), 0, vocab))(
        keys)
    return toks if rows is None else toks[:, :rows]
