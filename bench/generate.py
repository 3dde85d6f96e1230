"""The one traffic generator: every input of a run, made from ``--seed``.

Seeds are whole numbers of any size up to 64 bits; the seed's two 32-bit
halves are the raw key, and each kind of input folds its own salt into
it, so the weights, the round keys, the prompts and the data of a seed
never share a stream.  What a traffic file fixes (sizes, counts, lengths)
reaches here as arguments; nothing here knows a cell by name.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

WEIGHTS, ROUNDS, PROMPTS, DATA, TOKENS = 1, 2, 3, 4, 5


def base_key(seed: int, salt: int) -> jnp.ndarray:
    """Raw uint32[2] key of ``seed`` (any whole number below 2**64)."""
    seed = int(seed) & 0xFFFFFFFFFFFFFFFF
    raw = jnp.asarray([seed >> 32, seed & 0xFFFFFFFF], jnp.uint32)
    return jax.random.fold_in(raw, salt)


def round_keys(seed: int, n: int) -> np.ndarray:
    """(n, 2) uint32: the key of round t is row t."""
    base = base_key(seed, ROUNDS)
    return np.asarray(jax.vmap(lambda t: jax.random.fold_in(base, t))(
        jnp.arange(n)))


def prompts(seed: int, n: int, batch: int, length: int,
            vocab: int) -> np.ndarray:
    """(n, batch, length) int32 prompt tokens, one batch per round."""
    return np.asarray(jax.random.randint(
        base_key(seed, PROMPTS), (n, batch, length), 0, vocab, jnp.int32))


def token_batches(seed: int, n: int, batch: int, length: int,
                  vocab: int) -> np.ndarray:
    """(n, batch, length) int32 training tokens, one batch per step."""
    return np.asarray(jax.random.randint(
        base_key(seed, TOKENS), (n, batch, length), 0, vocab, jnp.int32))


@functools.partial(jax.jit, static_argnames=(
    "n_train", "n_test", "dim", "n_classes", "rank", "m", "b"))
def _surrogate(key, noise, *, n_train, n_test, dim, n_classes, rank, m, b):
    k_t, k_f, k_tr, k_te, k_p = jax.random.split(key, 5)
    templates = jax.random.normal(k_t, (n_classes, dim))
    factors = jax.random.normal(k_f, (n_classes * rank, dim)) / np.sqrt(rank)

    def sample(k, n):
        k_y, k_z, k_n = jax.random.split(k, 3)
        y = jax.random.randint(k_y, (n,), 0, n_classes, jnp.int32)
        z = jax.random.normal(k_z, (n, rank))
        # z . factors[y] without materialising (n, rank, dim)
        zc = (jax.nn.one_hot(y, n_classes)[:, :, None] * z[:, None, :]
              ).reshape(n, n_classes * rank)
        x = (templates[y]
             + 0.5 * jnp.matmul(zc, factors,
                                precision=jax.lax.Precision.HIGHEST)
             + noise * jax.random.normal(k_n, (n, dim)))
        x = (x - x.mean(1, keepdims=True)) / (x.std(1, keepdims=True) + 1e-6)
        return x, y

    x_tr, y_tr = sample(k_tr, n_train)
    x_te, y_te = sample(k_te, n_test)
    idx = jax.random.permutation(k_p, n_train)[: m * b].reshape(m, b)
    return x_tr[idx], y_tr[idx], x_te, y_te


def classification(seed: int, *, n_train: int, n_test: int, dim: int,
                   n_classes: int, rank: int, noise: float, m: int, b: int):
    """The paper's MNIST surrogate on the device, split IID over devices:
    class-conditioned low-rank Gaussian images normalised per row.
    Returns ``(x_dev (m, b, dim), y_dev (m, b), x_test, y_test)``."""
    return _surrogate(base_key(seed, DATA), jnp.float32(noise),
                      n_train=n_train, n_test=n_test, dim=dim,
                      n_classes=n_classes, rank=rank, m=m, b=b)
