"""Compressive projection of sparsified gradients (paper §IV).

Two realisations:

* ``DenseProjector`` — the paper's A in R^{s_tilde x d}, entries
  N(0, 1/s_tilde), generated once from a shared seed (PS and devices agree).
  Used at paper scale (MNIST, d = 7850).
* ``BlockedProjector`` — TPU-native block-diagonal A: the flattened gradient
  is split into ``n_blocks`` chunks of ``block_size``; each chunk has an
  independent (s_block x block_size) matrix generated on-the-fly from a
  counter hash (kernels/).  Memory O(tile), shardable along d, AMP
  factorises per block.  See docs/DESIGN.md §4.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref

# Host-side cache of generated dense measurement matrices, keyed by
# (seed, s_tilde, d).  Values are *numpy* arrays: an lru_cache of
# jnp.ndarray pins (s_tilde x d) device buffers across sweeps and
# backends (up to 8 full matrices of HBM leaked per multi-seed dense
# sweep).  Host bytes are cheap; ``jnp.asarray`` on use re-devices to
# whatever backend is current, and :func:`clear_dense_cache` frees
# everything explicitly.
_DENSE_CACHE: dict = {}
_DENSE_CACHE_MAX = 8


def clear_dense_cache() -> None:
    """Drop all cached dense measurement matrices (host copies)."""
    _DENSE_CACHE.clear()


def _dense_matrix(seed: int, s_tilde: int, d: int) -> jnp.ndarray:
    """Concrete (never traced) shared measurement matrix; cached per shape.

    Generation goes through jax.random so values are bitwise-identical to
    the historical device-cached version; only the *storage* is host-side.
    """
    key_t = (int(seed), int(s_tilde), int(d))
    host = _DENSE_CACHE.get(key_t)
    if host is None:
        with jax.ensure_compile_time_eval():
            key = jax.random.PRNGKey(seed)
            mat = jax.random.normal(key, (s_tilde, d), jnp.float32) / jnp.sqrt(
                jnp.float32(s_tilde))
        host = np.asarray(mat)
        if len(_DENSE_CACHE) >= _DENSE_CACHE_MAX:
            _DENSE_CACHE.pop(next(iter(_DENSE_CACHE)))
        _DENSE_CACHE[key_t] = host
    return jnp.asarray(host)


@dataclass(frozen=True)
class DenseProjector:
    d: int
    s_tilde: int
    seed: int = 0

    @property
    def out_dim(self) -> int:
        return self.s_tilde

    def matrix(self) -> jnp.ndarray:
        return _dense_matrix(self.seed, self.s_tilde, self.d)

    def project(self, v: jnp.ndarray) -> jnp.ndarray:
        return self.matrix() @ v

    def project_t(self, r: jnp.ndarray) -> jnp.ndarray:
        return self.matrix().T @ r

    def norm_bound(self) -> float:
        """sigma_max = sqrt(d/s_tilde) + 1 (paper App. A, Bai-Yin)."""
        return float(jnp.sqrt(self.d / self.s_tilde) + 1.0)


def _chunk_blocks_for(s_block: int, c: int, budget_bytes: int = 128 << 20) -> int:
    """How many blocks' A matrices fit the working-set budget at once."""
    return max(1, budget_bytes // max(s_block * c * 4, 1))


def blocked_projection(xb: jnp.ndarray, seed, s_block: int,
                       chunk_blocks: int, rademacher: bool = True,
                       use_kernel: bool = False) -> jnp.ndarray:
    """xb (n_blocks, c) -> (n_blocks, s_block): block b times its A_b, each
    A_b generated on the fly from ``seed`` (a python int or a traced uint32,
    e.g. the slice driver's shard-folded seed).

    ``use_kernel`` runs the Pallas projection kernel.  The call stays a
    direct call of ``ops.ota_project``, so that a ``vmap`` over devices
    that share the seed reaches its rule that generates each A tile once
    for all of them.  Otherwise up to ``chunk_blocks`` blocks run the jnp
    oracle at once; past that a scan generates and consumes ``chunk_blocks``
    blocks' A at a time, bounding the A working set (the jnp analogue of the
    kernel's VMEM tiling, docs/DESIGN.md §4.1).
    """
    n_blocks, c = xb.shape
    if use_kernel or n_blocks <= chunk_blocks:
        return ops.ota_project(xb, seed=seed, s_block=s_block,
                               rademacher=rademacher, use_kernel=use_kernel)
    pad = (-n_blocks) % chunk_blocks
    xs = jnp.pad(xb, ((0, pad), (0, 0))).reshape(-1, chunk_blocks, c)
    ids = jnp.arange(xs.shape[0] * chunk_blocks,
                     dtype=jnp.uint32).reshape(-1, chunk_blocks)

    def gen(b):
        return ref.block_matrix_ref(seed, b, s_block, c, rademacher)

    def body(_, inp):
        ids_c, x_c = inp
        A = jax.vmap(gen)(ids_c)                   # (chunk_blocks, s_block, c)
        return None, jnp.einsum("isc,ic->is", A, x_c)

    _, ys = jax.lax.scan(body, None, (ids, xs))
    return ys.reshape(-1, s_block)[:n_blocks]


@dataclass(frozen=True)
class BlockedProjector:
    d: int
    block_size: int            # c
    s_block: int               # s_c  (per-block channel uses)
    seed: int = 0
    rademacher: bool = True
    use_kernel: bool = False

    @property
    def n_blocks(self) -> int:
        return -(-self.d // self.block_size)

    @property
    def chunk_blocks(self) -> int:
        return _chunk_blocks_for(self.s_block, self.block_size)

    @property
    def kernel_nb_tile(self) -> int:
        """Blocks per program of the fused AMP kernel (the VMEM analogue of
        the HBM-budget ``chunk_blocks``: their A stays resident)."""
        from repro.kernels.amp_fused import AMP_A_BYTES
        return _chunk_blocks_for(self.s_block, self.block_size,
                                 budget_bytes=AMP_A_BYTES)

    @property
    def d_pad(self) -> int:
        return self.n_blocks * self.block_size

    @property
    def out_dim(self) -> int:
        return self.n_blocks * self.s_block

    # -- layout ------------------------------------------------------------
    def to_blocks(self, v: jnp.ndarray) -> jnp.ndarray:
        v = jnp.pad(v, (0, self.d_pad - self.d))
        return v.reshape(self.n_blocks, self.block_size)

    def from_blocks(self, xb: jnp.ndarray) -> jnp.ndarray:
        return xb.reshape(self.d_pad)[: self.d]

    # -- ops ----------------------------------------------------------------
    def project(self, v: jnp.ndarray) -> jnp.ndarray:
        """(d,) -> (n_blocks * s_block,) flat projected signal."""
        return self.project_blocks(self.to_blocks(v)).reshape(-1)

    def project_blocks(self, xb: jnp.ndarray) -> jnp.ndarray:
        return blocked_projection(xb, self.seed, self.s_block,
                                  self.chunk_blocks, self.rademacher,
                                  self.use_kernel)

    def project_t_blocks(self, yb: jnp.ndarray) -> jnp.ndarray:
        """The adjoint, all blocks' A at once (the jnp oracle): the one
        :func:`repro.core.amp.amp_decode_blocked` runs within the budget."""
        return ref.ota_project_t_ref(yb, self.seed, self.block_size,
                                     self.rademacher)

    def block_matrix(self, b: int) -> jnp.ndarray:
        """Materialise one block (tests only)."""
        return ref.block_matrix_ref(self.seed, jnp.uint32(b), self.s_block,
                                    self.block_size, self.rademacher)

    def norm_bound(self) -> float:
        return float(jnp.sqrt(self.block_size / self.s_block) + 1.0)


def make_projector(cfg, d: int):
    """Build the projector described by an OTAConfig for a d-dim gradient."""
    if cfg.projection == "dense":
        s = cfg.s_for(d)
        # analog frame reserves 2 channel uses (mean slot + scale slot)
        proj = DenseProjector(d=d, s_tilde=max(s - 2, 1), seed=cfg.seed)
        proj.matrix()   # materialise eagerly (outside any trace)
        return proj
    if cfg.projection == "blocked":
        c = cfg.block_size
        s_block = max(2, int(round(cfg.s_frac * c)))
        return BlockedProjector(d=d, block_size=c, s_block=s_block,
                                seed=cfg.seed, rademacher=cfg.rademacher,
                                use_kernel=cfg.use_kernel)
    raise ValueError(f"unknown projection {cfg.projection!r}")
