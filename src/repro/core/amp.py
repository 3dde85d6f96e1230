"""Approximate message passing (AMP) reconstruction at the PS (paper §IV, [31]).

Soft-threshold AMP for y = A x + z with x ~ k-sparse:

    r_t   = x_t + A^T z_t
    x_t+1 = soft(r_t, tau_t),   tau_t = mult * ||z_t|| / sqrt(s)
    z_t+1 = y - A x_t+1 + z_t * (||x_t+1||_0 / s)      (Onsager correction)

Lemma 1 of the paper: the effective observation becomes x + sigma_tau * w with
sigma_tau decreasing monotonically — the tests verify this contraction on
synthetic k-sparse signals.

The blocked variant runs an independent AMP per projection block (the
block-diagonal A factorises the problem) — fully batched, shardable along d.
:func:`amp_blocked_core` is the one chunked decode, run by every kernel
decode, by the slice driver and by the flat jnp path past the working-set
budget: the on-the-fly A chunk is generated exactly ONCE per decode (vs
2*iters+1 times for launch-per-op decoding) and consumed by all iterations,
either as a jnp ``lax.scan`` (XLA path) or inside the fused single-launch
Pallas kernel (kernels/amp_fused.py, ``use_kernel=True``).  Within the
budget the flat jnp path runs :func:`amp_decode_blocked`.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp


def soft_threshold(x: jnp.ndarray, tau) -> jnp.ndarray:
    return jnp.sign(x) * jnp.maximum(jnp.abs(x) - tau, 0.0)


def _debias_factor(num, den):
    """Clamped LS rescale factor correcting the soft-threshold shrinkage.

    Shrinkage can only make ||A x|| smaller than its LS fit to y, so the
    correction is >= 1 by construction; raw factors < 1 (converged AMP — the
    Onsager term has already debiased) or >> 1 (den -> 0 at very low SNR)
    are noise fits and are clamped away.
    """
    return jnp.clip(num / jnp.maximum(den, 1e-12), 1.0, 2.0)


def _ls_rescale(x: jnp.ndarray, ax: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    """Debias the soft-threshold shrinkage: scale x so A x best matches y."""
    return x * _debias_factor(jnp.vdot(ax, y), jnp.vdot(ax, ax))


def amp_decode_dense(y: jnp.ndarray, A: jnp.ndarray, iters: int = 20,
                     threshold_mult: float = 1.3,
                     debias: bool = True) -> jnp.ndarray:
    """Recover x (d,) from y (s,) with the dense measurement matrix A (s,d)."""
    s, d = A.shape

    def body(_, carry):
        x, z = carry
        sigma_hat = jnp.linalg.norm(z) / jnp.sqrt(s)
        r = x + A.T @ z
        x_new = soft_threshold(r, threshold_mult * sigma_hat)
        onsager = z * (jnp.sum(x_new != 0.0) / s)
        z_new = y - A @ x_new + onsager
        return x_new, z_new

    x0 = jnp.zeros((d,), y.dtype)
    x, _ = jax.lax.fori_loop(0, iters, body, (x0, y))
    if debias:
        x = _ls_rescale(x, A @ x, y)
    return x


def amp_decode_blocked(yb: jnp.ndarray, projector, iters: int = 20,
                       threshold_mult: float = 1.3,
                       debias: bool = True) -> jnp.ndarray:
    """Per-block AMP. yb: (n_blocks, s_block) -> flat (d,) estimate.

    All matvecs go through the projector (on-the-fly A), so each AMP
    iteration is two batched projection applications + pointwise math, and
    every application regenerates A — 2*iters+1 generations per decode.
    Prefer :func:`amp_blocked_core` (one generation per decode) unless the
    whole A fits the working-set budget anyway.
    """
    n_blocks, s_block = yb.shape
    c = projector.block_size

    def body(_, carry):
        xb, zb = carry
        sigma_hat = jnp.linalg.norm(zb, axis=1, keepdims=True) / jnp.sqrt(
            jnp.float32(s_block))
        rb = xb + projector.project_t_blocks(zb)
        xb_new = soft_threshold(rb, threshold_mult * sigma_hat)
        onsager = zb * (jnp.sum(xb_new != 0.0, axis=1, keepdims=True) / s_block)
        zb_new = yb - projector.project_blocks(xb_new) + onsager
        return xb_new, zb_new

    x0 = jnp.zeros((n_blocks, c), yb.dtype)
    xb, _ = jax.lax.fori_loop(0, iters, body, (x0, yb))
    if debias:
        axb = projector.project_blocks(xb)
        num = jnp.sum(axb * yb, axis=1, keepdims=True)
        den = jnp.sum(axb * axb, axis=1, keepdims=True)
        xb = xb * _debias_factor(num, den)
    return projector.from_blocks(xb)


def amp_blocked_core(yb: jnp.ndarray, seed, c: int, iters: int = 20,
                     chunk_blocks: int = 8, threshold_mult: float = 1.3,
                     debias: bool = True, rademacher: bool = True,
                     id_offset=0, use_kernel: bool = False) -> jnp.ndarray:
    """Chunked per-block AMP with ONE A-generation per block per decode.

    yb: (n_blocks, s_block) -> xb: (n_blocks, c).  ``seed`` and
    ``id_offset`` (global index of this slice's first block — lets a device
    decode a sub-range of blocks with the encoder's global block ids) may
    be traced uint32 scalars.

    ``use_kernel=False``: jnp ``lax.scan`` over chunks of ``chunk_blocks``
    blocks; each chunk's A is generated once and all AMP iterations for its
    blocks run against it inside the scan body (blocks are independent
    sub-problems under the block-diagonal A), bounding the A working set.
    ``use_kernel=True``: the same structure realised in VMEM by the fused
    single-launch Pallas kernel (kernels/amp_fused.py).
    """
    if use_kernel:
        from repro.kernels import ops
        return ops.amp_decode_fused(yb, seed=seed, c=c, iters=iters,
                                    threshold_mult=threshold_mult,
                                    debias=debias, rademacher=rademacher,
                                    nb_tile=chunk_blocks,
                                    id_offset=id_offset)
    from repro.kernels import ref
    n_blocks, s_block = yb.shape
    ni = min(chunk_blocks, n_blocks)
    pad = (-n_blocks) % ni
    yb_p = jnp.pad(yb, ((0, pad), (0, 0)))
    n_outer = (n_blocks + pad) // ni
    ys = yb_p.reshape(n_outer, ni, s_block)
    ids = (jnp.arange(n_outer * ni, dtype=jnp.uint32)
           + jnp.asarray(id_offset, jnp.uint32)).reshape(n_outer, ni)

    def gen(b):
        return ref.block_matrix_ref(seed, b, s_block, c, rademacher)

    def chunk_amp(_, inp):
        ids_c, y_c = inp
        A = jax.vmap(gen)(ids_c)                     # (ni, s, c) — ONCE

        def body(_, carry):
            x, z = carry
            sigma_hat = jnp.linalg.norm(z, axis=1, keepdims=True) / jnp.sqrt(
                jnp.float32(s_block))
            r = x + jnp.einsum("isc,is->ic", A, z)
            x_new = soft_threshold(r, threshold_mult * sigma_hat)
            onsager = z * (jnp.sum(x_new != 0.0, axis=1, keepdims=True)
                           / s_block)
            z_new = y_c - jnp.einsum("isc,ic->is", A, x_new) + onsager
            return x_new, z_new

        x0 = jnp.zeros((ni, c), y_c.dtype)
        x, _ = jax.lax.fori_loop(0, iters, body, (x0, y_c))
        if debias:
            ax = jnp.einsum("isc,ic->is", A, x)
            num = jnp.sum(ax * y_c, axis=1, keepdims=True)
            den = jnp.sum(ax * ax, axis=1, keepdims=True)
            x = x * _debias_factor(num, den)
        return None, x

    _, xs = jax.lax.scan(chunk_amp, None, (ids, ys))
    return xs.reshape(-1, c)[:n_blocks]


def amp_decode(y_flat: jnp.ndarray, projector, iters: int = 20,
               threshold_mult: float = 1.3) -> jnp.ndarray:
    """Dispatch on projector type; y_flat has projector.out_dim entries."""
    from repro.core.projection import BlockedProjector, DenseProjector
    if isinstance(projector, DenseProjector):
        return amp_decode_dense(y_flat, projector.matrix(), iters,
                                threshold_mult)
    assert isinstance(projector, BlockedProjector)
    yb = y_flat.reshape(projector.n_blocks, projector.s_block)
    if projector.use_kernel or projector.n_blocks > projector.chunk_blocks:
        chunk = (projector.kernel_nb_tile if projector.use_kernel
                 else projector.chunk_blocks)
        xb = amp_blocked_core(yb, projector.seed, projector.block_size,
                              iters, chunk, threshold_mult,
                              rademacher=projector.rademacher,
                              use_kernel=projector.use_kernel)
        return projector.from_blocks(xb)
    return amp_decode_blocked(yb, projector, iters, threshold_mult)
