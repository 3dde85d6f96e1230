"""Fused single-launch AMP decode kernel (paper §IV, Lemma 1).

The PS-side AMP reconstruction is the per-round hot path of A-DSGD: every
iteration needs one forward and one adjoint pass through the block-diagonal
measurement matrix ``A``, which at framework scale is regenerated from a
counter hash on every use.  Launch-per-op decoding therefore pays
``2 * amp_iters + 1`` A-generations per block (adjoint + forward per
iteration, plus the LS debias).

This kernel is the in-kernel realisation of the chunked-scan structure of
``repro.core.amp.amp_blocked_core``: the grid runs over chunks of
``nb_tile`` blocks, each program generates its chunk's A tile **once** into
VMEM, keeps the AMP carries ``(x, z)`` resident, and runs all ``iters``
soft-threshold/Onsager iterations plus the clamped LS debias inside one
``pallas_call``.  A-generation cost per decode drops to exactly one pass
per block and HBM traffic to O(y + x).

Seed and block-id offset arrive through SMEM as *traced* uint32 scalars so
the shard-folded seeds of the fully-sharded slice driver
(core/distributed.py) use the same kernel.  Validated in interpret mode
against the jnp oracle (tests/test_amp_fused.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# pointwise AMP math is shared with the jnp paths (pure-jnp helpers lower
# fine inside a kernel body; core.amp has no module-level kernels import,
# so this does not cycle) — the clamp/epsilon constants live in ONE place
from repro.core.amp import _debias_factor, soft_threshold
from repro.kernels.ota_project import (_SUBLANE, VMEM_TILE_BYTES,
                                       _legal_tile, _pad_blocks, _tile_A)

#: VMEM budget for the resident A of one program (bytes).  One block at the
#: published c = 4096, s_block = 1024 is 16 MiB in f32: the kernel raises
#: its scoped-VMEM limit above the 16 MiB default to hold it (a v5e core
#: has 128 MiB of VMEM).
AMP_A_BYTES = 16 << 20
#: scoped-VMEM headroom above the resident A: the y/x blocks, the AMP
#: carries and the matvec temporaries.
_AMP_HEADROOM = 24 << 20


def _amp_kernel(scal_ref, y_ref, x_ref, a_scr, *, nb_tile, s_block, c, r_tile,
                iters, threshold_mult, debias, rademacher):
    t = pl.program_id(0)
    seed = scal_ref[0, 0]
    b0 = scal_ref[0, 1] + jnp.uint32(t * nb_tile)

    # ONE A-generation per block, resident in VMEM for the whole decode
    def gen(i, carry):
        r0 = pl.multiple_of(i * r_tile, r_tile)
        a_scr[:, pl.ds(r0, r_tile), :] = _tile_A(
            seed, b0, r0.astype(jnp.uint32), jnp.uint32(0), nb_tile, r_tile,
            c, s_block, rademacher)
        return carry

    jax.lax.fori_loop(0, s_block // r_tile, gen, 0)

    # the matvecs run on the VPU in f32: A^T z sums the rows of A against
    # z held as a column (nb, s, 1); A x sums the lanes against x held as
    # a row (nb, 1, c)
    y = jnp.swapaxes(y_ref[...], 1, 2)               # (nb_tile, s_block, 1)
    inv_sqrt_s = jnp.float32(1.0 / (s_block ** 0.5))

    def body(_, carry):
        x, z = carry
        A = a_scr[...]
        sigma_hat = jnp.sqrt(jnp.sum(z * z, axis=1, keepdims=True)) \
            * inv_sqrt_s
        r = x + jnp.sum(A * z, axis=1, keepdims=True)          # adjoint
        x_new = soft_threshold(r, threshold_mult * sigma_hat)
        nnz = jnp.sum((x_new != 0.0).astype(jnp.float32), axis=2,
                      keepdims=True)
        onsager = z * (nnz / s_block)
        z_new = y - jnp.sum(A * x_new, axis=2, keepdims=True) + onsager
        return x_new, z_new

    x0 = jnp.zeros((nb_tile, 1, c), jnp.float32)
    x, z = jax.lax.fori_loop(0, iters, body, (x0, y))
    if debias:
        ax = jnp.sum(a_scr[...] * x, axis=2, keepdims=True)
        num = jnp.sum(ax * y, axis=1, keepdims=True)
        den = jnp.sum(ax * ax, axis=1, keepdims=True)
        x = x * _debias_factor(num, den)
    x_ref[...] = x


def amp_decode_fused_pallas(yb: jnp.ndarray, seed, c: int, *,
                            iters: int = 20, threshold_mult: float = 1.3,
                            debias: bool = True, rademacher: bool = True,
                            nb_tile: int | None = None, id_offset=0,
                            interpret: bool = True) -> jnp.ndarray:
    """Decode yb: (n_blocks, s_block) -> xb: (n_blocks, c) in one launch.

    ``seed`` and ``id_offset`` (global index of the first block, for
    decoding a sub-range with the encoder's global block ids) may be traced
    uint32 scalars.
    """
    n_blocks, s_block = yb.shape
    # clamp any requested nb_tile to the resident-A budget: callers hand
    # down HBM-sized knobs (MACContext.chunk_blocks)
    a_block = s_block * c * 4
    cap = max(1, AMP_A_BYTES // a_block)
    nb_tile = cap if nb_tile is None else max(1, min(nb_tile, cap))
    nb_tile = min(nb_tile, n_blocks)
    r_tile = _legal_tile(s_block, max(_SUBLANE, VMEM_TILE_BYTES // 4
                                      // (nb_tile * c)), _SUBLANE)
    # (n, 1, s) layout: the last two block dims span the array, so any
    # nb_tile is a legal block (a single 16 MiB block is the common case)
    y_p = _pad_blocks(yb.astype(jnp.float32), nb_tile)[:, None, :]
    n_pad = y_p.shape[0]
    scal = jnp.stack([jnp.asarray(seed, jnp.uint32),
                      jnp.asarray(id_offset, jnp.uint32)]).reshape(1, 2)
    kern = functools.partial(_amp_kernel, nb_tile=nb_tile, s_block=s_block,
                             c=c, r_tile=r_tile, iters=iters,
                             threshold_mult=threshold_mult, debias=debias,
                             rademacher=rademacher)
    xb = pl.pallas_call(
        kern,
        grid=(n_pad // nb_tile,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec((nb_tile, 1, s_block), lambda t: (t, 0, 0))],
        out_specs=pl.BlockSpec((nb_tile, 1, c), lambda t: (t, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, 1, c), jnp.float32),
        scratch_shapes=[pltpu.VMEM((nb_tile, s_block, c), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=nb_tile * a_block + _AMP_HEADROOM),
        interpret=interpret,
        name="amp_decode_fused",
    )(scal, y_p)
    return xb[:n_blocks, 0]
