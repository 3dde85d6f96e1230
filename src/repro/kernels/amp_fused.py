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

The matvecs run on the VPU in f32 and walk the resident A in register
tiles inside explicit loops (``_amp_tiles``): a whole-array expression over
a (1024, 4096) block unrolls into straight-line code that keeps thousands
of vregs live and spills most of them to VMEM, which bounded the kernel
(PERF.md section 5).  The adjoint ``A^T z`` runs column tiles outer and rows
inner into a small accumulator; the forward ``A x`` runs row tiles outer
and lanes inner into partial sums, whose lanes it reduces in a pass of its
own.  z and y are kept in VMEM as columns broadcast across the 128 lanes,
x-hat as a row broadcast down one sublane group, and re-read tile by
tile.

Seed and block-id offset arrive through SMEM as *traced* uint32 scalars so
the shard-folded seeds of the fully-sharded slice driver
(core/distributed.py) use the same kernel.  Validated in interpret mode
against the jnp oracle (tests/test_amp_fused.py).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# pointwise AMP math is shared with the jnp paths (pure-jnp helpers lower
# fine inside a kernel body; core.amp has no module-level kernels import,
# so this does not cycle) — the clamp/epsilon constants live in ONE place
from repro.core.amp import _debias_factor, soft_threshold
from repro.kernels.ota_project import (_LANE, _SUBLANE, _legal_tile,
                                       _pad_blocks, _tile_A)

#: VMEM budget for the resident A of one program (bytes).  One block at the
#: published c = 4096, s_block = 1024 is 16 MiB in f32: the kernel raises
#: its scoped-VMEM limit above the 16 MiB default to hold it (a v5e core
#: has 128 MiB of VMEM).
AMP_A_BYTES = 16 << 20
#: scoped-VMEM headroom above the resident A: the y/x blocks, the staged
#: z, y and x-hat and the matvec temporaries.
_AMP_HEADROOM = 24 << 20
#: vector registers (of a v5e core's 64) that one step of the tiled loops
#: may keep live; the rest are the scheduler's.
_LIVE_VREGS = 48
#: The largest register tiles, rows x lanes of one block's A, chosen on a
#: v5e at c = 4096, s_block = 1024 by the bundle dump and the kernel's time
#: (PERF.md section 6): one hash tile of the generation; rows per trip x
#: accumulator lanes of the adjoint; rows per trip x lanes per product of
#: the forward; and the rows whose lanes one step of the forward reduces.
_GEN_TILE = (128, 128)
_ADJ_TILE = (1024, 1024)
_FWD_TILE = (128, 128)
_RED_ROWS = 1024


class _Tiles(NamedTuple):
    """Register tiles of one (s_block, c) block of A.  ``sub`` rows are one
    sublane group: 8, or the whole block when s_block is not a multiple
    of 8."""
    sub: int
    gen_rows: int   # A generated gen_rows x gen_cols at a time
    gen_cols: int
    adj_rows: int   # A^T z: adj_rows rows per trip into a (sub, adj_cols)
    adj_cols: int   # accumulator
    fwd_rows: int   # A x: fwd_rows rows per trip, fwd_cols lanes per
    fwd_cols: int   # product, then red_rows rows per lane reduction
    red_rows: int


def _amp_tiles(s_block: int, c: int) -> _Tiles:
    """Cut each tile to the largest divisor of the block's dims that obeys
    the (8, 128) rule; a dim that has none is taken whole.  The blocks of
    one program are decoded one after another, so nb_tile does not enter."""
    sub = _SUBLANE if s_block % _SUBLANE == 0 else s_block

    def rows(n):
        return _legal_tile(s_block, max(sub, n), sub)

    def cols(n):
        return _legal_tile(c, max(_LANE, n), _LANE)

    return _Tiles(sub, rows(_GEN_TILE[0]), cols(_GEN_TILE[1]),
                  rows(_ADJ_TILE[0]), cols(_ADJ_TILE[1]),
                  rows(_FWD_TILE[0]), cols(_FWD_TILE[1]), rows(_RED_ROWS))


def _vregs(rows: int, cols: int) -> int:
    return -(-rows // _SUBLANE) * -(-cols // _LANE)


def _live_vregs(t: _Tiles) -> int:
    """Estimated vregs one step keeps live, the most over the loops: a hash
    tile and its indices; the adjoint's accumulator, A tile and z; the
    forward's accumulators, A tiles and x-hat tile."""
    gen = 2 * _vregs(t.gen_rows, t.gen_cols)
    adj = 2 * _vregs(t.sub, t.adj_cols) + 1
    fwd = 2 * _vregs(t.fwd_rows, t.fwd_cols) + _vregs(t.sub, t.fwd_cols)
    return max(gen, adj, fwd)


def _lanes(v, width: int):
    """A lane-uniform (rows, 128) tile widened or cut to ``width`` lanes."""
    if width <= _LANE:
        return v[:, :width]
    q, rem = divmod(width, _LANE)
    return jnp.concatenate([v] * q + ([v[:, :rem]] if rem else []), axis=1)


def _folded(width: int) -> int:
    """Lanes of ``_lane_fold`` of a width-lane array."""
    return width if width % _LANE else _LANE


def _lane_fold(v):
    """Sum the 128-lane chunks of v: (rows, w) -> (rows, 128) when the
    lanes tile, else v as it is."""
    w = v.shape[1]
    if w % _LANE:
        return v
    out = v[:, :_LANE]
    for l0 in range(_LANE, w, _LANE):
        out = out + v[:, l0:l0 + _LANE]
    return out


def _amp_kernel(scal_ref, y_ref, x_ref, a_scr, yb_scr, zb_scr, xb_scr,
                axp_scr, *, nb_tile, s_block, c, tiles, iters, threshold_mult,
                debias, rademacher):
    t = pl.program_id(0)
    seed = scal_ref[0, 0]
    b0 = scal_ref[0, 1] + jnp.uint32(t * nb_tile)
    (sub, gen_rows, gen_cols, adj_rows, adj_cols, fwd_rows, fwd_cols,
     red_rows) = tiles
    inv_sqrt_s = jnp.float32(1.0 / (s_block ** 0.5))
    f32 = jnp.float32

    def decode(b, carry):
        # ONE A-generation per block, resident in VMEM for the whole decode
        def gen(i, carry):
            r0 = pl.multiple_of(i * gen_rows, gen_rows)
            for c0 in range(0, c, gen_cols):
                a_scr[pl.ds(b, 1), pl.ds(r0, gen_rows), pl.ds(c0, gen_cols)] \
                    = _tile_A(seed, b0 + b.astype(jnp.uint32),
                              r0.astype(jnp.uint32), jnp.uint32(c0), 1,
                              gen_rows, gen_cols, s_block, rademacher)
            return carry

        jax.lax.fori_loop(0, s_block // gen_rows, gen, 0)

        y_col = jnp.swapaxes(y_ref[b], 0, 1)                # (s_block, 1)
        yb_scr[...] = jnp.broadcast_to(y_col, (s_block, _LANE))
        zb_scr[...] = yb_scr[...]
        xb_scr[...] = jnp.zeros((sub, c), f32)

        # the tiled passes unroll into thousands of small ops: they call lax
        # directly, since a jnp operator costs more to trace
        def a_tile(r0, rows, c0, cols):
            return a_scr[b, pl.ds(r0, rows), c0:c0 + cols]

        def adjoint_threshold(thr):
            """x-hat <- eta(x-hat + A^T z; thr), column tile by column tile;
            returns the count of its nonzeros, (1, 1)."""
            nnz = jnp.zeros((1, _folded(adj_cols)), f32)
            for c0 in range(0, c, adj_cols):
                def rows(i, acc):
                    r0 = pl.multiple_of(i * adj_rows, adj_rows)
                    for g in range(0, adj_rows, sub):
                        rg = lax.add(r0, g)
                        z = _lanes(zb_scr[pl.ds(rg, sub), :], adj_cols)
                        acc = lax.add(acc, lax.mul(
                            a_tile(rg, sub, c0, adj_cols), z))
                    return acc

                acc = jax.lax.fori_loop(0, s_block // adj_rows, rows,
                                        jnp.zeros((sub, adj_cols), f32))
                r = xb_scr[0:1, pl.ds(c0, adj_cols)] \
                    + jnp.sum(acc, axis=0, keepdims=True)
                x_new = soft_threshold(r, thr)
                xb_scr[:, pl.ds(c0, adj_cols)] = jnp.broadcast_to(
                    x_new, (sub, adj_cols))
                nnz = nnz + _lane_fold((x_new != 0.0).astype(f32))
            return jnp.sum(nnz, axis=1, keepdims=True)

        def forward_partial(r0):
            """(A x-hat) of rows r0 .. r0 + fwd_rows, its lanes summed down
            to one vreg per sublane group where they tile."""
            acc = [jnp.zeros((sub, fwd_cols), f32)] * (fwd_rows // sub)
            for c0 in range(0, c, fwd_cols):
                x = xb_scr[:, c0:c0 + fwd_cols]
                acc = [lax.add(a, lax.mul(
                    a_tile(lax.add(r0, g), sub, c0, fwd_cols), x))
                       for g, a in zip(range(0, fwd_rows, sub), acc)]
            acc = jnp.concatenate(acc, axis=0) if len(acc) > 1 else acc[0]
            return _lane_fold(acc)

        def forward(step, init):
            """Fold ``step(rows, ax, carry)`` over the rows, ax the column
            (A x-hat)[rows].  The products run first and store each row
            tile's partial sums (pipelined by hand: a trip stores the
            previous tile's while this one multiplies); the lane reductions
            follow, red_rows rows at a time, so that their wait on the
            cross-lane unit is paid once a pass and not once a row tile."""
            def multiply(i, prev):
                r0 = pl.multiple_of(i * fwd_rows, fwd_rows)
                axp_scr[pl.ds(r0 - fwd_rows, fwd_rows), :] = prev
                return forward_partial(r0)

            last = jax.lax.fori_loop(1, s_block // fwd_rows, multiply,
                                     forward_partial(0))
            axp_scr[pl.ds(s_block - fwd_rows, fwd_rows), :] = last

            def reduce(i, carry):
                rows = pl.ds(pl.multiple_of(i * red_rows, red_rows), red_rows)
                return step(rows, jnp.sum(axp_scr[rows, :], axis=1,
                                          keepdims=True), carry)

            return jax.lax.fori_loop(0, s_block // red_rows, reduce, init)

        def col_sum(v):                       # (rows, 128) -> (1, 1)
            return jnp.sum(v, axis=0, keepdims=True)[:, :1]

        zero = jnp.zeros((1, 1), f32)

        def body(_, ssq):
            sigma_hat = jnp.sqrt(ssq) * inv_sqrt_s
            nnz = adjoint_threshold(threshold_mult * sigma_hat)
            frac = nnz / s_block

            def residual(rows, ax, ssq):      # z <- y - A x-hat + Onsager
                z_new = yb_scr[rows, :] - ax + zb_scr[rows, :] * frac
                zb_scr[rows, :] = z_new
                return ssq + col_sum(z_new * z_new)

            return forward(residual, zero)

        jax.lax.fori_loop(0, iters, body,
                          jnp.sum(y_col * y_col, axis=0, keepdims=True))
        x = xb_scr[0:1, :]
        if debias:
            def fit(rows, ax, carry):
                num, den = carry
                return (num + col_sum(ax * yb_scr[rows, :]),
                        den + col_sum(ax * ax))

            num, den = forward(fit, (zero, zero))
            x = x * _debias_factor(num, den)
        x_ref[b] = x
        return carry

    jax.lax.fori_loop(0, nb_tile, decode, 0)


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
    tiles = _amp_tiles(s_block, c)
    # (n, 1, s) layout: the last two block dims span the array, so any
    # nb_tile is a legal block (a single 16 MiB block is the common case)
    y_p = _pad_blocks(yb.astype(jnp.float32), nb_tile)[:, None, :]
    n_pad = y_p.shape[0]
    scal = jnp.stack([jnp.asarray(seed, jnp.uint32),
                      jnp.asarray(id_offset, jnp.uint32)]).reshape(1, 2)
    kern = functools.partial(_amp_kernel, nb_tile=nb_tile, s_block=s_block,
                             c=c, tiles=tiles, iters=iters,
                             threshold_mult=threshold_mult, debias=debias,
                             rademacher=rademacher)
    xb = pl.pallas_call(
        kern,
        grid=(n_pad // nb_tile,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec((nb_tile, 1, s_block), lambda t: (t, 0, 0))],
        out_specs=pl.BlockSpec((nb_tile, 1, c), lambda t: (t, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, 1, c), jnp.float32),
        # the resident A; y and z broadcast across lanes; x-hat down
        # sublanes; the forward's partial sums
        scratch_shapes=[pltpu.VMEM((nb_tile, s_block, c), jnp.float32),
                        pltpu.VMEM((s_block, _LANE), jnp.float32),
                        pltpu.VMEM((s_block, _LANE), jnp.float32),
                        pltpu.VMEM((tiles.sub, c), jnp.float32),
                        pltpu.VMEM((s_block, _folded(tiles.fwd_cols)),
                                   jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=nb_tile * a_block + _AMP_HEADROOM),
        interpret=interpret,
        name="amp_decode_fused",
    )(scal, y_p)
    return xb[:n_blocks, 0]
