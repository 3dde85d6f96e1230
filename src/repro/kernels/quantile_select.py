"""Exact order statistics of non-negative float32 rows, by bisection on bits.

The codec's sampled threshold (core/compression.py) is the linear quantile
of a strided sample of |g|: two adjacent order statistics of each row,
combined by one interpolation.  Sorting the whole sample to read two of
its entries is what the threshold spends its time on.  For non-negative
float32 values, +inf and +NaN included, the int32 bit patterns order as
the values do, so the r-th smallest value (0-based) is the largest bit
pattern ``x`` with ``count(bits < x) <= r``.  A bisection over bits 30..0,
one compare-and-count pass over the row per bit, finds it bit for bit.

Each program holds one row in VMEM as (rows, 128) int32 lanes and counts
into ``unroll`` independent (8, 128) tiles, with one cross-lane sum a pass.
The row is padded up to whole groups of vregs with INT32_MAX, which no
candidate exceeds, so no pad enters a count of the bisection; the NaN test
(bits above +inf) subtracts the pads' known number.  Rows stack along the
grid, and a ``vmap`` adds one more grid axis (Pallas's own batching): one
launch serves every device of the stream.

Validated in interpret mode against ``jnp.quantile``
(tests/test_quantile_select.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ota_project import _LANE, _SUBLANE

_INT32_MAX = 0x7FFFFFFF
_INF_BITS = 0x7F800000
_NAN_BITS = 0x7FC00000
#: independent count tiles a pass keeps, so the adds of a pass do not form
#: one serial chain
_UNROLL = 8


def _fold(x_ref, step, init, unroll: int):
    """``acc = step(acc, v)`` over every (8, 128) vreg ``v`` of the row in
    ``x_ref``, into ``unroll`` accumulators that each start at ``init``."""
    group = _SUBLANE * unroll

    def body(j, accs):
        base = pl.multiple_of(j * group, group)
        return tuple(step(acc, x_ref[pl.ds(base + _SUBLANE * u, _SUBLANE), :])
                     for u, acc in enumerate(accs))

    return lax.fori_loop(0, x_ref.shape[0] // group, body, (init,) * unroll)


def _select_kernel(x_ref, o_ref, *, lo_rank: int, hi_rank: int, n_pad: int,
                   unroll: int):
    def count(pred):
        zero = jnp.zeros((_SUBLANE, _LANE), jnp.int32)
        accs = _fold(x_ref, lambda acc, v: acc + pred(v).astype(jnp.int32),
                     zero, unroll)
        return jnp.sum(functools.reduce(jnp.add, accs))

    def bisect(i, prefix):
        cand = prefix | (1 << (30 - i))
        return jnp.where(count(lambda v: v < cand) <= lo_rank, cand, prefix)

    lo = lax.fori_loop(0, 31, bisect, jnp.int32(0))
    if hi_rank == lo_rank:
        hi = lo
    else:
        # the next order statistic is lo again if more than hi_rank entries
        # are <= lo, else the least entry above lo (no pad undercuts one)
        n_le = count(lambda v: v <= lo)
        big = jnp.full((_SUBLANE, _LANE), _INT32_MAX, jnp.int32)
        mins = _fold(x_ref,
                     lambda acc, v: jnp.minimum(
                         acc, jnp.where(v > lo, v, _INT32_MAX)),
                     big, unroll)
        above = jnp.min(functools.reduce(jnp.minimum, mins))
        hi = jnp.where(n_le > hi_rank, lo, above)
    has_nan = count(lambda v: v > _INF_BITS) > n_pad
    lo = jnp.where(has_nan, _NAN_BITS, lo)
    hi = jnp.where(has_nan, _NAN_BITS, hi)
    lane = lax.broadcasted_iota(jnp.int32, (_SUBLANE, _LANE), 1)
    o_ref[...] = jnp.where(lane == 0, lo, hi)


def quantile_select_pallas(x: jnp.ndarray, lo_rank: int, hi_rank: int,
                           interpret: bool = True):
    """The values at 0-based ranks ``lo_rank`` and ``hi_rank`` (equal or
    adjacent) of each row of ``x``: (R, n) float32, every entry >= 0 (or
    +NaN).  Returns two (R,) float32 arrays, NaN in a row that holds a NaN
    (as ``jnp.quantile`` reads it)."""
    n_rows, n = x.shape
    if not 0 <= lo_rank <= hi_rank <= min(lo_rank + 1, n - 1):
        raise ValueError(f"ranks ({lo_rank}, {hi_rank}) are not adjacent "
                         f"ranks of a row of {n}")
    vregs = -(-n // (_SUBLANE * _LANE))
    unroll = min(vregs, _UNROLL)
    rows = -(-vregs // unroll) * unroll * _SUBLANE
    n_pad = rows * _LANE - n
    bits = lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)
    bits = jnp.pad(bits, ((0, 0), (0, n_pad)), constant_values=_INT32_MAX)
    kern = functools.partial(_select_kernel, lo_rank=lo_rank,
                             hi_rank=hi_rank, n_pad=n_pad, unroll=unroll)
    out = pl.pallas_call(
        kern,
        grid=(n_rows,),
        in_specs=[pl.BlockSpec((pl.Squeezed(), rows, _LANE),
                               lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((pl.Squeezed(), _SUBLANE, _LANE),
                               lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_rows, _SUBLANE, _LANE), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="quantile_select",
    )(bits.reshape(n_rows, rows, _LANE))
    vals = lax.bitcast_convert_type(out[:, 0, :2], jnp.float32)
    return vals[:, 0], vals[:, 1]
