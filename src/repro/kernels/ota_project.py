"""Pallas TPU kernel for the on-the-fly blocked compressive projection.

The paper (§IV) projects each device's sparsified gradient with a shared
pseudo-random matrix ``A``.  At framework scale A cannot live in HBM
(s x d = O(1e20) entries for a 100B model), so this kernel generates each
VMEM tile of A from a counter-based hash (see kernels/ref.py) *inside* the
matmul kernel: HBM traffic is O(d + s) and A never exists.

TPU adaptation notes (docs/DESIGN.md §4): each grid program batches
``nb_tile`` blocks, generates their A tile once from integer hashes on the
VPU, and contracts it with the x tile of every device in the stack it is
given (the devices share A: the seed is the projector's), one batched f32
``dot_general`` per device.  At these shapes Mosaic lowers each product to
VPU multiplies and cross-lane sums that fill the hash's free slots, so
the program's time is its generation of A (PERF.md §6).
Rademacher entries (one hash + sign) instead of Box-Muller Gaussians.
Tiles follow the TPU block rule (8 blocks per program, lane tiles of 128)
and the contracted dim is split over the grid's last axis, summed into
the resident output block, so one A tile stays a few MiB at the published
c = 4096, s_block = 1024.  The seed arrives through SMEM as a *traced*
uint32 scalar, so the shard-folded seeds of the fully-sharded slice driver
(core/distributed.py) lower through the same kernel.  The kernel is the
forward projection only: the adjoint that AMP needs runs inside the fused
decode (kernels/amp_fused.py).

Validated in interpret mode against kernels/ref.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import _GOLDEN, _M1, _M2

#: VMEM budget for one program's generated A tile (bytes).  The hash
#: temporaries of the tile live in VMEM beside it, so the tile stays well
#: under the 16 MiB scoped-VMEM default of a v5e core.
VMEM_TILE_BYTES = 2 << 20

#: VMEM budget for the x and y blocks, double-buffered, of the devices one
#: forward program serves beside its A tile: 25 devices at c = 4096,
#: s_block = 1024; a larger stack splits into groups.
VMEM_STACK_BYTES = 1 << 20

#: TPU block rule: the last two dims of a block are multiples of
#: (_SUBLANE, _LANE) or span the whole array dim.
_SUBLANE, _LANE = 8, 128


# ---------------------------------------------------------------------------
# in-kernel hash (identical math to ref.splitmix32 / ref.hash3)
# ---------------------------------------------------------------------------


def _splitmix32(x):
    x = x + _GOLDEN
    x = x ^ (x >> 16)
    x = x * _M1
    x = x ^ (x >> 15)
    x = x * _M2
    x = x ^ (x >> 15)
    return x


def _u32_to_f32(h):
    """``h.astype(float32)`` without the unsigned cast Mosaic lacks: both
    16-bit halves convert exactly, and the one rounding of their exact sum
    is the correctly rounded conversion of ``h``."""
    hi = (h >> 16).astype(jnp.int32).astype(jnp.float32)
    lo = (h & jnp.uint32(0xFFFF)).astype(jnp.int32).astype(jnp.float32)
    return hi * jnp.float32(65536.0) + lo


def _tile_A(seed, block0, row0, col0, nb_tile: int, r_tile: int, c_tile: int,
            s_block: int, rademacher: bool):
    """Generate the (nb_tile, r_tile, c_tile) stacked-A tile whose first
    block is ``block0``, starting at entry (row0, col0) of each block.

    ``seed``/``block0``/``row0``/``col0`` may be traced uint32 scalars."""
    shape = (nb_tile, r_tile, c_tile)
    blocks = block0 + jax.lax.broadcasted_iota(jnp.uint32, shape, 0)
    rows = row0 + jax.lax.broadcasted_iota(jnp.uint32, shape, 1)
    cols = col0 + jax.lax.broadcasted_iota(jnp.uint32, shape, 2)
    h = _splitmix32(jnp.uint32(seed) ^ blocks)
    h = _splitmix32(h ^ rows)
    h = _splitmix32(h ^ cols)
    scale = jnp.float32(1.0 / (s_block ** 0.5))
    if rademacher:
        # sign = 1 - 2 * (h >> 31), as a select
        return jnp.where(h < jnp.uint32(1 << 31), scale, -scale)
    h2 = _splitmix32(h ^ jnp.uint32(0xDEADBEEF))
    u1 = (_u32_to_f32(h) + 0.5) * jnp.float32(2.0 ** -32)
    u2 = (_u32_to_f32(h2) + 0.5) * jnp.float32(2.0 ** -32)
    z = jnp.sqrt(-2.0 * jnp.log(u1)) * jnp.cos(2.0 * jnp.pi * u2)
    return z * scale


def _bdot(a, b, contract_a: int, contract_b: int):
    """Batched (leading-dim) contraction on the MXU at full f32 precision
    (the oracle's f32 matvec, not a one-pass bf16 product)."""
    return jax.lax.dot_general(
        a, b, (((contract_a,), (contract_b,)), ((0,), (0,))),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def _legal_tile(n: int, cap: int, unit: int) -> int:
    """Largest multiple of ``unit`` that divides n and is at most cap; the
    whole dim when n <= cap or no such divisor exists."""
    if n <= cap:
        return n
    t = cap - cap % unit
    while t >= unit and n % t:
        t -= unit
    return t if t >= unit else n


def _block_rows(n_blocks: int, nb_tile: int | None) -> int:
    """Blocks per program: the whole stack when it fits one program, else a
    multiple of 8 (the stack is padded to a multiple of it)."""
    nb_tile = _SUBLANE if nb_tile is None else nb_tile
    if n_blocks <= nb_tile:
        return n_blocks
    return max(_SUBLANE, nb_tile - nb_tile % _SUBLANE)


def _accumulate(o_ref, part, k):
    """``o_ref`` = sum of ``part`` over the grid's last axis ``k``; the first
    step stores, so a one-step axis is bitwise the un-tiled result."""
    @pl.when(k == 0)
    def _():
        o_ref[...] = part

    @pl.when(k > 0)
    def _():
        o_ref[...] += part


def _pad_blocks(x: jnp.ndarray, nb_tile: int) -> jnp.ndarray:
    pad = (-x.shape[0]) % nb_tile
    return jnp.pad(x, ((0, pad), (0, 0))) if pad else x


def _seed_arr(seed) -> jnp.ndarray:
    """[[seed]] as a uint32 SMEM operand; accepts python ints and traced
    scalars (e.g. the shard-folded seeds of the slice driver).  2-D so that
    a vmapped (m, 1, 1) stack still spans its last two block dims."""
    return jnp.asarray(seed, jnp.uint32).reshape(1, 1)


# ---------------------------------------------------------------------------
# forward projection: y[d, b] = A_b @ x[d, b] for a stack of devices d,
# nb_tile blocks per program, each A tile generated once for all devices
# ---------------------------------------------------------------------------


def _fwd_kernel(seed_ref, x_ref, y_ref, *, lead, m_tile, nb_tile, s_tile,
                c_tile, s_block, rademacher):
    g = pl.program_id(lead)              # block-chunk index
    i = pl.program_id(lead + 1)          # row-tile index inside s_block
    k = pl.program_id(lead + 2)          # col-tile index inside c (summed)
    A = _tile_A(seed_ref[0, 0], jnp.uint32(g * nb_tile),
                jnp.uint32(i * s_tile), jnp.uint32(k * c_tile), nb_tile,
                s_tile, c_tile, s_block, rademacher)
    for d in range(m_tile):              # the one A tile, every device's x
        _accumulate(y_ref.at[d], _bdot(A, x_ref[d], 2, 1), k)


def ota_project_pallas(x: jnp.ndarray, seed, s_block: int,
                       rademacher: bool = True, nb_tile: int | None = None,
                       interpret: bool = True) -> jnp.ndarray:
    """x: (n_blocks, c) -> y: (n_blocks, s_block), or a stack of devices
    (m, n_blocks, c) -> (m, n_blocks, s_block), all float32.

    A stack shares A: each program generates its tile once and contracts
    it with every device's x tile, each product the one a lone device's
    call makes, so every device's y is bitwise its own call's.  Devices
    whose x and y blocks outgrow ``VMEM_STACK_BYTES`` split into groups
    along one more (leading) grid axis, one A tile per group."""
    if x.ndim == 2:
        return ota_project_pallas(x[None], seed, s_block, rademacher,
                                  nb_tile, interpret)[0]
    m, n_blocks, c = x.shape
    nb_tile = _block_rows(n_blocks, nb_tile)
    s_tile = _legal_tile(s_block, _LANE, _LANE)
    c_tile = _legal_tile(c, max(_LANE, VMEM_TILE_BYTES // 4
                                // (nb_tile * s_tile)), _LANE)
    per_device = 2 * 4 * nb_tile * (c_tile + s_tile)   # double-buffered
    groups = -(-m // max(1, VMEM_STACK_BYTES // per_device))
    m_tile = -(-m // groups)
    pad = ((0, groups * m_tile - m), (0, (-n_blocks) % nb_tile), (0, 0))
    x_p = x.astype(jnp.float32)
    if any(p for _, p in pad):
        x_p = jnp.pad(x_p, pad)
    grid = ((groups,) if groups > 1 else ()) + (
        x_p.shape[1] // nb_tile, s_block // s_tile, c // c_tile)

    def x_map(*ids):                     # ([group,] chunk, row, col)
        return (*(ids[:-3] or (0,)), ids[-3], ids[-1])

    def y_map(*ids):
        return (*(ids[:-3] or (0,)), ids[-3], ids[-2])

    kern = functools.partial(_fwd_kernel, lead=len(grid) - 3, m_tile=m_tile,
                             nb_tile=nb_tile, s_tile=s_tile, c_tile=c_tile,
                             s_block=s_block, rademacher=rademacher)
    y = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec((m_tile, nb_tile, c_tile), x_map)],
        out_specs=pl.BlockSpec((m_tile, nb_tile, s_tile), y_map),
        out_shape=jax.ShapeDtypeStruct(x_p.shape[:2] + (s_block,),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            ("parallel",) * (len(grid) - 1) + ("arbitrary",))),
        interpret=interpret,
        name="ota_project",
    )(_seed_arr(seed), x_p)
    return y[:m, :n_blocks]
