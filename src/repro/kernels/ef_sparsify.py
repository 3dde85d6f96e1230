"""Fused error-feedback + threshold sparsification Pallas kernel.

One HBM pass computes, per tile:
    g_ec  = g + delta
    keep  = |g_ec| >= tau
    g_sp  = keep ? g_ec : 0
    delta'= g_ec - g_sp
instead of the 3-pass jnp version (add, compare/select, subtract), which is
memory-bound at d ~ 1e9+.  tau is a scalar SMEM operand.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ota_project import _LANE, _SUBLANE


def _kernel(tau_ref, g_ref, d_ref, sp_ref, nd_ref):
    g_ec = g_ref[...] + d_ref[...]
    tau = tau_ref[0, 0]
    keep = jnp.abs(g_ec) >= tau
    sp = jnp.where(keep, g_ec, 0.0)
    sp_ref[...] = sp
    nd_ref[...] = g_ec - sp


def ef_sparsify_pallas(g: jnp.ndarray, delta: jnp.ndarray, tau: jnp.ndarray,
                       tile: int = 1 << 16, interpret: bool | None = None):
    """g, delta: (n,) float32; tau: scalar. Returns (g_sp, new_delta).

    The vectors are laid out as rows of 128 lanes and ``tile`` entries
    (rounded up to 8 whole rows) go to each program, so every block obeys
    the TPU (8, 128) rule — also under ``vmap`` over devices, which adds a
    squeezed leading block dim.  ``n`` is padded up to whole tiles and the
    outputs sliced back — the tile never shrinks, so a prime-length
    gradient launches ceil(n/tile) programs, not n.  The pad lanes are pure
    zeros (0 + 0 compared against tau >= 0 stays 0 in both outputs), so
    padding is value-exact for the real lanes.  ``interpret=None`` resolves
    lazily per call to the same backend detection as
    :mod:`repro.kernels.ops` (which imports this module, hence the local
    check).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    (n,) = g.shape
    rows = -(-n // _LANE)
    tile_rows = -(-tile // (_LANE * _SUBLANE)) * _SUBLANE
    if tile_rows >= rows:
        tile_rows = rows                   # one program spans the array
    n_rows = -(-rows // tile_rows) * tile_rows
    pad = n_rows * _LANE - n
    # (1, 1): a vmapped (m, 1, 1) stack still spans its last two dims
    tau_arr = jnp.asarray(tau, jnp.float32).reshape(1, 1)
    g_p = jnp.pad(g.astype(jnp.float32), (0, pad)).reshape(n_rows, _LANE)
    d_p = jnp.pad(delta.astype(jnp.float32), (0, pad)).reshape(n_rows, _LANE)
    spec = pl.BlockSpec((tile_rows, _LANE), lambda i: (i, 0))
    out_shape = (jax.ShapeDtypeStruct((n_rows, _LANE), jnp.float32),
                 jax.ShapeDtypeStruct((n_rows, _LANE), jnp.float32))
    sp, nd = pl.pallas_call(
        _kernel,
        grid=(n_rows // tile_rows,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), spec, spec],
        out_specs=(spec, spec),
        out_shape=out_shape,
        interpret=interpret,
        name="ef_sparsify",
    )(tau_arr, g_p, d_p)
    return sp.reshape(-1)[:n], nd.reshape(-1)[:n]
