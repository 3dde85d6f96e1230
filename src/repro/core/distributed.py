"""Fully-sharded aggregation driver (shard_map manual over data x model).

Phase 2 of the distributed train step (see train/trainer.py): every device
owns a (d_pad / n_shards) slice of its data-replica's gradient.  This module
provides the *generic* slice driver :func:`sharded_round` — it pre-averages
edge-site groups, runs the scheme's ``encode_slice``, superposes the frame
over the device axes (the MAC psum), injects AWGN for analog schemes, and
hands the observation to ``decode_slice``.  All scheme-specific pipeline
logic (EF -> threshold sparsify -> blocked projection -> power scaling ->
per-block AMP for A-DSGD) lives on the scheme classes in
:mod:`repro.core.schemes`; this driver never branches on a scheme name.

Cross-shard coordination inside the A-DSGD hooks stays tiny and explicit:
the top-k threshold gathers 65k |g| samples, the frame energy / mean / scale
slots are scalar psums.  Per-shard measurement matrices derive from a
shard-folded seed (the PS uses the same fold — consistency by construction).
No d-sized tensor is ever replicated, gathered, or scanned across shards.

The helpers :func:`proj_forward` / :func:`amp_blocked` are the traced-seed
blocked projection + AMP realisation: a chunked jnp scan by default, or the
chunk-batched projection kernels (kernels/ota_project.py) and the fused
single-launch AMP kernel (kernels/amp_fused.py) when the scheme passes
``use_kernel=True`` — both kernels take the traced shard-folded seed
through an SMEM operand, so PS and devices stay consistent by construction.
"""
from __future__ import annotations

from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core import channel
from repro.kernels import ref
from repro.tracing import stage


# ---------------------------------------------------------------------------
# traced-seed blocked projection + AMP (the jnp/XLA realisation)
# ---------------------------------------------------------------------------


def proj_forward(xb: jnp.ndarray, seed_u32, s_block: int,
                 chunk_blocks: int, use_kernel: bool = False) -> jnp.ndarray:
    """xb (n_blocks, c) -> (n_blocks, s_block); A generated per chunk.

    ``use_kernel=True`` lowers through the chunk-batched Pallas projection
    kernel (kernels/ota_project.py) — the traced shard-folded seed passes
    straight through its SMEM operand.
    """
    n_blocks, c = xb.shape
    if use_kernel:
        from repro.kernels import ops
        return ops.ota_project(xb, seed=seed_u32, s_block=s_block,
                               rademacher=True, use_kernel=True)
    ni = min(chunk_blocks, n_blocks)
    pad = (-n_blocks) % ni
    xb_p = jnp.pad(xb, ((0, pad), (0, 0)))
    n_outer = (n_blocks + pad) // ni
    xs = xb_p.reshape(n_outer, ni, c)
    ids = jnp.arange(n_outer * ni, dtype=jnp.uint32).reshape(n_outer, ni)

    def body(_, inp):
        ids_c, x_c = inp
        A = jax.vmap(lambda b: ref.block_matrix_ref(seed_u32, b, s_block,
                                                    c, True))(ids_c)
        return None, jnp.einsum("isc,ic->is", A, x_c)

    _, ys = jax.lax.scan(body, None, (ids, xs))
    return ys.reshape(-1, s_block)[:n_blocks]


def amp_blocked(yb: jnp.ndarray, seed_u32, c: int, iters: int,
                chunk_blocks: int, threshold_mult: float = 1.3,
                debias: bool = True, id_offset=0,
                use_kernel: bool = False) -> jnp.ndarray:
    """Per-block AMP with traced seed; A generated ONCE per block per decode.

    Thin re-export of :func:`repro.core.amp.amp_blocked_core` (the single
    chunked implementation: jnp scan, or the fused single-launch Pallas
    kernel when ``use_kernel=True``).

    id_offset (traced ok): global index of this slice's first block — lets a
    device decode a sub-range of blocks with the encoder's global block ids.
    """
    from repro.core.amp import amp_blocked_core
    return amp_blocked_core(yb, seed_u32, c, iters, chunk_blocks,
                            threshold_mult, debias, rademacher=True,
                            id_offset=id_offset, use_kernel=use_kernel)


def psum_all(x, axes: Sequence[str]):
    for ax in axes:
        x = jax.lax.psum(x, ax)
    return x


# ---------------------------------------------------------------------------
# the generic sharded-slice driver
# ---------------------------------------------------------------------------


def sharded_round(scheme, g_slice: jnp.ndarray, delta_slice: jnp.ndarray,
                  step, key, ctx) -> Tuple[jnp.ndarray, jnp.ndarray, Dict]:
    """One aggregation round on gradient slices for any scheme with slice
    hooks (manual over ``ctx.device_axes`` + ``ctx.shard_axes``).

    g_slice, delta_slice: (d_local,) — this device-replica's shard of the
    ctx.d_pad-dim vector; d_local = d_pad / n_shards.

    The scheme's ``encode_slice`` returns a frame dict with a ``"body"``
    array and optional ``"slots"`` scalars; this driver psums both over the
    device axes (the MAC superposition — the body optionally in
    ``ctx.frame_dtype``, e.g. bf16: its quantisation noise is far below the
    channel AWGN sigma^2), adds AWGN once per channel slice when the scheme
    is analog, and calls ``decode_slice`` on the observation.
    """
    from repro.core.schemes import (
        channel_amp, round_sigma2, sharded_channel_draw, shard_info,
    )
    if ctx.key_salt:
        key = jax.random.fold_in(key, ctx.key_salt)
    g_slice = g_slice.astype(jnp.float32)
    group_size = ctx.group_size
    if ctx.groups is not None:
        g_slice = jax.lax.psum(
            g_slice, ctx.device_axes[-1],
            axis_index_groups=[list(g) for g in ctx.groups]) / group_size

    with stage("encode"):
        if scheme.analog:
            # per-device channel draw (same h on every shard of a
            # device-replica: the full-M realisation is evaluated from the
            # shared round key and indexed by the device row, never by the
            # shard index)
            draw = sharded_channel_draw(scheme, key, step, ctx)
            ctx = ctx.with_p_factor(draw.p_factor)
        frame, new_delta, metrics = scheme.encode_slice(
            g_slice, delta_slice, step, key, ctx)
        if scheme.analog:
            amp = channel_amp(draw)
            frame = {k: (v * amp.astype(v.dtype) if v is not None else None)
                     for k, v in frame.items()}
            new_delta = jnp.where(draw.active, new_delta,
                                  scheme.silent_state(g_slice, delta_slice,
                                                      new_delta))

        # --- the MAC: superposition over device axes + AWGN -----------------
        body = frame["body"]
        if ctx.frame_dtype is not None and scheme.analog:
            # the narrow-psum optimisation only applies to analog frames,
            # whose quantisation noise hides under the channel AWGN;
            # non-analog aggregation (ideal benchmark, digital) stays exact
            # in f32
            body = body.astype(ctx.frame_dtype)
        y_body = psum_all(body, ctx.device_axes).astype(jnp.float32)
        slots = frame.get("slots")
        y_slots = (psum_all(slots, ctx.device_axes)
                   if slots is not None else None)
        if group_size > 1:
            y_body = y_body / group_size
            if y_slots is not None:
                y_slots = y_slots / group_size
        if scheme.analog:
            sigma2 = round_sigma2(scheme, draw)
            shard_idx, n_shards = shard_info(ctx.shard_axes)
            body_key = jax.random.fold_in(key, shard_idx.astype(jnp.int32))
            n_sites = (len(ctx.groups)
                       if ctx.site_mac and ctx.groups is not None else 1)
            if n_sites > 1:
                # hierarchical MAC: each edge-site group's partial sum
                # carries its own receiver AWGN per channel slice (summed by
                # the PS combine), mirroring round_sharded's site path
                y_body = y_body + channel.site_awgn(
                    body_key, y_body.shape, sigma2, n_sites,
                    site_noise_scale=ctx.site_noise_scale)
                if y_slots is not None:
                    slot_key = jax.random.fold_in(key, n_shards + 7)
                    y_slots = y_slots + channel.site_awgn(
                        slot_key, y_slots.shape, sigma2, n_sites,
                        site_noise_scale=ctx.site_noise_scale)
            else:
                y_body = y_body + channel.awgn(body_key, y_body.shape, sigma2)
                if y_slots is not None:
                    slot_key = jax.random.fold_in(key, n_shards + 7)
                    y_slots = y_slots + channel.awgn(slot_key, y_slots.shape,
                                                     sigma2)

    with stage("decode"):
        ghat_slice = scheme.decode_slice({"body": y_body, "slots": y_slots},
                                         step, ctx)
    return ghat_slice, new_delta, metrics
