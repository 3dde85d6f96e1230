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

The A-DSGD slice hooks project and decode through the flat path's own
blocked codec, :func:`repro.core.projection.blocked_projection` and
:func:`repro.core.amp.amp_blocked_core`, with the traced shard-folded seed:
jnp scans over chunks of ``MACContext.chunk_blocks`` blocks, or the Pallas
projection and fused AMP kernels when ``OTAConfig.use_kernel`` is set.
Both kernels take the seed through an SMEM operand, so PS and devices stay
consistent by construction.
"""
from __future__ import annotations

from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core import channel
from repro.tracing import stage


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
