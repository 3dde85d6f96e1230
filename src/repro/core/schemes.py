"""Pluggable aggregation schemes: one encode/decode contract for every MAC.

The paper contributes a *family* of gradient aggregation schemes over a
shared wireless multiple-access channel (ideal, A-DSGD, D-DSGD, SignSGD,
QSGD), and the follow-up work adds channel variants (Rayleigh fading with
truncated inversion).  This module makes the family extensible: each scheme
is a class implementing the :class:`Scheme` contract

    init_state(d)              -- per-device error accumulator Delta_m(0)
    encode(g, state, step, key, ctx)   -- device-side compression + frame
    decode(y, step, ctx)       -- PS-side reconstruction from the MAC output
    channel_dim(d)             -- channel uses consumed per round

registered under a name with :func:`register_scheme` and resolved from an
``OTAConfig`` via :func:`get_scheme`.  Schemes that support the fully-sharded
slice driver additionally implement ``encode_slice`` / ``decode_slice``
(see :mod:`repro.core.distributed`).

Three generic drivers run *any* registered scheme without per-scheme
branches (scheme behaviour is expressed through the hooks, never through
name dispatch):

  * :func:`round_simulated` -- M devices on one host; the MAC is a sum over
    the leading axis (paper-scale benchmarks).
  * :func:`round_sharded`   -- inside a shard_map; the MAC is ``lax.psum``
    over the manual mesh axes (the TPU ICI plays the superposing channel).
  * :func:`repro.core.distributed.sharded_round` -- fully-sharded slices;
    every device owns ``d_pad / n_shards`` entries, nothing d-sized is ever
    replicated.

Topology facts (device axes, shard axes, group structure, per-device fading
power factor, perf knobs) travel in an explicit :class:`MACContext` so the
same scheme object serves all three drivers.  The *channel* is its own
pluggable axis (:mod:`repro.core.fading`): per round the drivers ask the
scheme for a :class:`ChannelDraw` — received-power factor, transmit set,
frame gain, noise scale — so fading processes (static / iid / gauss_markov)
and CSI models (perfect / noisy estimate / none) compose with any analog
scheme; see ``ADSGDFadingScheme`` / ``ADSGDCSIErrScheme`` /
``ADSGDBlindScheme`` and docs/DESIGN.md §8.

Registering a new scheme takes ~10 lines::

    @register_scheme("a_dsgd_fading")
    class ADSGDFadingScheme(ADSGDScheme):
        def device_factors(self, key, m):
            h = channel.rayleigh_gains(key, m)
            return channel.truncated_inversion_power(
                h, self.cfg.fading_threshold)

        def silent_state(self, g, state, new_state):
            return (g + state).astype(new_state.dtype)
"""
from __future__ import annotations

import copy
import dataclasses
from dataclasses import dataclass
from functools import cached_property
from typing import Any, Dict, NamedTuple, Optional, Sequence, Tuple, Type

import jax
import jax.numpy as jnp

from repro.configs.base import OTAConfig
from repro.core import channel, compression, fading, geometry, power
from repro.core.amp import amp_blocked_core, amp_decode
from repro.core.projection import (
    DenseProjector, blocked_projection, make_projector,
)
from repro.kernels import ops, ref
from repro.robust import faults
from repro.tracing import stage


# ---------------------------------------------------------------------------
# MAC context: where a round runs (axes, groups, fading, perf knobs)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class MACContext:
    """Topology and channel context threaded through encode/decode.

    One context describes one placement of the MAC: which mesh axes act as
    OTA devices, which shard the d-vector, how devices group into edge
    sites, and the per-device received-power factor (1.0 on the AWGN MAC;
    ``h_m^2`` under truncated-inversion fading, 0 in a deep fade).
    """
    m: int = 1                                   # effective OTA device count
    device_axes: Tuple[str, ...] = ()            # manual axes = MAC users
    shard_axes: Tuple[str, ...] = ()             # manual axes sharding d
    groups: Optional[Tuple[Tuple[int, ...], ...]] = None   # edge-site groups
    fading: str = "none"                         # descriptive channel model
    csi: str = "perfect"                         # descriptive CSI model
    p_factor: Any = 1.0                          # received-power scale (traced)
    # slice-driver geometry / perf knobs (defaults = paper-faithful)
    d_pad: int = 0                               # global padded dimension
    p_scale: float = 1.0                         # power share of this frame
    key_salt: int = 0                            # decorrelates sub-frames
    sample_per_shard: int = 4096                 # threshold sample budget
    chunk_blocks: int = 8                        # A-matrix working set
    frame_dtype: Any = None                      # psum analog bodies in bf16
    shard_decode: bool = False                   # split PS AMP across devices
    # hierarchical MAC: each edge-site group receives its own AWGN (the
    # partial OTA sums combine over the backhaul; repro.population.hierarchy)
    site_mac: bool = False
    site_noise_scale: Any = 1.0                  # per-site variance scale

    @property
    def group_size(self) -> int:
        return len(self.groups[0]) if self.groups else 1

    def with_p_factor(self, p_factor) -> "MACContext":
        return dataclasses.replace(self, p_factor=p_factor)


def shard_info(shard_axes: Sequence[str]):
    """(shard_idx, n_shards) of the calling device along the manual axes."""
    n_shards = 1
    shard_idx = jnp.zeros((), jnp.uint32)
    for ax in shard_axes:
        sz = jax.lax.axis_size(ax)
        shard_idx = shard_idx * sz + jax.lax.axis_index(ax).astype(jnp.uint32)
        n_shards *= sz
    return shard_idx, n_shards


class ChannelDraw(NamedTuple):
    """One round's channel realisation, as seen by a driver.

    ``p_factor``/``active`` are the pre-existing truncated-inversion pair
    (received-power scale inside ``encode``; transmit-set membership).  The
    two optional fields carry what imperfect-CSI channels add on top:
    ``gain`` is a per-device amplitude applied to the *encoded frame* (the
    misalignment ``Re(h/h_hat)`` under estimated inversion, the combiner
    gain under blind transmission — ``None`` means exactly 1 and preserves
    the legacy bitwise path), and ``noise_scale`` is a scalar multiplier on
    the AWGN variance (the blind PS combiner's noise enhancement; ``None``
    means exactly 1).
    """
    p_factor: jnp.ndarray                        # (m,) received-power factor
    active: jnp.ndarray                          # (m,) bool transmit set
    gain: Optional[jnp.ndarray] = None           # (m,) frame amplitude
    noise_scale: Optional[jnp.ndarray] = None    # scalar sigma^2 multiplier


# ---------------------------------------------------------------------------
# the Scheme contract + registry
# ---------------------------------------------------------------------------

SCHEME_REGISTRY: Dict[str, Type["Scheme"]] = {}

#: the five schemes evaluated in the paper's §VI figures
PAPER_SCHEMES = ("ideal", "a_dsgd", "d_dsgd", "signsgd", "qsgd")


def register_scheme(name: str):
    """Class decorator: register a Scheme subclass under ``name``."""
    def deco(cls: Type["Scheme"]) -> Type["Scheme"]:
        cls.name = name
        SCHEME_REGISTRY[name] = cls
        return cls
    return deco


def get_scheme(cfg: OTAConfig, d: int, m: int) -> "Scheme":
    """Resolve ``cfg.scheme`` through the registry and build the scheme.

    Back-compat promotion: ``scheme="a_dsgd"`` with ``fading="rayleigh"``
    (the pre-registry spelling) resolves to the ``a_dsgd_fading`` scheme.
    """
    name = cfg.scheme
    if name == "a_dsgd" and cfg.fading == "rayleigh":
        name = "a_dsgd_fading"
    try:
        cls = SCHEME_REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown scheme {name!r}; registered: "
            f"{', '.join(sorted(SCHEME_REGISTRY))}") from None
    return cls(cfg, d, m)


class Scheme:
    """Base class: common state/schedule plumbing + the generic hooks.

    Subclasses override :meth:`encode` / :meth:`decode` (and optionally the
    slice hooks and the fading hooks).  ``analog`` schemes superpose real
    frames on the Gaussian MAC (AWGN added by the driver); non-analog
    schemes (ideal benchmark, digital baselines) aggregate noiselessly —
    their channel impairment is the bit budget baked into the q schedule.
    """

    name: str = "?"
    analog: bool = False
    #: descriptive CSI model of the scheme's channel (MACContext.csi)
    csi: str = "perfect"

    def __init__(self, cfg: OTAConfig, d: int, m: int):
        self.cfg = cfg
        self.d = d
        self.m = m
        self._p_np = power.schedule_array(cfg.total_steps, cfg.p_avg,
                                          cfg.power_schedule)
        self.p_sched = jnp.asarray(self._p_np, jnp.float32)
        # channel-model scalars: these enter the round as data (compares /
        # multiplies), so the sweep engine can swap them per grid point via
        # with_overrides and vmap whole fading grids on one trace
        self.fading_threshold = jnp.float32(cfg.fading_threshold)
        self.csi_err_var = jnp.float32(cfg.csi_err_var)
        self.fading_rho = jnp.float32(cfg.fading_rho)
        #: run-level key anchoring the static / gauss_markov gain streams
        self.fading_key = fading.fading_base_key(cfg.seed)
        # geometry / scheduling scalars: traced like the channel scalars
        # above, so radius / path-loss / subband grids vmap on one program
        # (SCALAR_VMAP_AXES in repro.experiments.sweep; docs/DESIGN.md §12)
        self.cell_radius = jnp.float32(cfg.cell_radius)
        self.path_loss_exp = jnp.float32(cfg.path_loss_exp)
        self.n_subbands = jnp.float32(cfg.n_subbands)
        #: run-level key anchoring the device placement (geometry axis)
        self.geometry_key = geometry.geometry_base_key(cfg.seed)
        # robustness scalars: like the channel scalars above, these enter
        # the round as data, so fault/defence grids vmap on one program
        # (ROBUST_VMAP_AXES in repro.experiments.sweep); the *kinds*
        # (byz_attack / fault_kind / aggregator / clip_power) are static
        self.byzantine_frac = jnp.float32(cfg.byzantine_frac)
        self.byz_scale = jnp.float32(cfg.byz_scale)
        self.fault_rate = jnp.float32(cfg.fault_rate)
        self.erasure_prob = jnp.float32(cfg.erasure_prob)
        self.trim_frac = jnp.float32(cfg.trim_frac)
        self.norm_cap = jnp.float32(cfg.norm_cap)
        self.power_cap = jnp.float32(cfg.power_cap)
        #: run-level key anchoring the persistent Byzantine membership
        self.fault_key = faults.fault_base_key(cfg.seed)

    # ------------------------------------------------------------- state
    def init_state(self, d: Optional[int] = None) -> jnp.ndarray:
        """Per-device error accumulator Delta_m(0) = 0 (paper Alg. 1)."""
        return jnp.zeros((self.d if d is None else d,),
                         jnp.dtype(self.cfg.state_dtype))

    def channel_dim(self, d: Optional[int] = None) -> int:
        """Channel uses consumed per round for a d-dim gradient."""
        raise NotImplementedError

    def with_overrides(self, **attrs) -> "Scheme":
        """Shallow copy with attributes replaced — the sweep-engine hook.

        ``repro.experiments`` vmaps whole sweep grids through one trace by
        swapping the *schedule arrays* (``p_sched``, and ``q_sched`` for the
        digital schemes) for batched tracers per grid point; everything
        shape-defining (projector, k, q_max) stays on the copy untouched.
        Call inside the traced function so the tracers bind per trace.
        """
        new = copy.copy(self)
        for name, value in attrs.items():
            if not hasattr(new, name):
                raise AttributeError(
                    f"scheme {self.name!r} has no attribute {name!r} to "
                    "override")
            setattr(new, name, value)
        return new

    def p_t(self, step, p_factor=1.0) -> jnp.ndarray:
        """P_t for this step, scaled by the device's received-power factor."""
        p = self.p_sched[jnp.minimum(step, self.p_sched.shape[0] - 1)]
        return p * jnp.asarray(p_factor, jnp.float32)

    # ----------------------------------------------------- fading hooks
    @cached_property
    def fading_spec(self) -> fading.FadingSpec:
        """Static channel-model description (process / window / antennas),
        tagged with this scheme's CSI model."""
        return dataclasses.replace(fading.spec_from_cfg(self.cfg),
                                   csi=self.csi)

    def gains(self, key: jnp.ndarray, step, m: int):
        """Complex gains (re, im) for this round under cfg.fading_process —
        pure in (key, step), so it evaluates identically inside a compiled
        scan, in the looped reference, and under vmap."""
        return fading.process_gains(self.fading_spec, self.fading_key, key,
                                    step, m, rho=self.fading_rho)

    def device_factors(self, key: jnp.ndarray, m: int):
        """(received-power factor, participation mask) per device."""
        return jnp.ones((m,)), jnp.ones((m,), bool)

    # --------------------------------------------------- geometry hooks
    @property
    def geometry_on(self) -> bool:
        """Static gate for the geometry composition: with ``"none"`` no
        geometry op enters the trace (pre-geometry goldens stay bitwise)."""
        return self.cfg.geometry != "none"

    @cached_property
    def geometry_spec(self) -> geometry.GeometrySpec:
        """Static cell-geometry description (placement model / antennas)."""
        return geometry.spec_from_cfg(self.cfg)

    def geometry_gains(self, m: int) -> jnp.ndarray:
        """(m,) run-constant large-scale gains of the device placement —
        pure in the run-level ``geometry_key``; ``cell_radius`` and
        ``path_loss_exp`` are the traced scheme attributes, so
        ``with_overrides`` vmaps whole radius / path-loss grids."""
        return geometry.large_scale_gains(
            self.geometry_key, m, self.cell_radius, self.path_loss_exp,
            self.geometry_spec)

    def small_scale_draw(self, key: jnp.ndarray, step, m: int,
                         mask=None) -> ChannelDraw:
        """The small-scale (fading/CSI) part of the round's realisation.

        The base implementation wraps the legacy :meth:`device_factors`
        pair; channel-aware schemes override *this* hook to add fading,
        CSI error or PS-side combining — :meth:`channel_draw` then
        composes the geometry layer on top, so every scheme inherits the
        geometry axis without touching it.
        """
        p_factor, active = self.device_factors(key, m)
        return ChannelDraw(p_factor, active)

    def channel_draw(self, key: jnp.ndarray, step, m: int,
                     mask=None) -> ChannelDraw:
        """One round's channel realisation (the driver-facing hook).

        Composes the scheme's :meth:`small_scale_draw` with the run-
        constant large-scale geometry gains (``p_factor *= g_m``, the
        standard large-scale/small-scale factorisation) when the static
        ``cfg.geometry`` gate is on; with geometry off this *is* the
        small-scale draw — no extra op, bitwise the pre-geometry path.
        ``key`` is the fading-salted round key (``fold_in(round_key,
        2)``); ``step`` feeds the time-correlated processes.  ``mask``
        (optional, (m,) bool) marks which of the m padded devices
        physically exist — per-device draws can ignore it (masked frames
        are zeroed by the driver anyway), but draws that couple devices
        (the blind PS combiner) must exclude phantom rows.
        """
        draw = self.small_scale_draw(key, step, m, mask=mask)
        if self.geometry_on:
            draw = draw._replace(
                p_factor=draw.p_factor * self.geometry_gains(m))
        return draw

    def cohort_channel_draw(self, key: jnp.ndarray, step,
                            cohort: jnp.ndarray, m_total: int,
                            mask=None) -> ChannelDraw:
        """The K-cohort's rows of the full-population channel realisation.

        Evaluates :meth:`channel_draw` at the population size ``m_total``
        from the same salted key and gathers the cohort's rows — a K < M
        cohort sees exactly the channels the full simulation would have
        dealt those devices, and a K == M cohort (``cohort == arange(M)``)
        reproduces the legacy draw bitwise.  Costs O(m_total) scalars per
        round, never O(m_total * d).  ``mask`` (K,) bool marks live cohort
        rows; it is scattered to the full population so device-coupled
        draws (the blind PS combiner) see the true transmitter set.
        """
        full_mask = None
        if mask is not None:
            full_mask = jnp.zeros((m_total,), bool).at[cohort].set(mask)
        draw = self.channel_draw(key, step, m_total, mask=full_mask)

        def take(v):
            return None if v is None else jnp.take(v, cohort, axis=0)

        return ChannelDraw(take(draw.p_factor), take(draw.active),
                           gain=take(draw.gain),
                           noise_scale=draw.noise_scale)

    def silent_state(self, g: jnp.ndarray, state: jnp.ndarray,
                     new_state: jnp.ndarray) -> jnp.ndarray:
        """Error state of a non-participating (deep-fade / dropout) device."""
        return new_state

    # ------------------------------------------------------ fault hooks
    @property
    def robust_on(self) -> bool:
        """Static gate for the fault-injection path: the robust master
        switch, or any nonzero *configured* fault rate (a swept rate axis
        rides ``robust=True`` — the sweep engine auto-promotes it)."""
        cfg = self.cfg
        return bool(cfg.robust or cfg.byzantine_frac > 0
                    or cfg.fault_rate > 0 or cfg.erasure_prob > 0)

    def fault_draw(self, key: jnp.ndarray, step, m: int) -> faults.FaultDraw:
        """One round's fault realisation (pure in the salted round key).

        ``key`` is the fault-salted round key (``fold_in(round_key,
        faults.SALT_FAULT)``) — callers own the salt, matching
        :meth:`channel_draw`.  Rates are the traced scheme attributes, so
        ``with_overrides`` vmaps them; the Byzantine set threshold draws
        from the run-level ``fault_key`` (persistent, nested in the
        fraction)."""
        return faults.fault_draw(self.fault_key, key, m,
                                 byzantine_frac=self.byzantine_frac,
                                 fault_rate=self.fault_rate,
                                 erasure_prob=self.erasure_prob,
                                 fault_kind=self.cfg.fault_kind)

    def cohort_fault_draw(self, key: jnp.ndarray, step,
                          cohort: jnp.ndarray,
                          m_total: int) -> faults.FaultDraw:
        """The K-cohort's rows of the full-population fault realisation —
        the fault analogue of :meth:`cohort_channel_draw`: a K < M cohort
        sees exactly the faults the full simulation would have dealt those
        devices, and K == M reproduces :meth:`fault_draw` bitwise."""
        return faults.take_rows(self.fault_draw(key, step, m_total), cohort)

    # ---------------------------------------------------- encode/decode
    def encode(self, g: jnp.ndarray, state: jnp.ndarray, step, key,
               ctx: Optional[MACContext] = None
               ) -> Tuple[jnp.ndarray, jnp.ndarray, Dict[str, jnp.ndarray]]:
        """Device-side: (d,) gradient -> channel frame. Returns
        ``(frame, new_state, metrics)``."""
        raise NotImplementedError

    def decode(self, y: jnp.ndarray, step,
               ctx: Optional[MACContext] = None) -> jnp.ndarray:
        """PS-side: MAC output -> average-gradient estimate."""
        m = ctx.m if ctx is not None else self.m
        return y / m

    # ------------------------------------------------------ slice hooks
    # Optional: schemes that can run on gradient *slices* (the fully-
    # sharded driver in core/distributed.py) implement these.  The frame is
    # a dict with a "body" array (psum'd over the device axes, optionally
    # in a narrow dtype) and optional "slots" scalars (always f32).
    def encode_slice(self, g_slice, state_slice, step, key, ctx: MACContext):
        raise NotImplementedError(
            f"scheme {self.name!r} does not support the sharded slice "
            "driver (needs a slice-local encode); use the simulated or "
            "round_sharded drivers")

    def decode_slice(self, y: Dict[str, jnp.ndarray], step, ctx: MACContext):
        raise NotImplementedError


# ---------------------------------------------------------------------------
# ideal (error-free shared link, the paper's benchmark)
# ---------------------------------------------------------------------------


@register_scheme("ideal")
class IdealScheme(Scheme):
    """y = sum_m g_m / M over an error-free link."""

    def channel_dim(self, d: Optional[int] = None) -> int:
        return self.d if d is None else d

    def encode(self, g, state, step, key, ctx=None):
        return g.astype(jnp.float32), state, {}

    # slice driver: the MAC psum *is* the aggregation
    def encode_slice(self, g_slice, state_slice, step, key, ctx):
        return {"body": g_slice}, state_slice, {"p_t": jnp.zeros(())}

    def decode_slice(self, y, step, ctx):
        return y["body"] / ctx.m


# ---------------------------------------------------------------------------
# A-DSGD (paper §IV): EF + top-k + compressive projection + analog MAC + AMP
# ---------------------------------------------------------------------------


@register_scheme("a_dsgd")
class ADSGDScheme(Scheme):
    """Analog DSGD: the paper's over-the-air scheme (§IV, §IV-A)."""

    analog = True

    @cached_property
    def projector(self):
        return make_projector(self.cfg, self.d)

    @cached_property
    def k(self) -> int:
        if isinstance(self.projector, DenseProjector):
            return self.cfg.k_for(self.d)
        # blocked: k scales with the realised channel dimension
        return max(1, int(self.cfg.k_frac * self.projector.out_dim))

    def channel_dim(self, d: Optional[int] = None) -> int:
        # body + mean slot + scale slot (static frame layout, channel.py)
        if d is not None and d != self.d:
            raise ValueError(
                "an A-DSGD scheme's channel dimension is fixed by its "
                f"projector (built for d={self.d}); call get_scheme with "
                f"d={d} to size a different gradient")
        return self.projector.out_dim + 2

    def encode(self, g, state, step, key, ctx=None):
        cfg = self.cfg
        g = g.astype(jnp.float32)
        p_t = self.p_t(step, ctx.p_factor if ctx is not None else 1.0)
        g_ec = g + state.astype(jnp.float32)
        projector = self.projector
        if isinstance(projector, DenseProjector):
            g_sp = compression.top_k_sparsify(g_ec, self.k)
            new_state = g_ec - g_sp
        else:
            tau = compression.sampled_topk_threshold(
                g_ec, self.k, key, use_kernel=cfg.use_kernel)
            g_sp, new_state = ops.ef_sparsify(
                g, state.astype(jnp.float32), tau, use_kernel=cfg.use_kernel)
        g_tilde = projector.project(g_sp)
        use_mr = (jnp.asarray(step) < cfg.mean_removal_steps)
        frame, alpha = channel.make_frame(g_tilde, p_t, use_mr)
        metrics = {"alpha": alpha, "p_t": p_t,
                   "frame_power": channel.frame_power(frame)}
        return frame, new_state.astype(state.dtype), metrics

    def decode(self, y, step, ctx=None):
        use_mr = (jnp.asarray(step) < self.cfg.mean_removal_steps)
        y_body = channel.ps_normalize(y, use_mr)
        return amp_decode(y_body, self.projector, self.cfg.amp_iters)

    def silent_state(self, g, state, new_state):
        # a device that could not transmit (deep fade, mid-round dropout)
        # banks its whole update — nothing of g_sp reached the MAC.  On
        # the AWGN channel every device is active, so this branch is never
        # *selected*; the fading subclasses inherit it.
        return (g + state).astype(new_state.dtype)

    # ------------------------------------------------------ slice hooks
    # The fully-sharded pipeline (train/trainer.py phase 2): every device
    # owns a (d_pad / n_shards) slice.  EF, thresholding, projection and the
    # power scalars are slice-local; cross-shard coordination is a 65k-
    # sample all_gather and scalar psums.  Per-shard measurement matrices
    # derive from a shard-folded seed (the PS uses the same fold).

    def _slice_seed(self, ctx: MACContext):
        shard_idx, n_shards = shard_info(ctx.shard_axes)
        return ref.splitmix32(jnp.uint32(self.cfg.seed)
                              ^ shard_idx.astype(jnp.uint32)), shard_idx

    def encode_slice(self, g_slice, state_slice, step, key, ctx):
        from repro.core.distributed import psum_all
        cfg = self.cfg
        d_pad = ctx.d_pad
        d_local = g_slice.shape[0]

        # --- error feedback + sampled global threshold ---------------------
        g_ec = g_slice + state_slice.astype(jnp.float32)
        k = max(1, int(cfg.k_frac * cfg.s_frac * d_pad))
        stride = max(1, d_local // ctx.sample_per_shard)
        n_s = d_local // stride
        with stage("threshold"):
            local_sample = jnp.abs(jax.lax.slice_in_dim(
                g_ec, 0, n_s * stride, stride, axis=0))
            all_samples = (jax.lax.all_gather(local_sample,
                                              ctx.shard_axes).reshape(-1)
                           if ctx.shard_axes else local_sample)
            q = 1.0 - k / d_pad
            tau = ops.sample_quantile(all_samples, q,
                                      use_kernel=cfg.use_kernel)
        keep = jnp.abs(g_ec) >= tau
        g_sp = jnp.where(keep, g_ec, 0.0)
        new_state = (g_ec - g_sp).astype(state_slice.dtype)

        # --- blocked projection (per-shard folded seed) --------------------
        c = cfg.block_size
        s_block = max(2, int(round(cfg.s_frac * c)))
        n_blocks_local = d_local // c
        seed_u32, _ = self._slice_seed(ctx)
        # (nb_local, s_block); the slice path always draws Rademacher A
        yb = blocked_projection(g_sp.reshape(n_blocks_local, c), seed_u32,
                                s_block, ctx.chunk_blocks, rademacher=True,
                                use_kernel=cfg.use_kernel)

        # --- power scaling (paper eq. 13/22; scalars psum'd over shards) ---
        # ctx.p_factor carries this device's fading received-power factor
        p_t = self.p_t(step, ctx.p_factor) * ctx.p_scale
        use_mr = (jnp.asarray(step)
                  < cfg.mean_removal_steps).astype(jnp.float32)
        s_tilde = float((d_pad // c) * s_block)          # global channel dim
        mu = use_mr * psum_all(jnp.sum(yb), ctx.shard_axes) / s_tilde
        energy = psum_all(jnp.sum(yb * yb), ctx.shard_axes)
        energy_az = energy - (s_tilde - 1.0) * mu * mu + 1.0
        alpha = p_t / jnp.maximum(energy_az, 1e-12)
        ra = jnp.sqrt(alpha)
        frame = {"body": ra * (yb - mu), "slots": jnp.stack([ra * mu, ra])}
        metrics = {"alpha": alpha, "p_t": p_t, "tau": tau,
                   "frame_power": alpha * energy_az}
        return frame, new_state, metrics

    def decode_slice(self, y, step, ctx):
        cfg = self.cfg
        body, slots = y["body"], y["slots"]
        use_mr = (jnp.asarray(step)
                  < cfg.mean_removal_steps).astype(jnp.float32)
        # the clean scale slot is sum_m sqrt(alpha_m) > 0 by construction;
        # a noise-dominated reading falls back to 1.0 so it can neither
        # flip the observation's sign nor amplify it unboundedly (same rule
        # as channel.ps_normalize on the dense path)
        scale = jnp.where(slots[1] > channel.SCALE_SLOT_FLOOR, slots[1], 1.0)
        y_norm = (body + use_mr * slots[0]) / scale
        seed_u32, _ = self._slice_seed(ctx)
        c = cfg.block_size
        if ctx.shard_decode and ctx.device_axes:
            # the y slice is identical on every device row after the psum —
            # decode 1/M of its blocks per row and all-gather the results;
            # block ids stay global via the id offset (encode used global
            # ids, so a row-salted projector would be wrong).
            n_rows = 1
            row_idx = jnp.zeros((), jnp.int32)
            for ax in ctx.device_axes:
                sz = jax.lax.axis_size(ax)
                row_idx = row_idx * sz + jax.lax.axis_index(ax)
                n_rows *= sz
            nb = y_norm.shape[0]
            nb_pad = -(-nb // n_rows) * n_rows
            y_p = jnp.pad(y_norm, ((0, nb_pad - nb), (0, 0)))
            per = nb_pad // n_rows
            y_mine = jax.lax.dynamic_slice_in_dim(y_p, row_idx * per, per, 0)
            x_mine = amp_blocked_core(
                y_mine, seed_u32, c, cfg.amp_iters, ctx.chunk_blocks,
                id_offset=(row_idx * per).astype(jnp.uint32),
                use_kernel=cfg.use_kernel)
            xg = jax.lax.all_gather(x_mine, ctx.device_axes, tiled=True)
            return xg[:nb].reshape(-1)
        return amp_blocked_core(y_norm, seed_u32, c, cfg.amp_iters,
                                ctx.chunk_blocks,
                                use_kernel=cfg.use_kernel).reshape(-1)


# ---------------------------------------------------------------------------
# A-DSGD over fading MACs (follow-ups 1907.09769 / 1907.03909): truncated
# inversion under perfect / estimated CSI, and CSI-free blind transmission
# ---------------------------------------------------------------------------


@register_scheme("a_dsgd_fading")
class ADSGDFadingScheme(ADSGDScheme):
    """A-DSGD under Rayleigh fading with truncated channel inversion
    (perfect CSI, arXiv:1907.09769): devices below the fade threshold stay
    silent this round (their whole update accumulates into the error
    state); the rest pre-invert, so the usable received power becomes
    ``P_t * h_m^2``.  The gain *process* (``cfg.fading_process``: block-flat
    ``static``, per-round ``iid``, time-correlated ``gauss_markov``) comes
    from :mod:`repro.core.fading`; ``iid`` is bitwise the original
    per-round Rayleigh draw."""

    def device_factors(self, key, m):
        # legacy spelling of the iid draw — kept because it is the module
        # docstring's ~10-line extension example; channel_draw generalises
        # it across fading processes
        h = channel.rayleigh_gains(key, m)
        return channel.truncated_inversion_power(h, self.fading_threshold)

    def small_scale_draw(self, key, step, m, mask=None):
        re, im = self.gains(key, step, m)
        h = fading.magnitude(re, im)
        p_factor, active = channel.truncated_inversion_power(
            h, self.fading_threshold)
        return ChannelDraw(p_factor, active)

    def silent_state(self, g, state, new_state):
        # a silent (deep-fade) device accumulates its whole update
        return (g + state).astype(new_state.dtype)


@register_scheme("a_dsgd_csi_err")
class ADSGDCSIErrScheme(ADSGDFadingScheme):
    """Truncated inversion driven by a *noisy* CSI estimate.

    The device only sees ``h_hat = h + e``, ``e ~ CN(0, csi_err_var)``
    (an MMSE-style estimation error): it makes its truncation decision and
    pre-inverts with ``h_hat``, so the frame arrives scaled by the
    misalignment ``Re(h / h_hat)`` — residual fading that survives decode —
    while the power budget follows ``|h_hat|^2``.  With ``csi_err_var == 0``
    every quantity degrades bitwise to :class:`ADSGDFadingScheme` (pinned by
    the ``a_dsgd_csi_err0`` golden).
    """

    csi = "noisy"

    def small_scale_draw(self, key, step, m, mask=None):
        re, im = self.gains(key, step, m)
        est_re, est_im = fading.csi_estimate(
            re, im, jax.random.fold_in(key, 3), self.csi_err_var)
        h_est = fading.magnitude(est_re, est_im)
        p_factor, active = channel.truncated_inversion_power(
            h_est, self.fading_threshold)
        gain = fading.misalignment_gain(re, im, est_re, est_im,
                                        self.csi_err_var)
        return ChannelDraw(p_factor, active, gain=gain)


@register_scheme("a_dsgd_blind")
class ADSGDBlindScheme(ADSGDScheme):
    """A-DSGD with blind transmitters (no CSIT, arXiv:1907.03909).

    Devices cannot invert a gain they do not know: every device transmits
    its plain power-scaled frame (full transmit set, ``p_factor = 1``), and
    alignment is recovered at the PS, whose K antennas combine the
    superposed observations against the known receive CSI
    (:func:`repro.core.fading.blind_combiner_stats`).  Each frame then
    carries a per-device effective gain ``1 + O(sqrt(M/K))`` and the AWGN
    variance is enhanced by ``~ M/K`` — both vanish as K grows (channel
    hardening), which is the paper's asymptotic result.  The decode is
    untouched: the analog scale slot arrives as ``sum_m g_m sqrt(alpha_m)``
    and absorbs the combiner's average gain exactly like the fading
    alpha-spread it was designed for.
    """

    csi = "none"

    def small_scale_draw(self, key, step, m, mask=None):
        k_ant = self.fading_spec.ps_antennas
        re, im = self.gains(key, step, m * k_ant)
        re, im = re.reshape(m, k_ant), im.reshape(m, k_ant)
        if mask is not None:
            # phantom (masked-out) devices do not exist physically: their
            # channel rows must not enter the PS combiner f_k = sum_m h_mk,
            # so an m_active sweep sees the m_eff-transmitter combiner
            # statistics, not the padded cohort's
            live = mask.astype(re.dtype)[:, None]
            re, im = re * live, im * live
        gain, noise_scale = fading.blind_combiner_stats(re, im)
        return ChannelDraw(jnp.ones((m,)), jnp.ones((m,), bool),
                           gain=gain, noise_scale=noise_scale)


# ---------------------------------------------------------------------------
# digital baselines (paper §III, §VI): quantize to the MAC bit budget R_t
# ---------------------------------------------------------------------------


class _BitBudgetScheme(Scheme):
    """Shared plumbing for the digital schemes: the per-step budget q_t is
    precomputed on the host from the MAC capacity R_t (paper eq. 8/9)."""

    def __init__(self, cfg: OTAConfig, d: int, m: int):
        super().__init__(cfg, d, m)
        q_np = self.build_q_schedule(m, self._p_np)
        self.q_sched = jnp.asarray(q_np, jnp.int32)
        self.q_max = int(max(int(q_np.max()), 1))

    def build_q_schedule(self, m: int, p_np) -> Any:
        """Host-precomputed q_t array for an (m, P_t) pair — the single
        source of the budget/cap rule, shared with the sweep engine
        (repro.experiments.sweep precomputes per-grid-point schedules
        with the effective device count and vmaps them)."""
        return compression.digital_q_schedule(
            self.d, self.cfg.s_for(self.d), m, p_np, self.cfg.sigma2,
            scheme=self.name, l_q=self.cfg.quant_bits,
            q_cap=min(self.d // 2, 1 << 16))

    def channel_dim(self, d: Optional[int] = None) -> int:
        return self.cfg.s_for(self.d if d is None else d)

    def q_t(self, step) -> jnp.ndarray:
        return self.q_sched[jnp.minimum(step, self.q_sched.shape[0] - 1)]

    def encode(self, g, state, step, key, ctx=None):
        g = g.astype(jnp.float32)
        p_t = self.p_t(step, ctx.p_factor if ctx is not None else 1.0)
        q_t = self.q_t(step)
        v_q, new_state = self.compress(g, state, q_t, key)
        return v_q, new_state, {"q_t": q_t, "p_t": p_t}

    def compress(self, g, state, q_t, key):
        raise NotImplementedError


@register_scheme("d_dsgd")
class DDSGDScheme(_BitBudgetScheme):
    """Digital DSGD: error feedback + SBC quantization (paper §III)."""

    def compress(self, g, state, q_t, key):
        g_ec = g + state.astype(jnp.float32)
        v_q = compression.sbc_quantize(g_ec, q_t, self.q_max)
        return v_q, (g_ec - v_q).astype(state.dtype)

    def silent_state(self, g, state, new_state):
        # a D-DSGD device that failed mid-round banks its whole update
        # (error feedback over the digital link); only the fault-injection
        # path selects this — the legacy digital drivers never drop devices
        return (g + state).astype(new_state.dtype)


@register_scheme("signsgd")
class SignSGDScheme(_BitBudgetScheme):
    """SignSGD [16] adapted to the bit budget (paper eq. 43)."""

    def compress(self, g, state, q_t, key):
        return compression.signsgd_compress(g, q_t, self.q_max), state


@register_scheme("qsgd")
class QSGDScheme(_BitBudgetScheme):
    """QSGD [2] adapted to the bit budget (paper eq. 44)."""

    def compress(self, g, state, q_t, key):
        return compression.qsgd_compress(g, q_t, self.q_max,
                                         self.cfg.quant_bits, key), state


def registered_schemes() -> Tuple[str, ...]:
    """Every registered scheme name (registration order), evaluated live."""
    return tuple(SCHEME_REGISTRY)


def __getattr__(name: str):
    # SCHEMES is a live view of the registry: schemes registered after this
    # module imported (e.g. user @register_scheme) still appear.
    if name == "SCHEMES":
        return registered_schemes()
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


# ---------------------------------------------------------------------------
# generic drivers (scheme-agnostic: behaviour comes from the hooks)
# ---------------------------------------------------------------------------


def channel_amp(draw: ChannelDraw, dtype=jnp.float32) -> jnp.ndarray:
    """Per-device amplitude of the received frame: the transmit mask, times
    the channel gain when the draw carries one.  ``gain=None`` means exactly
    1, so the expression stays the 0/1 mask and the legacy path is bitwise
    (multiplying by the cast mask is IEEE-identical to multiplying by the
    bool — promotion performs the same cast)."""
    active = draw.active.astype(dtype)
    return active if draw.gain is None else draw.gain * active


def apply_channel_gain(frames: jnp.ndarray, draw: ChannelDraw) -> jnp.ndarray:
    """Silence inactive devices and apply the per-device channel gain to a
    stacked (m, s) frame batch (the simulated/masked drivers)."""
    return frames * channel_amp(draw, frames.dtype)[..., None]


def round_sigma2(scheme: Scheme, draw: ChannelDraw):
    """This round's AWGN variance: cfg.sigma2, under the channel's traced
    noise enhancement when the draw carries one (blind PS combining)."""
    if draw.noise_scale is None:
        return scheme.cfg.sigma2
    return scheme.cfg.sigma2 * draw.noise_scale


@stage("encode")
def encode_round(scheme: Scheme, grads: jnp.ndarray, deltas: jnp.ndarray,
                 step, key: jnp.ndarray, ctx: MACContext):
    """The device/channel half of :func:`round_simulated`: per-device
    encode, channel gain, MAC superposition (+AWGN for analog schemes).

    Returns ``(y, new_deltas, metrics, draw)`` — everything up to (but not
    including) the PS-side ``scheme.decode``.  Splitting here is what lets
    the streamed LLM driver (``train/fedllm.py``) double-buffer: while the
    PS decodes chunk ``i-1``, the devices encode and transmit chunk ``i``.
    ``round_simulated`` composes this with the decode, so the split is
    bitwise-invisible to every existing driver and golden.
    """
    m = grads.shape[0]
    dev_keys = jax.random.split(jax.random.fold_in(key, 1), m)
    draw = scheme.channel_draw(jax.random.fold_in(key, 2), step, m)
    active = draw.active
    frames, new_deltas, metrics = jax.vmap(
        lambda g, dl, kk, pf: scheme.encode(g, dl, step, kk,
                                            ctx.with_p_factor(pf)))(
            grads, deltas, dev_keys, draw.p_factor)
    if scheme.analog:
        frames = apply_channel_gain(frames, draw)
        new_deltas = jnp.where(active[:, None], new_deltas,
                               scheme.silent_state(grads, deltas, new_deltas))
        y = channel.mac_sum(frames, jax.random.fold_in(key, 0),
                            round_sigma2(scheme, draw))
    else:
        y = jnp.sum(frames, axis=0)
    return y, new_deltas, metrics, draw


@stage("decode")
def decode_round(scheme: Scheme, y: jnp.ndarray, step,
                 ctx: Optional[MACContext]) -> jnp.ndarray:
    """The PS half of a round: ``scheme.decode`` of the MAC output, under
    the ``decode`` stage scope; every round function decodes through here."""
    return scheme.decode(y, step, ctx)


def round_simulated(scheme: Scheme, grads: jnp.ndarray, deltas: jnp.ndarray,
                    step, key: jnp.ndarray,
                    ctx: Optional[MACContext] = None):
    """M devices on one host. grads/deltas: (M, d). Returns
    ``(ghat, new_deltas, metrics)``; the MAC is a sum over the leading axis
    (plus AWGN for analog schemes)."""
    if ctx is None:
        ctx = MACContext(m=scheme.m, fading=scheme.cfg.fading,
                         csi=scheme.csi)
    y, new_deltas, metrics, draw = encode_round(scheme, grads, deltas,
                                                step, key, ctx)
    ghat = decode_round(scheme, y, step, ctx)
    metrics = {k: jnp.mean(v) for k, v in metrics.items()}
    metrics["active_frac"] = jnp.mean(draw.active.astype(jnp.float32))
    if draw.gain is not None:
        metrics["chan_gain"] = jnp.mean(draw.gain)
    if draw.noise_scale is not None:
        metrics["noise_scale"] = draw.noise_scale
    return ghat, new_deltas, metrics


def sharded_channel_draw(scheme: Scheme, key: jnp.ndarray, step,
                         ctx: MACContext) -> ChannelDraw:
    """This device's channel realisation inside a shard_map.

    Every manual device evaluates the *full-M* draw from the shared round
    key (salt 2, matching :func:`round_simulated`) and takes its own row —
    the realisation is common knowledge across devices, which is what the
    correlated processes and the blind PS combiner (whose per-device gain
    depends on everyone's channel) require, and the per-scalar cost of the
    M-row draw is noise next to the d-sized frame math.
    """
    dev_idx, _ = shard_info(ctx.device_axes)
    draw = scheme.channel_draw(jax.random.fold_in(key, 2), step, ctx.m)

    def take(v):
        if v is None:
            return None
        return jax.lax.dynamic_index_in_dim(v, dev_idx.astype(jnp.int32),
                                            keepdims=False)

    return ChannelDraw(take(draw.p_factor), take(draw.active),
                       gain=take(draw.gain), noise_scale=draw.noise_scale)


def round_sharded(scheme: Scheme, g_local: jnp.ndarray,
                  delta_local: jnp.ndarray, step, key: jnp.ndarray,
                  ctx: MACContext):
    """One aggregation round inside a shard_map (manual axes = devices).

    ``ctx.groups``: optional axis_index_groups for the *ideal* intra-site
    average (hierarchical edge-site mapping) over the last device axis; the
    MAC psum then runs over all manual devices and is divided by the group
    size (the scale slot absorbs any per-device alpha spread).
    """
    group_size = ctx.group_size
    if ctx.groups is not None:
        g_local = jax.lax.psum(g_local, ctx.device_axes[-1],
                               axis_index_groups=[list(g) for g in ctx.groups])
        g_local = g_local / group_size
    # distinct salts for the three RNG consumers (matching round_simulated):
    # fold 1 -> device-side encode randomness, fold 2 -> the channel draw,
    # fold 0 -> the channel AWGN
    with stage("encode"):
        if scheme.analog:
            draw = sharded_channel_draw(scheme, key, step, ctx)
            ctx = ctx.with_p_factor(draw.p_factor)
        frame, new_delta, metrics = scheme.encode(
            g_local, delta_local, step, jax.random.fold_in(key, 1), ctx)
        if scheme.analog:
            frame = frame * channel_amp(draw, frame.dtype)
            new_delta = jnp.where(draw.active, new_delta,
                                  scheme.silent_state(g_local, delta_local,
                                                      new_delta))
        y = frame
        for ax in ctx.device_axes:
            y = jax.lax.psum(y, ax)
        if group_size > 1:
            y = y / group_size
        if scheme.analog:
            mac_key = jax.random.fold_in(key, 0)
            sigma2 = round_sigma2(scheme, draw)
            if (ctx.site_mac and ctx.groups is not None
                    and len(ctx.groups) > 1):
                # hierarchical MAC: every edge-site group's partial OTA sum
                # carries its own receiver AWGN, summed by the backhaul
                # combine
                y = y + channel.site_awgn(
                    mac_key, y.shape, sigma2, len(ctx.groups),
                    site_noise_scale=ctx.site_noise_scale, dtype=y.dtype)
            else:
                y = y + channel.awgn(mac_key, y.shape, sigma2, y.dtype)
    ghat = decode_round(scheme, y, step, ctx)
    return ghat, new_delta, metrics
