"""Compiled experiment engine: a federated run as ONE jitted ``lax.scan``.

``train/paper_repro.run_federated`` is the reference implementation — a
Python loop dispatching one jitted round at a time, with host evals in
between.  This module compiles the *entire run* instead: the scan carry is
``(params, opt_state, deltas, momenta)``, each scan step performs the full
round (per-device gradients -> scheme encode -> MAC -> PS decode -> ADAM)
with the paper's per-round key stream, and test accuracy/loss are computed
inside the scan, so ``steps`` rounds cost one XLA dispatch and zero host
round-trips.  ``repro.experiments.sweep`` vmaps whole sweep grids over the
scan (see docs/DESIGN.md §6 for the traced/static split).

The round body is built from the same pieces as the reference loop
(``device_grads``, ``round_simulated``, ``Optimizer.apply``), which is what
the bitwise parity test in ``tests/test_experiments.py`` pins.

Device-count sweeps use :func:`round_masked`: M is a *shape*, so a vmapped
M-axis pads every grid point to ``M_pad`` devices and silences the padding
with a traced participation mask (docs/DESIGN.md §6 explains why padding,
not reshaping, is the only way to put M on a vmap axis).
"""
from __future__ import annotations

import dataclasses
import os
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

import jax
import jax.flatten_util
import jax.numpy as jnp
import numpy as np

from repro.configs.base import OTAConfig
from repro.core import channel, scheduling
from repro.core import schemes as schemes_mod
from repro.core.schemes import MACContext, Scheme, get_scheme, round_simulated
from repro.local.work import (
    LOCAL_OVERRIDE_ATTRS, LocalWork, get_local, local_device_grads,
)
from repro.optim.optim import Optimizer
from repro.robust import aggregators, faults, guards
from repro.tracing import span, stage
from repro.train.paper_repro import (
    accuracy, ce_loss, device_grads, flat_grad_fn, init_linear,
)

#: base of the per-round key stream; round t of seed 0 uses PRNGKey(1000 + t),
#: matching run_federated exactly (seed k shifts the stream by k * steps so
#: seed sweeps draw disjoint keys)
KEY_STREAM_BASE = 1000


def round_keys(steps: int, seed: int = 0) -> jnp.ndarray:
    """(steps, ...) stacked per-round PRNG keys for one run."""
    seeds = KEY_STREAM_BASE + seed * steps + jnp.arange(steps)
    return jax.vmap(jax.random.PRNGKey)(seeds)


def eval_indices(steps: int, eval_every: int) -> np.ndarray:
    """The rounds run_federated evaluates after (t % every == 0 or last)."""
    return np.asarray([t for t in range(steps)
                       if t % eval_every == 0 or t == steps - 1], np.int64)


@dataclass(frozen=True)
class Experiment:
    """Static description of one federated training configuration."""
    cfg: OTAConfig
    steps: int
    lr: float = 1e-3
    eval_every: int = 10
    optimizer: str = "adam"
    local_steps: int = 1
    local_lr: float = 0.1
    momentum_correction: float = 0.0
    seed: int = 0
    use_kernel: bool = False     # Pallas projection/AMP inside the scan
    guard: Optional[guards.GuardConfig] = None   # round guardrails (§10)


@dataclass
class EngineRun:
    """Result of one compiled run — mirrors FederatedRun at eval points."""
    accs: List[float]
    losses: List[float]
    metrics: List[Dict[str, float]]
    eval_steps: np.ndarray
    all_accs: np.ndarray         # (steps,) — every round, free inside scan
    all_losses: np.ndarray
    params: Any = None           # final model parameters (pytree)


# ---------------------------------------------------------------------------
# masked round (padded device-count sweeps)
# ---------------------------------------------------------------------------


def round_masked(scheme: Scheme, grads: jnp.ndarray, deltas: jnp.ndarray,
                 step, key: jnp.ndarray, mask: jnp.ndarray, ctx: MACContext,
                 *, dev_keys=None, draw=None, mac=None, fault=None,
                 sched=None):
    """:func:`~repro.core.schemes.round_simulated` with a traced device mask.

    ``mask`` (M_pad,) marks which padded devices exist at this grid point:
    masked-out devices transmit nothing (their frames — including the analog
    power/mean slots — are zeroed before the MAC sum), keep their error
    state untouched, and the PS decodes against the traced effective device
    count.  The RNG layout (key salts, ``split(key, M_pad)``) matches
    ``round_simulated`` at ``M = M_pad``, so an all-ones mask reproduces it
    exactly (masking multiplies frames by 1.0 and adds 0.0 to the sum).

    The keyword hooks re-seat the round on a sampled cohort
    (:mod:`repro.population`): ``dev_keys`` (M_pad, ...) replaces the
    in-place key split, ``draw`` replaces the channel realisation (the
    cohort view of a full-population draw), ``mac`` — a callable
    ``(frames, key, sigma2) -> y`` — replaces the flat analog MAC sum
    (hierarchical edge-site aggregation), and ``fault`` replaces the fault
    realisation (the cohort view of a full-population trace), and
    ``sched`` — a (M_pad,) bool transmit set from the subband scheduler
    (:mod:`repro.core.scheduling`) — restricts the round to the scheduled
    devices: an unscheduled device is treated exactly like a deep-faded
    one (its frame never reaches the MAC and its whole update banks via
    ``Scheme.silent_state``).  Defaults preserve the legacy path bitwise.

    Fault injection (:mod:`repro.robust`, docs/DESIGN.md §10) is gated on
    the *static* ``scheme.robust_on``: Byzantine/stale gradients transform
    before encode, NaN/Inf poisoning hits the encoded *frame* (a broken
    transmitter on the air interface — gradient-level NaN would be
    filtered structurally by top-k sparsification), dropouts leave the
    transmit set with error-feedback banking via ``Scheme.silent_state``,
    and digital packet erasures drop the frame while the unaware device
    banks nothing.  Robust aggregation gates on the static
    ``cfg.aggregator`` / ``cfg.clip_power`` — independent of fault
    injection, so defences can run without attacks and vice versa.
    """
    m_pad = grads.shape[0]
    mask_b = mask > 0
    # the max guard only engages when *every* device is masked out (an
    # empty cohort round); any populated mask is untouched bitwise
    m_eff = jnp.maximum(jnp.sum(mask.astype(jnp.float32)), 1.0)
    ctx = dataclasses.replace(ctx, m=m_eff)
    if dev_keys is None:
        dev_keys = jax.random.split(jax.random.fold_in(key, 1), m_pad)
    if draw is None:
        # device-coupled draws (the blind PS combiner) must not see the
        # padded phantom devices' channels; an all-ones mask multiplies
        # rows by 1.0, so the unmasked equivalence below still holds bitwise
        draw = scheme.channel_draw(jax.random.fold_in(key, 2), step, m_pad,
                                   mask=mask_b)
    if sched is not None:
        # the scheduler's transmit set composes like a deep fade: the
        # frame is silenced and the analog silent_state banking below
        # catches the unscheduled device (digital banking is explicit)
        draw = draw._replace(active=draw.active & sched)
    robust = scheme.robust_on
    cfg = scheme.cfg
    true_grads = grads
    if robust:
        if fault is None:
            fault = scheme.fault_draw(
                jax.random.fold_in(key, faults.SALT_FAULT), step, m_pad)
        grads = faults.apply_gradient_faults(
            grads, fault, byz_attack=cfg.byz_attack,
            byz_scale=scheme.byz_scale)
    with stage("encode"):
        active = draw.active
        frames, new_deltas, metrics = jax.vmap(
            lambda g, dl, kk, pf: scheme.encode(g, dl, step, kk,
                                                ctx.with_p_factor(pf)))(
                grads, deltas, dev_keys, draw.p_factor)
        if scheme.analog:
            if robust:
                # make_frame normalises every frame to P_t, so an analog
                # attacker's leverage is transmit *power*, not gradient scale:
                # Byzantine frames violate the power constraint by byz_scale
                # in amplitude, and dropouts leave the transmit set mid-round
                byz_amp = jnp.where(fault.byz, scheme.byz_scale, 1.0)
                frames = frames * byz_amp[:, None].astype(frames.dtype)
                active = active & ~fault.dropout
            if cfg.clip_power:
                # transmit-side hardware cap: the analog defence (bounds the
                # power any device — honest or Byzantine — can put on the MAC)
                frames = aggregators.clip_frame_power(
                    frames, scheme.power_cap * scheme.p_t(step))
            if robust:
                # after the clip: a power limiter cannot repair a broken DAC
                frames = faults.apply_frame_faults(frames, fault)
            new_deltas = jnp.where(active[:, None], new_deltas,
                                   scheme.silent_state(true_grads, deltas,
                                                       new_deltas))
            active = active & mask_b
            frames = schemes_mod.apply_channel_gain(
                frames, draw._replace(active=active))
            mac_key = jax.random.fold_in(key, 0)
            sigma2 = schemes_mod.round_sigma2(scheme, draw)
            y = (channel.mac_sum(frames, mac_key, sigma2) if mac is None
                 else mac(frames, mac_key, sigma2))
        else:
            if robust:
                # dropouts know they failed -> bank their whole update; erased
                # packets are lost in the channel and poisoned packets carry
                # garbage payloads — either way the unaware device's state
                # evolves as if sent
                frames = faults.apply_frame_faults(frames, fault)
                new_deltas = jnp.where(
                    fault.dropout[:, None],
                    scheme.silent_state(true_grads, deltas, new_deltas),
                    new_deltas)
                active = active & ~fault.dropout & ~fault.erased
            if sched is not None:
                # an unscheduled digital device knows it was not granted a
                # subband this round and banks its whole update (EF over the
                # digital link, like a robust dropout that saw it coming)
                new_deltas = jnp.where(
                    sched[:, None], new_deltas,
                    scheme.silent_state(true_grads, deltas, new_deltas))
            active = active & mask_b
            if cfg.aggregator != "mean":
                y = aggregators.robust_combine(
                    frames, active, m_eff, aggregator=cfg.aggregator,
                    trim_frac=scheme.trim_frac, norm_cap=scheme.norm_cap)
            else:
                # the literal sum (never the trimmed path at trim=0: a sorted
                # sum re-associates, which is not bitwise the same reduction)
                frames = frames * (active if (robust or sched is not None)
                                   else mask_b)[:, None]
                y = jnp.sum(frames, axis=0)
    # padded devices do not exist: their error state must not evolve
    new_deltas = jnp.where(mask_b[:, None], new_deltas, deltas)
    ghat = schemes_mod.decode_round(scheme, y, step, ctx)
    w = mask.astype(jnp.float32)
    metrics = {k: jnp.sum(v * w) / m_eff for k, v in metrics.items()}
    metrics["active_frac"] = jnp.sum(active.astype(jnp.float32)) / m_eff
    if robust:
        faulty = fault.poison | fault.stale | fault.dropout | fault.erased
        metrics["byz_frac"] = (jnp.sum((fault.byz & mask_b)
                                       .astype(jnp.float32)) / m_eff)
        metrics["fault_frac"] = (jnp.sum((faulty & mask_b)
                                         .astype(jnp.float32)) / m_eff)
    return ghat, new_deltas, metrics


# ---------------------------------------------------------------------------
# the compiled runner
# ---------------------------------------------------------------------------


class CompiledExperiment:
    """Compile-once runner for one static configuration.

    :meth:`run` (and :meth:`run_masked`) are pure traced functions —
    ``jit``/``vmap`` them freely.  ``overrides`` swaps per-grid-point
    schedule arrays onto the scheme (``p_sched``, ``q_sched``) via
    :meth:`Scheme.with_overrides`; everything else about the scheme is
    static and shared by every point in a vmapped grid.
    """

    def __init__(self, x_dev: np.ndarray, y_dev: np.ndarray,
                 x_test: np.ndarray, y_test: np.ndarray, exp: Experiment):
        m, b, dim = x_dev.shape
        self.exp = exp
        self.m = m
        n_classes = int(np.max(y_dev)) + 1
        params = init_linear(dim, n_classes, jax.random.PRNGKey(exp.seed))
        flat0, self.unravel = jax.flatten_util.ravel_pytree(params)
        self.d = flat0.shape[0]
        self.params0 = params
        self.scheme = get_scheme(exp.cfg, self.d, m)
        self.localwork = get_local(exp.cfg, exp.local_lr)
        # static gate: cfg.scheduler == "none" resolves to None and no
        # scheduling op enters the trace (docs/DESIGN.md §12)
        self.scheduler = scheduling.get_scheduler(exp.cfg)
        if not self.localwork.identity and exp.local_steps > 1:
            raise ValueError(
                "local_steps > 1 (the legacy FedAvg path) conflicts with "
                f"the configured local algorithm {exp.cfg.local!r} at "
                f"local_epochs={exp.cfg.local_epochs}; use cfg.local_epochs")
        self._grad_fn = flat_grad_fn(self.unravel)
        self.opt = Optimizer(name=exp.optimizer, lr=exp.lr)
        self.xd, self.yd = jnp.asarray(x_dev), jnp.asarray(y_dev)
        self.xt, self.yt = jnp.asarray(x_test), jnp.asarray(y_test)
        self.ctx = MACContext(
            m=m, fading=exp.cfg.fading, csi=self.scheme.csi,
            use_kernel=exp.use_kernel or exp.cfg.use_kernel)

    # ------------------------------------------------------------- pieces
    def _carry0(self):
        carry = (self.params0, self.opt.init(self.params0),
                 jnp.zeros((self.m, self.d), jnp.float32),
                 jnp.zeros((self.m, self.d), jnp.float32))
        if self.localwork.has_dual:
            carry = carry + (self.localwork.init_dual(self.m, self.d),)
        if self._sched_state:
            carry = carry + (self.scheduler.init_state(self.m),)
        if self.exp.guard is not None:
            carry = carry + (guards.init_guard_state(),)
        return carry

    @property
    def _sched_state(self) -> bool:
        """Whether a scheduler state vector rides the scan carry (after
        the duals, before the guard state)."""
        return self.scheduler is not None and self.scheduler.has_state

    def _round(self, sch: Scheme, lw: LocalWork, carry, t, key, mask):
        exp = self.exp
        params, opt_state, deltas, momenta = carry[:4]
        duals = carry[4] if lw.has_dual else None
        sstate = (carry[4 + int(lw.has_dual)] if self._sched_state
                  else None)
        gstate = carry[-1] if exp.guard is not None else None
        old_extras = ((deltas, momenta) + ((duals,) if lw.has_dual else ())
                      + ((sstate,) if self._sched_state else ()))
        if lw.identity:
            # the pre-axis jaxpr, byte-for-byte — pins the goldens
            with stage("grads"):
                grads, momenta = device_grads(
                    params, self.unravel, self.xd, self.yd, momenta,
                    local_steps=exp.local_steps, local_lr=exp.local_lr,
                    momentum_correction=exp.momentum_correction)
        else:
            with stage("grads"):
                grads, momenta, new_duals = local_device_grads(
                    lw, self._grad_fn, params, self.xd, self.yd, momenta,
                    duals, momentum_correction=exp.momentum_correction)
            if lw.has_dual:
                # padded phantom devices do not exist: their dual must not
                # evolve (same keep-rule round_masked applies to deltas)
                duals = (new_duals if mask is None else
                         jnp.where((mask > 0)[:, None], new_duals, duals))
        if self.scheduler is not None:
            # the scheduler needs the round's received-power factors
            # (post-geometry, post-fading) to rank, so the channel draw is
            # evaluated here — the identical expression round_masked would
            # have built (same salt, same mask) — and injected alongside
            # the transmit set; round_masked folds ``sched`` into the
            # active set so unscheduled devices bank via silent_state
            rmask = (mask if mask is not None
                     else jnp.ones((self.m,), jnp.float32))
            rmask_b = rmask > 0
            draw = sch.channel_draw(jax.random.fold_in(key, 2), t, self.m,
                                    mask=rmask_b)
            sched, new_sstate = scheduling.schedule(
                self.scheduler,
                jax.random.fold_in(key, scheduling.SALT_SCHED), t,
                draw.p_factor, sch.n_subbands, state=sstate, mask=rmask_b)
            if self._sched_state:
                # phantom (masked-out) devices' carried scheduler state
                # must not evolve — the deltas keep-rule
                sstate = (new_sstate if mask is None else
                          jnp.where(rmask_b, new_sstate, sstate))
            ghat, deltas, met = round_masked(sch, grads, deltas, t, key,
                                             rmask, self.ctx, draw=draw,
                                             sched=sched)
        elif mask is None and not sch.robust_on:
            ghat, deltas, met = round_simulated(sch, grads, deltas, t, key,
                                                self.ctx)
        else:
            # the fault-injection path lives in round_masked; an all-ones
            # mask is pinned bitwise-equal to round_simulated
            rmask = (mask if mask is not None
                     else jnp.ones((self.m,), jnp.float32))
            ghat, deltas, met = round_masked(sch, grads, deltas, t, key,
                                             rmask, self.ctx)
        extras = ((deltas, momenta) + ((duals,) if lw.has_dual else ())
                  + ((sstate,) if self._sched_state else ()))
        if exp.guard is None:
            with stage("optimizer"):
                params, opt_state = self.opt.apply(
                    params, self.unravel(ghat), opt_state)
            with stage("eval"):
                out = {"acc": accuracy(params, self.xt, self.yt),
                       "loss": ce_loss(params, self.xt, self.yt),
                       "metrics": met}
            return (params, opt_state) + extras, out
        (params, opt_state, extras, gstate, loss,
         gmet) = guards.guarded_step(
            exp.guard, gstate, self.opt, params, opt_state, ghat,
            self.unravel, extras=extras, old_extras=old_extras,
            loss_fn=lambda p: ce_loss(p, self.xt, self.yt))
        with stage("eval"):
            out = {"acc": accuracy(params, self.xt, self.yt), "loss": loss,
                   "metrics": {**met, **gmet}}
        return (params, opt_state) + tuple(extras) + (gstate,), out

    def _scan(self, overrides, keys, mask):
        carry, outs = self.run_segment(overrides, keys, mask,
                                       self._carry0(), 0)
        outs["params"] = carry[0]
        return outs

    # ------------------------------------------------------- traced entry
    def run_segment(self, overrides: Dict[str, jnp.ndarray],
                    keys: jnp.ndarray, mask, carry, t0):
        """Scan rounds ``t0 .. t0 + len(keys)`` from an explicit carry.

        The checkpoint/resume building block: a full run is the composition
        of its segments (the scan body is a pure function of ``(carry,
        (t, key))``), so splitting a run at any boundary and resuming from
        the saved carry reproduces the uninterrupted run bitwise.  Returns
        ``(carry, outs)``.

        ``overrides`` splits between the scheme (schedule arrays, channel /
        robustness scalars) and the local-work knobs
        (``LOCAL_OVERRIDE_ATTRS``) — each lands on its own carrier via the
        matching ``with_overrides``.
        """
        lw_ov = {k: v for k, v in overrides.items()
                 if k in LOCAL_OVERRIDE_ATTRS}
        sch_ov = {k: v for k, v in overrides.items()
                  if k not in LOCAL_OVERRIDE_ATTRS}
        sch = (self.scheme.with_overrides(**sch_ov) if sch_ov
               else self.scheme)
        lw = (self.localwork.with_overrides(**lw_ov) if lw_ov
              else self.localwork)

        def body(carry, inp):
            t, key = inp
            return self._round(sch, lw, carry, t, key, mask)

        ts = t0 + jnp.arange(keys.shape[0])
        return jax.lax.scan(body, carry, (ts, keys))

    def run(self, overrides: Dict[str, jnp.ndarray], keys: jnp.ndarray):
        """One full run. Returns {"acc": (steps,), "loss": (steps,),
        "metrics": {...: (steps,)}, "params": pytree}."""
        return self._scan(overrides, keys, None)

    def run_masked(self, overrides: Dict[str, jnp.ndarray],
                   keys: jnp.ndarray, mask: jnp.ndarray):
        """Padded-M variant: mask (M_pad,) marks live devices."""
        return self._scan(overrides, keys, mask)


def _concat_outs(chunks: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Concatenate per-segment scan outputs along the round axis."""
    return jax.tree.map(lambda *xs: np.concatenate(xs, axis=0), *chunks)


def _restore_carry(ref_carry, loaded):
    """Rebuild a checkpointed carry against the engine's reference pytree
    (npz round-trips degrade NamedTuples — GuardState, BankedState — to
    plain tuples; the reference structure restores the classes)."""
    return jax.tree.unflatten(jax.tree.structure(ref_carry),
                              jax.tree.leaves(loaded))


def run_checkpointed(ce, overrides, keys, *, checkpoint_dir: str,
                     checkpoint_every: int, mask=None, resume: bool = False,
                     stop_after_step=None):
    """Drive a compiled runner in checkpointed segments.

    ``ce`` is any runner satisfying the segment contract: ``carry0()``
    (or legacy ``_carry0``) builds the initial scan carry, and
    ``run_segment(overrides, keys, mask, carry, t0)`` scans rounds
    ``t0 .. t0+len(keys)`` from an explicit carry, returning ``(carry,
    outs)``.  :class:`CompiledExperiment`,
    :class:`repro.population.CompiledPopulation` and
    :class:`repro.train.fedllm.CompiledFedLLM` all implement it, so one
    checkpoint driver serves the MNIST engines and the streamed-LLM loop
    alike.  Every ``checkpoint_every`` rounds the scan carry and the
    accumulated outputs are snapshotted via ``train/checkpoint.py``
    (atomic single-file replace); with ``resume=True`` the run continues
    from the latest snapshot.  Because a scan splits into segments as
    pure-function composition, the resumed run is *bitwise-equal* to the
    uninterrupted one (pinned by tests/test_robust.py and
    tests/test_fedllm.py).

    ``stop_after_step`` simulates an interruption: the driver returns
    ``None`` after the first segment boundary at or past it (the snapshot
    is on disk; rerun with ``resume=True`` to finish).  Returns the outs
    dict (with final ``params``) when the run completes.

    Under ``jax.profiler.trace`` each segment shows the host spans
    ``repro:segment`` (the compiled segment and its outputs read back) and
    ``repro:checkpoint`` (the snapshot written).
    """
    steps = keys.shape[0]
    every = max(int(checkpoint_every), 1)
    path = os.path.join(checkpoint_dir, "engine_ckpt.npz")
    from repro.train.checkpoint import load_checkpoint, save_checkpoint

    carry = (ce.carry0() if hasattr(ce, "carry0") else ce._carry0())
    t0 = 0
    chunks: List[Dict[str, Any]] = []
    if resume and os.path.exists(path):
        loaded, t0 = load_checkpoint(path)
        carry = _restore_carry(carry, loaded["carry"])
        if t0 > 0:
            chunks = [jax.tree.map(np.asarray, loaded["outs"])]

    seg_fn = jax.jit(lambda ov, k, c, t: ce.run_segment(ov, k, mask, c, t))
    while t0 < steps:
        n = min(every, steps - t0)
        with span("segment"):
            carry, outs = seg_fn(overrides, keys[t0:t0 + n], carry,
                                 jnp.int32(t0))
            chunks.append(jax.tree.map(np.asarray, outs))
        t0 += n
        with span("checkpoint"):
            save_checkpoint(path, {"carry": carry,
                                   "outs": _concat_outs(chunks)}, step=t0)
        if (stop_after_step is not None and t0 >= stop_after_step
                and t0 < steps):
            return None
    outs = _concat_outs(chunks)
    outs["params"] = jax.tree.map(np.asarray, carry[0])
    return outs


def _subsample(outs, exp: Experiment) -> EngineRun:
    idx = eval_indices(exp.steps, exp.eval_every)
    accs = np.asarray(outs["acc"])
    losses = np.asarray(outs["loss"])
    mets = {k: np.asarray(v) for k, v in outs["metrics"].items()}
    return EngineRun(
        accs=[float(accs[i]) for i in idx],
        losses=[float(losses[i]) for i in idx],
        metrics=[{k: float(v[i]) for k, v in mets.items()} for i in idx],
        eval_steps=idx, all_accs=accs, all_losses=losses,
        params=outs.get("params"))


def run_compiled(x_dev: np.ndarray, y_dev: np.ndarray, x_test: np.ndarray,
                 y_test: np.ndarray, cfg: OTAConfig, steps: int,
                 lr: float = 1e-3, eval_every: int = 10, seed: int = 0,
                 optimizer: str = "adam", local_steps: int = 1,
                 local_lr: float = 0.1, momentum_correction: float = 0.0,
                 use_kernel: bool = False,
                 guard: Optional[guards.GuardConfig] = None,
                 checkpoint_dir: Optional[str] = None,
                 checkpoint_every: int = 0, resume: bool = False,
                 stop_after_step=None) -> Optional[EngineRun]:
    """Compiled replacement for ``run_federated``: same model, same
    schedule — one jitted scan instead of a Python loop.  At ``seed=0``
    the per-round key stream is ``run_federated``'s exactly
    (``PRNGKey(1000 + t)``), so ``accs`` / ``losses`` / ``metrics`` match
    ``FederatedRun``'s lists entry for entry (pinned by
    tests/test_experiments.py).  Nonzero ``seed`` shifts the stream to a
    disjoint key range for independent replicas — a knob the reference
    loop does not have (its ``seed`` argument never reaches the round
    keys), so cross-implementation parity holds at seed 0 only.

    ``guard`` enables the in-scan round guardrails
    (:class:`repro.robust.guards.GuardConfig`); ``checkpoint_dir`` +
    ``checkpoint_every`` switch to the segmented checkpoint/resume driver
    (:func:`run_checkpointed`) — with ``resume=True`` an interrupted run
    continues from its snapshot, bitwise-equal to the uninterrupted run.
    Returns ``None`` when ``stop_after_step`` interrupts the run."""
    exp = Experiment(cfg=cfg, steps=steps, lr=lr, eval_every=eval_every,
                     optimizer=optimizer, local_steps=local_steps,
                     local_lr=local_lr, momentum_correction=momentum_correction,
                     seed=seed, use_kernel=use_kernel, guard=guard)
    ce = CompiledExperiment(x_dev, y_dev, x_test, y_test, exp)
    keys = round_keys(steps, seed)
    if checkpoint_dir is not None and checkpoint_every > 0:
        outs = run_checkpointed(ce, {}, keys, checkpoint_dir=checkpoint_dir,
                                checkpoint_every=checkpoint_every,
                                resume=resume,
                                stop_after_step=stop_after_step)
        if outs is None:
            return None
    else:
        outs = jax.jit(ce.run)({}, keys)
        outs = jax.tree.map(np.asarray, outs)
    return _subsample(outs, exp)
