"""Sampled-cohort round engine: federated runs over M-large populations.

One round = draw availability -> sample a K-cohort -> gather banked error
state and cohort data -> run the existing scheme encode/MAC/decode on the
K rows only (via :func:`repro.experiments.engine.round_masked` with
injected cohort-indexed device keys and channel draw) -> scatter the
updated accumulators back.  The whole federated run is one ``jit(lax.scan)``
— the scan carry is ``(params, opt_state, banks)``; per-round temporaries
are O(K * d) plus O(M) scalars (keys/scores/masks), never O(M * d).

RNG layout: round t of seed 0 uses ``PRNGKey(1000 + t)`` (the engine's key
stream), salted per consumer — 0 MAC AWGN, 1 device encode, 2 channel draw
(shared with the dense drivers), plus the population's own salts
3 availability, 4 cohort sampling, 5 straggler latency (6 is the fault
trace, shared with the dense drivers — repro.robust.faults).  Device m's
encode
key is row m of ``split(fold_in(key, 1), M)`` and its channel row comes
from the full-M draw (:meth:`Scheme.cohort_channel_draw`), so a K == M
cohort with no churn/stragglers reproduces ``round_simulated`` /
``run_compiled`` bitwise — pinned by the ``population_full`` golden.

Traced per-round knobs (``avail_rate``, ``straggler_deadline``,
``k_active``, ``site_noise_scale``, ``backhaul_sigma2``) live as
attributes on :class:`CompiledPopulation` and are swapped per grid point
via :meth:`CompiledPopulation.with_overrides` — the same contract as
``Scheme.with_overrides`` — which is how
:func:`repro.experiments.sweep.run_population_sweep` vmaps whole grids
over them.
"""

from __future__ import annotations

import copy
from dataclasses import dataclass
from typing import Dict, Optional

import jax
import jax.flatten_util
import jax.numpy as jnp
import numpy as np

from repro.configs.base import OTAConfig
from repro.core import scheduling
from repro.core.schemes import MACContext, Scheme, get_scheme
from repro.data.partition import PopulationPartition
from repro.experiments.engine import (
    EngineRun, _subsample, round_keys, round_masked, run_checkpointed,
)
from repro.local.work import (
    LOCAL_OVERRIDE_ATTRS, LocalWork, get_local, local_device_grads,
)
from repro.optim.optim import Optimizer
from repro.robust import faults, guards
from repro.population import churn, stragglers
from repro.population.hierarchy import site_mac_sum
from repro.population.sampler import sample_cohort
from repro.population.state import (
    BankedState, PopulationConfig, gather_cohort, init_banks,
    init_population, scatter_cohort,
)
from repro.train.paper_repro import (
    accuracy, ce_loss, device_grads, flat_grad_fn, init_linear,
)

#: round-key salts owned by the population layer (0/1/2 belong to the MAC /
#: encode / channel-draw consumers, matching round_simulated)
SALT_AVAIL, SALT_SAMPLE, SALT_LATENCY = 3, 4, 5

#: CompiledPopulation attributes that ride the vmapped override path
POP_OVERRIDE_ATTRS = (
    "avail_rate",
    "straggler_deadline",
    "k_active",
    "site_noise_scale",
    "backhaul_sigma2",
)


class PopulationData:
    """Training data addressable by cohort.

    Two layouts behind one ``cohort_batch`` view: dense per-device tensors
    ``(M, B, dim)`` (small M — the legacy layout, used by the parity
    tests), or a sample pool ``(N, dim)`` plus a
    :class:`~repro.data.partition.PopulationPartition` whose arithmetic
    shard addressing materialises only the cohort's ``(K, B)`` rows inside
    the trace (large M — nothing (M, B)-sized ever exists).
    """

    def __init__(self, m, b, dim, n_classes, *, xd=None, yd=None, x=None,
                 y=None, part: Optional[PopulationPartition] = None):
        self.m, self.b, self.dim, self.n_classes = m, b, dim, n_classes
        self.xd, self.yd = xd, yd
        self.x, self.y, self.part = x, y, part

    @classmethod
    def from_dense(cls, x_dev, y_dev) -> "PopulationData":
        m, b, dim = x_dev.shape
        return cls(m, b, dim, int(np.max(y_dev)) + 1,
                   xd=jnp.asarray(x_dev), yd=jnp.asarray(y_dev))

    @classmethod
    def from_pool(cls, x, y, part: PopulationPartition) -> "PopulationData":
        if len(y) != part.n:
            raise ValueError(
                f"pool has {len(y)} samples, partition expects {part.n}")
        return cls(part.m, part.b, x.shape[-1], int(np.max(y)) + 1,
                   x=jnp.asarray(x), y=jnp.asarray(y), part=part)

    def cohort_batch(self, cohort: jnp.ndarray):
        """(K, B, dim), (K, B) batches of the cohort's devices (traced)."""
        if self.xd is not None:
            return self.xd[cohort], self.yd[cohort]
        idx = self.part.sample_indices(cohort)
        return self.x[idx], self.y[idx]


def population_round(scheme: Scheme, banks: BankedState, cohort: jnp.ndarray,
                     mask: jnp.ndarray, grads: jnp.ndarray, step,
                     key: jnp.ndarray, ctx: MACContext, m_total: int, *,
                     gains=None, sites=None, n_sites: int = 1,
                     site_noise_scale=1.0, backhaul_sigma2=0.0,
                     site_trim_frac: float = 0.0, draw=None, sched=None):
    """One sampled-cohort aggregation round.

    cohort: (K,) sorted device ids; mask: (K,) 0/1 participation (churn,
    stragglers, k_active already folded in); grads: (K, d) cohort
    gradients.  ``gains``/``sites`` are the cohort rows of the population's
    large-scale gain / edge-site arrays.  Returns
    ``(ghat, new_banks, metrics)``.

    The round is :func:`round_masked` with cohort-addressed injections:
    device keys are the cohort rows of the full-M key split, the channel
    draw is the cohort view of the full-M realisation, large-scale gains
    multiply the received-power factor, and (for n_sites > 1) the MAC is
    the hierarchical two-stage sum.  All injections degrade bitwise to the
    dense driver at K == M with the defaults (identity gather, gains 1.0,
    flat MAC).

    ``draw`` / ``sched`` pre-empt the channel realisation and inject a
    subband-scheduler transmit set (``CompiledPopulation`` evaluates the
    cohort draw itself so the scheduler can rank the round's gains; a
    caller-supplied ``draw`` must already include any large-scale
    ``gains`` composition — the in-place multiply is skipped).
    """
    deltas = gather_cohort(banks, cohort)
    dev_keys = jax.random.split(jax.random.fold_in(key, 1), m_total)[cohort]
    if draw is None:
        draw = scheme.cohort_channel_draw(jax.random.fold_in(key, 2), step,
                                          cohort, m_total, mask=mask > 0)
        if gains is not None:
            draw = draw._replace(p_factor=draw.p_factor * gains)
    fault = None
    if scheme.robust_on:
        # the cohort's rows of the full-population fault trace — a K < M
        # cohort sees exactly the faults the full simulation would have
        # dealt those devices (matching the channel-draw contract)
        fault = scheme.cohort_fault_draw(
            jax.random.fold_in(key, faults.SALT_FAULT), step, cohort,
            m_total)
    mac = None
    if n_sites > 1:
        if sites is None:
            raise ValueError("n_sites > 1 needs the cohort's site ids")

        def mac(frames, mac_key, sigma2):
            return site_mac_sum(frames, sites, n_sites, mac_key, sigma2,
                                site_noise_scale=site_noise_scale,
                                backhaul_sigma2=backhaul_sigma2,
                                site_trim_frac=site_trim_frac)

    ghat, new_deltas, metrics = round_masked(scheme, grads, deltas, step,
                                             key, mask, ctx,
                                             dev_keys=dev_keys, draw=draw,
                                             mac=mac, fault=fault,
                                             sched=sched)
    banks = scatter_cohort(banks, cohort, new_deltas)
    metrics["cohort_frac"] = jnp.sum(mask) / cohort.shape[0]
    return ghat, banks, metrics


@dataclass(frozen=True)
class PopulationExperiment:
    """Static description of one population training configuration."""
    cfg: OTAConfig
    pop: PopulationConfig
    steps: int
    lr: float = 1e-3
    eval_every: int = 10
    optimizer: str = "adam"
    local_steps: int = 1
    local_lr: float = 0.1
    seed: int = 0
    guard: Optional[guards.GuardConfig] = None


class CompiledPopulation:
    """Compile-once runner: one population configuration, one scan.

    :meth:`run` is a pure traced function — ``jit``/``vmap`` it freely.
    ``overrides`` splits between the scheme (``p_sched``/``q_sched`` and
    the channel scalars, via ``Scheme.with_overrides``) and the runner's
    own traced knobs (``POP_OVERRIDE_ATTRS``, via :meth:`with_overrides`).
    """

    def __init__(self, data: PopulationData, x_test, y_test,
                 exp: PopulationExperiment):
        pop = exp.pop
        if data.m != pop.m_total:
            raise ValueError(
                f"data addresses {data.m} devices, population has "
                f"{pop.m_total}")
        self.exp = exp
        self.data = data
        params = init_linear(data.dim, data.n_classes,
                             jax.random.PRNGKey(exp.seed))
        flat0, self.unravel = jax.flatten_util.ravel_pytree(params)
        self.d = flat0.shape[0]
        self.params0 = params
        self.scheme = get_scheme(exp.cfg, self.d, pop.k_cohort)
        self.localwork = get_local(exp.cfg, exp.local_lr)
        if not self.localwork.identity and exp.local_steps > 1:
            raise ValueError(
                "local_steps > 1 (the legacy FedAvg path) conflicts with "
                f"the configured local algorithm {exp.cfg.local!r} at "
                f"local_epochs={exp.cfg.local_epochs}; use cfg.local_epochs")
        self._grad_fn = flat_grad_fn(self.unravel)
        self.opt = Optimizer(name=exp.optimizer, lr=exp.lr)
        self.xt, self.yt = jnp.asarray(x_test), jnp.asarray(y_test)
        self.ctx = MACContext(m=pop.k_cohort, fading=exp.cfg.fading,
                              csi=self.scheme.csi)
        self.pstate0 = init_population(
            pop, self.d, exp.steps, dtype=jnp.dtype(exp.cfg.state_dtype))
        # FedDyn duals are persistent per-device state, banked exactly like
        # the error accumulators — a cold slot reads dual = 0, which IS the
        # algorithm's fresh-device initialisation, so direct-mapped eviction
        # degrades a device to "fresh", never to "wrong" (DESIGN.md §11).
        # Kept float32 regardless of state_dtype: duals integrate alpha-
        # scaled drift and are never renormalised by error feedback.
        self.dual_banks0 = None
        if self.localwork.has_dual:
            cap = pop.capacity if pop.capacity else pop.m_total
            self.dual_banks0 = init_banks(cap, min(pop.bank_size, cap),
                                          self.d, jnp.float32)
        # proportional-fair average-rate state: one scalar per device,
        # banked exactly like the duals (cold slot reads 0 == fresh device)
        self.scheduler = scheduling.get_scheduler(exp.cfg)
        self.sched_banks0 = None
        if self._sched_state:
            cap = pop.capacity if pop.capacity else pop.m_total
            self.sched_banks0 = init_banks(cap, min(pop.bank_size, cap),
                                           1, jnp.float32)
        # traced per-round knobs — vmappable via with_overrides
        self.avail_rate = jnp.float32(pop.avail_rate)
        self.straggler_deadline = jnp.float32(pop.straggler_deadline)
        self.k_active = jnp.float32(pop.k_cohort)
        self.site_noise_scale = jnp.float32(pop.site_noise_scale)
        self.backhaul_sigma2 = jnp.float32(pop.backhaul_sigma2)

    def with_overrides(self, **attrs) -> "CompiledPopulation":
        """Shallow copy with traced knobs replaced (the sweep hook)."""
        new = copy.copy(self)
        for name, value in attrs.items():
            if name not in POP_OVERRIDE_ATTRS:
                raise AttributeError(
                    f"unknown population override {name!r}; traced knobs: "
                    f"{POP_OVERRIDE_ATTRS}")
            setattr(new, name, value)
        return new

    # ------------------------------------------------------------- pieces
    @property
    def _sched_state(self) -> bool:
        return self.scheduler is not None and self.scheduler.has_state

    def _carry0(self):
        carry = (self.params0, self.opt.init(self.params0),
                 self.pstate0.banks)
        if self.localwork.has_dual:
            carry = carry + (self.dual_banks0,)
        if self._sched_state:
            carry = carry + (self.sched_banks0,)
        if self.exp.guard is not None:
            carry = carry + (guards.init_guard_state(),)
        return carry

    def _round(self, sch: Scheme, lw: LocalWork, carry, t, key):
        params, opt_state, banks = carry[:3]
        dual_banks = carry[3] if lw.has_dual else None
        sched_banks = (carry[3 + int(lw.has_dual)] if self._sched_state
                       else None)
        gstate = carry[-1] if self.exp.guard is not None else None
        old_extras = ((banks,) + ((dual_banks,) if lw.has_dual else ())
                      + ((sched_banks,) if self._sched_state else ()))
        exp, pop, ps = self.exp, self.exp.pop, self.pstate0
        avail = churn.availability(ps.arrival, ps.departure, t,
                                   jax.random.fold_in(key, SALT_AVAIL),
                                   self.avail_rate)
        cohort, member, rank = sample_cohort(
            jax.random.fold_in(key, SALT_SAMPLE), avail, pop.k_cohort)
        lat = stragglers.latencies(jax.random.fold_in(key, SALT_LATENCY),
                                   ps.speed[cohort])
        mask = (member
                & (rank.astype(jnp.float32) < self.k_active)
                & stragglers.deadline_mask(lat, self.straggler_deadline))
        xk, yk = self.data.cohort_batch(cohort)
        if lw.identity:
            # the pre-axis jaxpr, byte-for-byte — pins the goldens
            grads, _ = device_grads(
                params, self.unravel, xk, yk,
                jnp.zeros((pop.k_cohort, self.d), jnp.float32),
                local_steps=exp.local_steps, local_lr=exp.local_lr)
        else:
            duals = (gather_cohort(dual_banks, cohort) if lw.has_dual
                     else None)
            grads, _, new_duals = local_device_grads(
                lw, self._grad_fn, params, xk, yk,
                jnp.zeros((pop.k_cohort, self.d), jnp.float32), duals)
            if lw.has_dual:
                # masked-out cohort members did not run this round: their
                # dual must not evolve (the keep-rule round_masked applies
                # to the error banks); the scatter re-writes the gathered
                # value, claiming the slot with unchanged contents
                new_duals = jnp.where(mask[:, None], new_duals, duals)
                dual_banks = scatter_cohort(dual_banks, cohort, new_duals)
        draw = sched = None
        if self.scheduler is not None:
            # evaluate the cohort draw here so the scheduler ranks this
            # round's effective gains (same salted key population_round
            # would use — XLA sees one draw either way)
            draw = sch.cohort_channel_draw(jax.random.fold_in(key, 2), t,
                                           cohort, pop.m_total, mask=mask)
            draw = draw._replace(p_factor=draw.p_factor * ps.gains[cohort])
            sstate = (gather_cohort(sched_banks, cohort)[:, 0]
                      if self._sched_state else None)
            sched, new_sstate = scheduling.schedule(
                self.scheduler,
                jax.random.fold_in(key, scheduling.SALT_SCHED), t,
                draw.p_factor, sch.n_subbands, state=sstate, mask=mask)
            if self._sched_state:
                # masked cohort rows keep their banked average (the dual
                # keep-rule); live-but-unscheduled rows decay — that decay
                # IS proportional fairness
                new_sstate = jnp.where(mask, new_sstate, sstate)
                sched_banks = scatter_cohort(sched_banks, cohort,
                                             new_sstate[:, None])
        ghat, banks, met = population_round(
            sch, banks, cohort, mask.astype(jnp.float32), grads, t, key,
            self.ctx, pop.m_total, gains=ps.gains[cohort],
            sites=ps.site[cohort], n_sites=pop.n_sites,
            site_noise_scale=self.site_noise_scale,
            backhaul_sigma2=self.backhaul_sigma2,
            site_trim_frac=pop.site_trim_frac, draw=draw, sched=sched)
        extras = ((banks,) + ((dual_banks,) if lw.has_dual else ())
                  + ((sched_banks,) if self._sched_state else ()))
        if exp.guard is not None:
            params, opt_state, extras, gstate, loss, gmet = (
                guards.guarded_step(
                    exp.guard, gstate, self.opt, params, opt_state, ghat,
                    self.unravel, extras=extras, old_extras=old_extras,
                    loss_fn=lambda p: ce_loss(p, self.xt, self.yt)))
            out = {"acc": accuracy(params, self.xt, self.yt),
                   "loss": loss, "metrics": {**met, **gmet}}
            return (params, opt_state) + tuple(extras) + (gstate,), out
        params, opt_state = self.opt.apply(params, self.unravel(ghat),
                                           opt_state)
        out = {"acc": accuracy(params, self.xt, self.yt),
               "loss": ce_loss(params, self.xt, self.yt),
               "metrics": met}
        return (params, opt_state) + extras, out

    # ------------------------------------------------------- traced entry
    def run_segment(self, overrides: Dict[str, jnp.ndarray],
                    keys: jnp.ndarray, mask, carry, t0):
        """Scan rounds ``t0 .. t0 + len(keys)`` from an explicit carry.

        The checkpoint/resume building block (the population analogue of
        :meth:`CompiledExperiment.run_segment` — same contract, so
        :func:`repro.experiments.engine.run_checkpointed` drives both).
        ``mask`` is accepted for signature compatibility and must be None:
        populations draw their own participation masks per round.  Returns
        ``(carry, outs)``.
        """
        if mask is not None:
            raise ValueError("population runs draw their own masks")
        pop_ov = {k: v for k, v in overrides.items()
                  if k in POP_OVERRIDE_ATTRS}
        lw_ov = {k: v for k, v in overrides.items()
                 if k in LOCAL_OVERRIDE_ATTRS}
        sch_ov = {k: v for k, v in overrides.items()
                  if k not in POP_OVERRIDE_ATTRS
                  and k not in LOCAL_OVERRIDE_ATTRS}
        runner = self.with_overrides(**pop_ov) if pop_ov else self
        sch = (self.scheme.with_overrides(**sch_ov) if sch_ov
               else self.scheme)
        lw = (self.localwork.with_overrides(**lw_ov) if lw_ov
              else self.localwork)

        def body(carry, inp):
            t, key = inp
            return runner._round(sch, lw, carry, t, key)

        ts = t0 + jnp.arange(keys.shape[0])
        return jax.lax.scan(body, carry, (ts, keys))

    def run(self, overrides: Dict[str, jnp.ndarray], keys: jnp.ndarray):
        """One full run. Returns {"acc": (steps,), "loss": (steps,),
        "metrics": {...: (steps,)}, "params": pytree}."""
        carry, outs = self.run_segment(overrides, keys, None,
                                       self._carry0(), jnp.int32(0))
        outs["params"] = carry[0]
        return outs


def run_population(data: PopulationData, x_test, y_test, cfg: OTAConfig,
                   pop: PopulationConfig, steps: int, lr: float = 1e-3,
                   eval_every: int = 10, seed: int = 0,
                   optimizer: str = "adam", local_steps: int = 1,
                   local_lr: float = 0.1,
                   guard: Optional[guards.GuardConfig] = None,
                   checkpoint_dir: Optional[str] = None,
                   checkpoint_every: int = 0, resume: bool = False,
                   stop_after_step=None) -> Optional[EngineRun]:
    """``run_compiled`` for populations: one jitted scan over sampled
    cohorts.  At K == M_total with the churn/straggler defaults the run is
    bitwise ``run_compiled`` on the same device tensors (the RNG layout
    and MAC order match; pinned by tests/test_population.py).

    ``guard`` and the ``checkpoint_*`` knobs mirror ``run_compiled``:
    in-scan round guardrails, and the segmented checkpoint/resume driver
    (returns ``None`` when ``stop_after_step`` interrupts the run)."""
    exp = PopulationExperiment(cfg=cfg, pop=pop, steps=steps, lr=lr,
                               eval_every=eval_every, optimizer=optimizer,
                               local_steps=local_steps, local_lr=local_lr,
                               seed=seed, guard=guard)
    cp = CompiledPopulation(data, x_test, y_test, exp)
    keys = round_keys(steps, seed)
    if checkpoint_dir is not None and checkpoint_every > 0:
        outs = run_checkpointed(cp, {}, keys, checkpoint_dir=checkpoint_dir,
                                checkpoint_every=checkpoint_every,
                                resume=resume,
                                stop_after_step=stop_after_step)
        if outs is None:
            return None
    else:
        outs = jax.jit(cp.run)({}, keys)
    outs = jax.tree.map(np.asarray, outs)
    return _subsample(outs, exp)
