"""Sweep grids over the compiled engine: vmap what traces, compile the rest.

A sweep axis is either *schedule-shaped* — its value enters the round as an
array of per-step scalars, so a whole grid of values rides one trace as a
vmapped batch — or *shape-defining* — it changes array shapes or the
compiled structure (projector size, scheme class), so each value needs its
own XLA program (still a single scan-over-rounds each, never a Python
per-round loop).

vmapped axes (``VMAP_AXES``):

``p_avg``          average power P-bar  -> the (T,) power schedule array
``power_schedule`` schedule shape       -> the same (T,) array
``seed``           round-key stream     -> the (T, key) array
``m_active``       device count         -> a traced participation mask over
                                           M_pad padded devices
                                           (:func:`engine.round_masked`)

plus the channel-model scalars (``SCALAR_VMAP_AXES``): ``csi_err_var``,
``fading_threshold``, ``fading_rho``, and the geometry/scheduling trio
``cell_radius`` / ``path_loss_exp`` / ``n_subbands`` enter the round as one
traced scalar each (a multiply or compare inside the scheme's channel draw
or the subband cutoff), so a whole CSI-error / truncation / correlation /
cell-size / subband-budget grid rides one vmapped program.
The fault/robustness rates (``ROBUST_VMAP_AXES``) vmap the same way —
sweeping one auto-promotes the config to ``robust=True`` so the (static)
fault path is compiled in for the whole grid.

Everything else (``scheme``, ``s_frac``, ``k_frac``, ``projection``,
``amp_iters``, ``sigma2``, ...) is an ``OTAConfig`` field swept statically:
the grid is grouped by static combo, one compile per combo, and the
vmapped sub-grid runs inside it.  For the digital schemes the per-step bit
budget ``q_t`` is host-precomputed per grid point and vmapped alongside the
power schedule (the static ``q_max`` bound is shared across the grid —
``top_k``'s q-th value is invariant to computing extra entries, so results
are bitwise identical to per-point bounds).
"""
from __future__ import annotations

import dataclasses
import itertools
import time
from dataclasses import dataclass
from typing import Any, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import OTAConfig
from repro.core import power
from repro.experiments.engine import (
    CompiledExperiment, Experiment, eval_indices, round_keys,
)
from repro.local.work import LOCAL_OVERRIDE_ATTRS

#: axes realised as vmapped per-point arrays on one trace
VMAP_AXES = ("p_avg", "power_schedule", "seed", "m_active")

#: OTAConfig fields that enter the round as a single traced scalar (a
#: compare or multiply inside the channel draw) — vmapped like the schedule
#: axes, but realised as a (G,) stack of per-point values swapped onto the
#: scheme via ``with_overrides`` (the attribute of the same name, set by
#: ``Scheme.__init__``).  docs/DESIGN.md §8 records why these are
#: data-like while ``fading_process`` / ``fading_window`` / ``ps_antennas``
#: are structure-defining and stay static.  The geometry/scheduling trio
#: (``cell_radius``, ``path_loss_exp``, ``n_subbands`` — DESIGN.md §12)
#: follows the same rule: each is one multiply or compare on a fixed
#: program, while ``geometry`` / ``scheduler`` select program structure
#: and stay static axes.
SCALAR_VMAP_AXES = ("csi_err_var", "fading_threshold", "fading_rho",
                    "cell_radius", "path_loss_exp", "n_subbands")

#: population knobs that enter the round as one traced scalar each
#: (compares/multiplies inside the cohort mask and the site MAC), swapped
#: onto the CompiledPopulation runner via its with_overrides — vmapped like
#: the channel scalars.  ``k_cohort`` / ``n_sites`` / ``capacity`` are
#: shape-defining and stay static (docs/DESIGN.md §9).
POP_VMAP_AXES = ("avail_rate", "straggler_deadline", "k_active",
                 "site_noise_scale", "backhaul_sigma2")

#: fault/robustness *rates* — traced scalars on the scheme (compares and
#: multiplies inside the fault draw / aggregator / power clip), so a whole
#: Byzantine-fraction or fault-rate grid rides one vmapped program.
#: Sweeping any of them auto-promotes the base config to ``robust=True``
#: (the static gate that compiles the fault path in; with all rates zero
#: that path is bitwise-neutral — pinned by tests/test_robust.py).  The
#: fault/aggregator *kinds* (``byz_attack``, ``fault_kind``,
#: ``aggregator``, ``clip_power``) select program structure and stay
#: static axes (docs/DESIGN.md §10).
ROBUST_VMAP_AXES = ("byzantine_frac", "fault_rate", "erasure_prob",
                    "byz_scale", "trim_frac", "norm_cap", "power_cap")

#: local-compute knobs (repro.local) — traced scalars on the LocalWork
#: carrier, swapped per grid point via ``LocalWork.with_overrides``.  The
#: epoch count is traced (a ``e < local_epochs`` cutoff inside a scan of
#: static length ``max_epochs``), so a whole (E, mu, alpha) grid rides one
#: vmapped program; sweeping ``local_epochs`` bumps the static
#: ``max_epochs`` bound to the grid maximum before tracing (the ``q_max``
#: pattern — discarded epochs leave the carry untouched bitwise).  The
#: algorithm *kind* (``local``) selects program structure and stays a
#: static axis (docs/DESIGN.md §11).
LOCAL_VMAP_AXES = LOCAL_OVERRIDE_ATTRS


@dataclass
class SweepResult:
    """One record per grid point, ``accs``/``final_acc`` at eval steps —
    the same reading ``benchmarks.common.run_series`` extracts from a
    looped ``FederatedRun``."""
    records: List[Dict[str, Any]]
    eval_steps: np.ndarray
    steps: int
    wall_s: float

    def record(self, **axis_values) -> Dict[str, Any]:
        """The unique record matching the given axis values."""
        hits = [r for r in self.records
                if all(r[k] == v for k, v in axis_values.items())]
        if len(hits) != 1:
            raise KeyError(f"{axis_values} matched {len(hits)} records")
        return hits[0]


def _validate_axes(axes: Dict[str, Sequence], base: OTAConfig) -> None:
    cfg_fields = {f.name for f in dataclasses.fields(OTAConfig)}
    vmapped = VMAP_AXES + SCALAR_VMAP_AXES + ROBUST_VMAP_AXES \
        + LOCAL_VMAP_AXES
    for name, values in axes.items():
        if name not in vmapped and name not in cfg_fields:
            raise KeyError(
                f"unknown sweep axis {name!r}: vmapped axes are "
                f"{vmapped}, static axes are OTAConfig fields")
        if not len(list(values)):
            raise ValueError(f"sweep axis {name!r} is empty")


def run_sweep(dev_data, test_data, base: OTAConfig,
              axes: Dict[str, Sequence], *, steps: int, lr: float = 1e-3,
              eval_every: int = 10, optimizer: str = "adam", seed: int = 0,
              local_lr: float = 0.1) -> SweepResult:
    """Run the cartesian grid of ``axes`` over ``base``.

    dev_data = (x_dev (M, B, dim), y_dev), test_data = (x_test, y_test).
    For an ``m_active`` axis the device tensors are the M_pad padding; every
    value must be <= M_pad.
    """
    (xd, yd), (xt, yt) = dev_data, test_data
    axes = {k: list(v) for k, v in axes.items()}
    _validate_axes(axes, base)
    if any(k in ROBUST_VMAP_AXES for k in axes):
        # the swept rates are traced, but the fault path itself is a
        # static gate — compile it in for the whole grid
        base = dataclasses.replace(base, robust=True)
    m_pad = xd.shape[0]
    masked = "m_active" in axes
    if masked and max(axes["m_active"]) > m_pad:
        raise ValueError(f"m_active values must be <= M_pad = {m_pad}")

    vmapped = VMAP_AXES + SCALAR_VMAP_AXES + ROBUST_VMAP_AXES \
        + LOCAL_VMAP_AXES
    static_names = [k for k in axes if k not in vmapped]
    vmap_names = [k for k in axes if k in vmapped]
    records: List[Dict[str, Any]] = []
    t0 = time.time()

    for static_vals in itertools.product(*[axes[k] for k in static_names]):
        static_d = dict(zip(static_names, static_vals))
        cfg = dataclasses.replace(base, **static_d)
        exp = Experiment(cfg=cfg, steps=steps, lr=lr, eval_every=eval_every,
                         optimizer=optimizer, seed=seed, local_lr=local_lr)
        ce = CompiledExperiment(xd, yd, xt, yt, exp)
        digital = hasattr(ce.scheme, "q_sched")
        if "local_epochs" in axes:
            # the static scan bound must cover the whole grid (the q_max
            # pattern): points at E < max run the extra epochs as bitwise
            # no-ops behind the traced cutoff
            ce.localwork.max_epochs = max(int(max(axes["local_epochs"])), 1)

        grid = ([dict(zip(vmap_names, vals)) for vals in itertools.product(
            *[axes[k] for k in vmap_names])] if vmap_names else [{}])

        # --- per-point schedule arrays (host precompute) -----------------
        scalar_names = [k for k in vmap_names
                        if k in SCALAR_VMAP_AXES or k in ROBUST_VMAP_AXES
                        or k in LOCAL_VMAP_AXES]
        p_rows, q_rows, key_rows, mask_rows = [], [], [], []
        scalar_rows: Dict[str, List[float]] = {k: [] for k in scalar_names}
        for point in grid:
            p_avg = point.get("p_avg", cfg.p_avg)
            sched = point.get("power_schedule", cfg.power_schedule)
            m_eff = point.get("m_active", m_pad)
            p_np = power.schedule_array(cfg.total_steps, p_avg, sched)
            p_rows.append(np.asarray(p_np, np.float32))
            if digital:
                # the scheme's own budget/cap rule, with this point's
                # effective device count
                q_rows.append(ce.scheme.build_q_schedule(m_eff, p_np))
            key_rows.append(round_keys(steps, point.get("seed", seed)))
            if masked:
                mask_rows.append(
                    (np.arange(m_pad) < m_eff).astype(np.float32))
            for k in scalar_names:
                scalar_rows[k].append(point[k])

        overrides = {"p_sched": jnp.asarray(np.stack(p_rows))}
        for k in scalar_names:
            overrides[k] = jnp.asarray(scalar_rows[k], jnp.float32)
        if digital:
            q_grid = np.stack(q_rows)
            ce.scheme.q_max = int(max(int(q_grid.max()), 1))
            overrides["q_sched"] = jnp.asarray(q_grid, jnp.int32)
        keys = jnp.stack(key_rows)

        # --- one XLA program for the whole vmapped sub-grid --------------
        ov_axes = {k: 0 for k in overrides}
        if masked:
            masks = jnp.asarray(np.stack(mask_rows))
            outs = jax.jit(jax.vmap(ce.run_masked,
                                    in_axes=(ov_axes, 0, 0)))(
                overrides, keys, masks)
        else:
            outs = jax.jit(jax.vmap(ce.run, in_axes=(ov_axes, 0)))(
                overrides, keys)
        outs.pop("params")
        outs = jax.tree.map(np.asarray, outs)

        idx = eval_indices(steps, eval_every)
        for g, point in enumerate(grid):
            accs = outs["acc"][g]
            rec: Dict[str, Any] = {**static_d, **point}
            rec["accs"] = [float(accs[i]) for i in idx]
            rec["losses"] = [float(outs["loss"][g][i]) for i in idx]
            rec["metrics"] = [
                {k: float(v[g][i]) for k, v in outs["metrics"].items()}
                for i in idx]
            rec["final_acc"] = rec["accs"][-1]
            records.append(rec)

    wall = time.time() - t0
    us = wall / max(len(records) * steps, 1) * 1e6
    for rec in records:
        rec["us_per_call"] = us
    return SweepResult(records=records, eval_steps=eval_indices(
        steps, eval_every), steps=steps, wall_s=wall)


def run_population_sweep(data, test_data, base: OTAConfig, base_pop,
                         axes: Dict[str, Sequence], *, steps: int,
                         lr: float = 1e-3, eval_every: int = 10,
                         optimizer: str = "adam", seed: int = 0,
                         local_lr: float = 0.1) -> SweepResult:
    """:func:`run_sweep` over the sampled-cohort population engine.

    ``data`` is a :class:`repro.population.PopulationData`; ``base_pop`` a
    :class:`repro.population.PopulationConfig`.  Vmapped axes are
    ``p_avg`` / ``power_schedule`` / ``seed``, the channel scalars
    (``SCALAR_VMAP_AXES``) and the population scalars (``POP_VMAP_AXES``);
    static axes are any OTAConfig *or* PopulationConfig field (grouped by
    combo, one compile each).  ``m_active`` is a padded-M dense-engine
    axis — its sampled-cohort analogue here is ``k_active`` (every value
    must be <= the static ``k_cohort``).
    """
    from repro.population.engine import (
        CompiledPopulation, PopulationExperiment,
    )
    from repro.population.state import PopulationConfig

    (xt, yt) = test_data
    axes = {k: list(v) for k, v in axes.items()}
    if any(k in ROBUST_VMAP_AXES for k in axes):
        base = dataclasses.replace(base, robust=True)
    cfg_fields = {f.name for f in dataclasses.fields(OTAConfig)}
    pop_fields = {f.name for f in dataclasses.fields(PopulationConfig)}
    vmapped = ("p_avg", "power_schedule", "seed") + SCALAR_VMAP_AXES \
        + POP_VMAP_AXES + ROBUST_VMAP_AXES + LOCAL_VMAP_AXES
    for name, values in axes.items():
        if name == "m_active":
            raise KeyError(
                "m_active is a dense-engine axis; the population engine "
                "sweeps the cohort via k_active")
        if name not in vmapped and name not in cfg_fields \
                and name not in pop_fields:
            raise KeyError(
                f"unknown sweep axis {name!r}: vmapped axes are {vmapped}, "
                "static axes are OTAConfig/PopulationConfig fields")
        if not len(values):
            raise ValueError(f"sweep axis {name!r} is empty")
    if "k_active" in axes and max(axes["k_active"]) > base_pop.k_cohort:
        raise ValueError(
            f"k_active values must be <= k_cohort = {base_pop.k_cohort}")

    static_names = [k for k in axes if k not in vmapped]
    vmap_names = [k for k in axes if k in vmapped]
    records: List[Dict[str, Any]] = []
    t0 = time.time()

    for static_vals in itertools.product(*[axes[k] for k in static_names]):
        static_d = dict(zip(static_names, static_vals))
        cfg = dataclasses.replace(
            base, **{k: v for k, v in static_d.items() if k in cfg_fields})
        pop = dataclasses.replace(
            base_pop,
            **{k: v for k, v in static_d.items() if k in pop_fields})
        exp = PopulationExperiment(cfg=cfg, pop=pop, steps=steps, lr=lr,
                                   eval_every=eval_every,
                                   optimizer=optimizer, seed=seed,
                                   local_lr=local_lr)
        cp = CompiledPopulation(data, xt, yt, exp)
        digital = hasattr(cp.scheme, "q_sched")
        if "local_epochs" in axes:
            # static scan bound covers the grid (see run_sweep)
            cp.localwork.max_epochs = max(int(max(axes["local_epochs"])), 1)

        grid = ([dict(zip(vmap_names, vals)) for vals in itertools.product(
            *[axes[k] for k in vmap_names])] if vmap_names else [{}])

        scalar_names = [k for k in vmap_names
                        if k in SCALAR_VMAP_AXES or k in POP_VMAP_AXES
                        or k in ROBUST_VMAP_AXES or k in LOCAL_VMAP_AXES]
        p_rows, q_rows, key_rows = [], [], []
        scalar_rows: Dict[str, List[float]] = {k: [] for k in scalar_names}
        for point in grid:
            p_np = power.schedule_array(
                cfg.total_steps, point.get("p_avg", cfg.p_avg),
                point.get("power_schedule", cfg.power_schedule))
            p_rows.append(np.asarray(p_np, np.float32))
            if digital:
                # the digital bit budget tracks the point's effective
                # cohort (the k_active analogue of m_active's q rule)
                q_rows.append(cp.scheme.build_q_schedule(
                    int(point.get("k_active", pop.k_cohort)), p_np))
            key_rows.append(round_keys(steps, point.get("seed", seed)))
            for k in scalar_names:
                scalar_rows[k].append(point[k])

        overrides = {"p_sched": jnp.asarray(np.stack(p_rows))}
        for k in scalar_names:
            overrides[k] = jnp.asarray(scalar_rows[k], jnp.float32)
        if digital:
            q_grid = np.stack(q_rows)
            cp.scheme.q_max = int(max(int(q_grid.max()), 1))
            overrides["q_sched"] = jnp.asarray(q_grid, jnp.int32)
        keys = jnp.stack(key_rows)

        ov_axes = {k: 0 for k in overrides}
        outs = jax.jit(jax.vmap(cp.run, in_axes=(ov_axes, 0)))(
            overrides, keys)
        outs.pop("params")
        outs = jax.tree.map(np.asarray, outs)

        idx = eval_indices(steps, eval_every)
        for g, point in enumerate(grid):
            rec: Dict[str, Any] = {**static_d, **point}
            rec["accs"] = [float(outs["acc"][g][i]) for i in idx]
            rec["losses"] = [float(outs["loss"][g][i]) for i in idx]
            rec["metrics"] = [
                {k: float(v[g][i]) for k, v in outs["metrics"].items()}
                for i in idx]
            rec["final_acc"] = rec["accs"][-1]
            records.append(rec)

    wall = time.time() - t0
    us = wall / max(len(records) * steps, 1) * 1e6
    for rec in records:
        rec["us_per_call"] = us
    return SweepResult(records=records, eval_steps=eval_indices(
        steps, eval_every), steps=steps, wall_s=wall)
