"""Round guardrails inside the jit scan: clamp, skip, back off — no host.

A single NaN decode or a diverging loss normally poisons every subsequent
round of a compiled run silently.  :func:`guarded_step` wraps the
PS-side optimizer application with three traced safety rails, all of
which stay inside one ``jit(lax.scan)`` (every decision is a ``where``
select on the carry — no host callback, no trace break, no retry loop):

* **update-norm clamp** (``update_clip > 0``): the decoded update's L2
  norm is capped before it reaches the optimizer.
* **finite check + skip-round fallback** (``skip_nonfinite``): if the
  decoded update is non-finite, the round is skipped — params, optimizer
  state, and every accumulator in ``extras`` are carried unchanged.
* **divergence detector + LR backoff** (``divergence_factor > 0``): if
  the post-step eval loss exceeds ``divergence_factor *`` the last
  accepted loss (or goes non-finite), the step is reverted and the
  traced ``lr_scale`` is multiplied by ``lr_backoff``; a cooldown
  counter then suppresses further backoffs for ``cooldown`` rounds so
  one bad stretch cannot collapse the LR geometrically.

``lr_scale`` is applied by *blending the applied step*
(``p0 + lr_scale * (p1 - p0)``) rather than scaling the gradient —
Adam's update is invariant to gradient scaling, so a gradient-side
scale would be a no-op exactly when the backoff is needed most.  The
blend is structurally gated: a guard-free engine never builds it, so
default runs stay bitwise-identical (``p0 + 1.0*(p1 - p0) != p1``
bitwise in IEEE arithmetic).

Engine wiring: ``Experiment.guard`` / ``PopulationExperiment.guard``
take a :class:`GuardConfig`; the scan carry then grows a
:class:`GuardState` tail and the per-round metrics gain
``guard_lr_scale`` / ``guard_skipped`` / ``guard_backoff`` columns.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.tracing import stage


@dataclass(frozen=True)
class GuardConfig:
    """Static guardrail configuration (trace structure; 0 disables a rail)."""
    update_clip: float = 0.0       # L2 cap on the decoded update (0 = off)
    skip_nonfinite: bool = True    # skip rounds with NaN/Inf updates
    divergence_factor: float = 0.0  # revert if loss > factor * last (0 = off)
    lr_backoff: float = 0.5        # lr_scale multiplier on divergence
    cooldown: int = 5              # rounds between successive backoffs


class GuardState(NamedTuple):
    """Traced guardrail state riding the scan carry."""
    lr_scale: jnp.ndarray          # current LR backoff multiplier
    cooldown: jnp.ndarray          # rounds until the next backoff may fire
    prev_loss: jnp.ndarray         # loss at the last accepted step
    skips: jnp.ndarray             # cumulative skipped rounds
    backoffs: jnp.ndarray          # cumulative LR backoffs


def init_guard_state() -> GuardState:
    return GuardState(lr_scale=jnp.float32(1.0),
                      cooldown=jnp.float32(0.0),
                      prev_loss=jnp.float32(jnp.inf),
                      skips=jnp.float32(0.0),
                      backoffs=jnp.float32(0.0))


def _select(ok, new: Any, old: Any) -> Any:
    """Traced pytree select: ``new`` where ok, else ``old``."""
    return jax.tree.map(lambda n, o: jnp.where(ok, n, o), new, old)


def guarded_step(guard: GuardConfig, gstate: GuardState, opt, params,
                 opt_state, ghat: jnp.ndarray, unravel, extras: Any,
                 old_extras: Any, loss_fn):
    """One guarded PS update.  Returns
    ``(params, opt_state, extras, gstate, loss, guard_metrics)``.

    ``extras``/``old_extras`` are the round's remaining carry (error
    accumulators, momenta, banks) in post-/pre-round form: a skipped or
    reverted round restores ``old_extras`` wholesale, so error feedback
    cannot absorb an update that was never applied.  ``loss_fn(params)``
    is the divergence detector's eval (the engines pass their existing
    test-set loss, so the detector costs one extra eval only when the
    divergence rail is on).
    """
    if guard.update_clip > 0:
        nrm = jnp.sqrt(jnp.sum(ghat.astype(jnp.float32) ** 2))
        ghat = ghat * jnp.minimum(1.0, guard.update_clip
                                  / jnp.maximum(nrm, 1e-30))
    finite = jnp.all(jnp.isfinite(ghat))
    # a non-finite update would corrupt Adam's moments even on a skipped
    # round — apply the optimizer to a zeroed stand-in and discard it
    ghat_safe = jnp.where(finite, ghat, 0.0)
    with stage("optimizer"):
        p1, o1 = opt.apply(params, unravel(ghat_safe), opt_state)
    # LR backoff by step blending (Adam is scale-invariant in the gradient)
    p1 = jax.tree.map(lambda p0, p: p0 + gstate.lr_scale * (p - p0),
                      params, p1)

    skip = (~finite) if guard.skip_nonfinite else jnp.asarray(False)
    if guard.divergence_factor > 0:
        loss1 = loss_fn(p1)
        diverged = ((~jnp.isfinite(loss1))
                    | (loss1 > guard.divergence_factor * gstate.prev_loss))
        diverged = diverged & (gstate.cooldown <= 0.0) & ~skip
    else:
        loss1 = None
        diverged = jnp.asarray(False)
    revert = skip | diverged

    ok = ~revert
    params = _select(ok, p1, params)
    opt_state = _select(ok, o1, opt_state)
    extras = _select(ok, extras, old_extras)

    if loss1 is None:
        loss = loss_fn(params)
    else:
        # reverted rounds report the last accepted loss (= loss(params))
        loss = jnp.where(ok, loss1, gstate.prev_loss)
    new_gstate = GuardState(
        lr_scale=jnp.where(diverged, gstate.lr_scale * guard.lr_backoff,
                           gstate.lr_scale),
        cooldown=jnp.where(diverged, jnp.float32(guard.cooldown),
                           jnp.maximum(gstate.cooldown - 1.0, 0.0)),
        prev_loss=jnp.where(ok, loss, gstate.prev_loss),
        skips=gstate.skips + skip.astype(jnp.float32),
        backoffs=gstate.backoffs + diverged.astype(jnp.float32),
    )
    metrics = {"guard_lr_scale": new_gstate.lr_scale,
               "guard_skipped": skip.astype(jnp.float32),
               "guard_backoff": diverged.astype(jnp.float32)}
    return params, opt_state, extras, new_gstate, loss, metrics
