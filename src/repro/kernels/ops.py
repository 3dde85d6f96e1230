"""jit'd public wrappers around the Pallas kernels with jnp fallbacks.

``use_kernel=True`` routes through pl.pallas_call (interpret mode off-TPU,
compiled Mosaic on TPU); ``use_kernel=False`` uses the pure-jnp oracle path,
which XLA fuses reasonably and which is what the multi-pod dry-run lowers
(Mosaic kernels do not lower on the CPU backend used for dry-runs).

``seed`` is a regular (traceable) operand on every wrapper: python ints,
concrete arrays and traced uint32 scalars (the shard-folded seeds of the
fully-sharded slice driver) all work.  Backend detection is lazy —
:func:`interpret_default` is evaluated at trace time of each call, never at
import time, so selecting a backend after importing this module works.
"""
from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.kernels import ref
from repro.kernels.amp_fused import amp_decode_fused_pallas
from repro.kernels.ef_sparsify import ef_sparsify_pallas
from repro.kernels.ota_project import ota_project_pallas
from repro.kernels.quantile_select import quantile_select_pallas


def interpret_default() -> bool:
    """Run Pallas in interpret mode?  Evaluated lazily per call (at trace
    time) — an import-time constant would pin the backend before the user
    could select one (e.g. via jax.config / JAX_PLATFORMS)."""
    return jax.default_backend() != "tpu"


#: Lowerings of the kernel projection under ``vmap``: ``shared`` stacks
#: whose devices share each generated A tile, the ``devices`` they cover,
#: and ``fallback`` vmaps of a batched seed (one A per device); of
#: :func:`sample_quantile`: ``select`` through the ``quantile_select``
#: kernel, ``sort`` through ``jnp.quantile``.
_LOWERINGS = collections.Counter()


def projection_lowerings() -> dict:
    """A copy of the projection's lowering counts (``_LOWERINGS``)."""
    return {k: _LOWERINGS[k] for k in ("shared", "devices", "fallback")}


@functools.lru_cache(maxsize=None)
def _kernel_project(s_block: int, rademacher: bool, nb_tile, interpret: bool):
    """``(x, seed) -> A x`` through the Pallas kernel.  Under ``vmap`` with
    the seed unbatched (the devices share A) the vmapped x is handed to the
    kernel as one stack, batch axis leading, and each program generates its
    A tile once for all devices; nested vmaps fold into that stack.  A
    batched seed takes Pallas's own batching: one A per device."""
    def call(x, seed):
        return ota_project_pallas(x, seed, s_block, rademacher,
                                  nb_tile=nb_tile, interpret=interpret)

    project = jax.custom_batching.custom_vmap(call)

    @project.def_vmap
    def _rule(axis_size, in_batched, x, seed):
        x_batched, seed_batched = in_batched
        if seed_batched:
            _LOWERINGS["fallback"] += 1
            return jax.vmap(call, in_axes=(0 if x_batched else None, 0))(
                x, seed), True
        if x.ndim == 3:                  # one device's blocks per entry
            _LOWERINGS["shared"] += 1
            _LOWERINGS["devices"] += axis_size
            return project(x, seed), True
        lead = x.shape[:2]               # an outer vmap over a stack
        _LOWERINGS["devices"] += (axis_size - 1) * lead[1]
        y = project(x.reshape(-1, *x.shape[2:]), seed)
        return y.reshape(lead + y.shape[1:]), True

    return project


@functools.partial(jax.jit, static_argnames=("s_block", "rademacher",
                                             "use_kernel", "nb_tile"))
def ota_project(x: jnp.ndarray, *, seed, s_block: int,
                rademacher: bool = True, use_kernel: bool = False,
                nb_tile: int | None = None):
    """Blocked forward projection. x: (n_blocks, c) -> (n_blocks, s_block).

    With ``use_kernel`` a ``vmap`` over devices that share the seed runs
    one kernel over the device stack (:func:`_kernel_project`)."""
    if use_kernel:
        return _kernel_project(s_block, rademacher, nb_tile,
                               interpret_default())(x, seed)
    return ref.ota_project_ref(x, seed, s_block, rademacher)


@functools.partial(jax.jit, static_argnames=("c", "iters", "threshold_mult",
                                             "debias", "rademacher",
                                             "nb_tile"))
def amp_decode_fused(yb: jnp.ndarray, *, seed, c: int, iters: int,
                     threshold_mult: float = 1.3, debias: bool = True,
                     rademacher: bool = True, nb_tile: int | None = None,
                     id_offset=0):
    """Single-launch fused AMP decode (kernels/amp_fused.py).

    The jnp realisation of the same one-generation-per-block structure is
    :func:`repro.core.amp.amp_blocked_core` (use_kernel=False).
    """
    return amp_decode_fused_pallas(yb, seed, c, iters=iters,
                                   threshold_mult=threshold_mult,
                                   debias=debias, rademacher=rademacher,
                                   nb_tile=nb_tile, id_offset=id_offset,
                                   interpret=interpret_default())


@functools.partial(jax.jit, static_argnames=("use_kernel",))
def ef_sparsify(g: jnp.ndarray, delta: jnp.ndarray, tau, *,
                use_kernel: bool = False):
    """Fused error-feedback + threshold sparsify. Returns (g_sp, new_delta)."""
    if use_kernel:
        return ef_sparsify_pallas(g, delta, jnp.asarray(tau),
                                  interpret=interpret_default())
    return ref.ef_sparsify_ref(g, delta, jnp.asarray(tau))


def quantile_lowerings() -> dict:
    """A copy of the sampled quantile's lowering counts (``_LOWERINGS``)."""
    return {k: _LOWERINGS[k] for k in ("select", "sort")}


@functools.partial(jax.jit, static_argnames=("q", "use_kernel"))
def sample_quantile(sample: jnp.ndarray, q: float, *,
                    use_kernel: bool = False) -> jnp.ndarray:
    """``jnp.quantile(sample, q, axis=-1)`` (linear) of a non-negative
    float32 sample (..., n), bitwise.

    ``use_kernel`` finds the two order statistics it interpolates by exact
    selection (kernels/quantile_select.py) instead of a sort.  Their ranks
    and weights are jnp's, computed as it computes them, and the weights
    combine the two values here, inside this jit, in jnp's own expression:
    the same lax ops, so the same rounding.  A ``vmap`` over devices runs
    one kernel launch for the stack."""
    if not use_kernel:
        _LOWERINGS["sort"] += 1
        return jnp.quantile(sample, q, axis=-1)
    _LOWERINGS["select"] += 1
    n = sample.shape[-1]
    # jnp.quantile's ranks: f32(q) * (f32(n) - 1), floored and ceiled,
    # clamped to [0, f32(n) - 1]
    last = np.float32(n) - np.float32(1)
    pos = np.float32(q) * last
    lo_rank, hi_rank = (int(np.clip(r, 0, last))
                        for r in (np.floor(pos), np.ceil(pos)))
    low_value, high_value = quantile_select_pallas(
        sample.reshape(-1, n), lo_rank, hi_rank,
        interpret=interpret_default())
    # ... and its weights and interpolation (method="linear"), op for op
    qa = jnp.asarray(q)
    pos = lax.mul(qa, lax.convert_element_type(n, qa.dtype) - 1)
    high_weight = lax.sub(pos, lax.floor(pos))
    low_weight = lax.sub(np.ones((), high_weight.dtype), high_weight)
    tau = lax.add(lax.mul(low_value, low_weight),
                  lax.mul(high_value, high_weight))
    return tau.reshape(sample.shape[:-1])
