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

from repro.kernels import ref
from repro.kernels.amp_fused import amp_decode_fused_pallas
from repro.kernels.ef_sparsify import ef_sparsify_pallas
from repro.kernels.ota_project import ota_project_pallas, ota_project_t_pallas


def interpret_default() -> bool:
    """Run Pallas in interpret mode?  Evaluated lazily per call (at trace
    time) — an import-time constant would pin the backend before the user
    could select one (e.g. via jax.config / JAX_PLATFORMS)."""
    return jax.default_backend() != "tpu"


#: Lowerings of the kernel projection under ``vmap``: ``shared`` stacks
#: whose devices share each generated A tile, the ``devices`` they cover,
#: and ``fallback`` vmaps of a batched seed (one A per device).
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


@functools.partial(jax.jit, static_argnames=("c", "rademacher",
                                             "use_kernel", "nb_tile"))
def ota_project_t(y: jnp.ndarray, *, seed, c: int,
                  rademacher: bool = True, use_kernel: bool = False,
                  nb_tile: int | None = None):
    """Blocked transpose projection. y: (n_blocks, s_block) -> (n_blocks, c)."""
    if use_kernel:
        return ota_project_t_pallas(y, seed, c, rademacher,
                                    nb_tile=nb_tile,
                                    interpret=interpret_default())
    return ref.ota_project_t_ref(y, seed, c, rademacher)


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
