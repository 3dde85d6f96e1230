"""Serving: prefill + one-token decode steps under auto (GSPMD) sharding.

OTA-DSGD is a training-time technique; serving has no gradient aggregation
(docs/DESIGN.md §5), so serve steps are plain jit with declarative shardings:
params over 'model', batch over the data axes, KV caches over
(batch -> data, heads-or-seq -> model).

Serve-while-train (docs/DESIGN.md §5, docs/EXPERIMENTS.md): the streamed
federated trainer (``train/fedllm.py``) hands each round's decoded global
params to :meth:`ServeStep.publish` — a jitted identity with
``out_shardings`` pinned to the serve placement and the input donated, so
the swap is a device-side relayout (an alias when the trainer already
produced the serve layout) with no host round-trip.  ``decode_fn`` keeps
answering requests against whichever published tree the caller holds.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models import model as model_lib
from repro.sharding.specs import named_sharding_tree, param_specs
from repro.tracing import stage
from repro.train.trainer import abstract_params


def _cache_leaf_spec(shape, data_axes, axis_sizes) -> P:
    """(layers, B, ...) cache leaf: B over data axes, one inner dim -> model."""
    model = axis_sizes.get("model", 1)
    data = int(np.prod([axis_sizes[a] for a in data_axes])) if data_axes else 1
    spec = [None] * len(shape)
    if len(shape) >= 2 and data > 1 and shape[1] % data == 0:
        spec[1] = data_axes if len(data_axes) > 1 else data_axes[0]
    if model > 1:
        for dim in range(2, len(shape)):
            if shape[dim] % model == 0 and shape[dim] >= model:
                spec[dim] = "model"
                break
    return P(*spec)


@dataclasses.dataclass
class ServeStep:
    arch: ArchConfig
    mesh: Any
    batch: int
    max_len: int
    decode_window: Optional[int]
    param_sharding: Any
    cache_sharding: Any
    decode_fn: Any          # jit'd (params, cache, token, pos) -> logits, cache
    prefill_fn: Any = None  # jit'd (params, cache, tokens) -> logits, cache
    publish_fn: Any = None  # jit'd identity onto param_sharding (donated)

    def init_cache(self, dtype=jnp.bfloat16):
        return model_lib.init_decode_cache(self.arch, self.batch,
                                           self.max_len, dtype,
                                           self.decode_window)

    def publish(self, params):
        """Swap a freshly decoded global param tree into the serve layout.

        The input is donated: when the trainer already produced the serve
        sharding (the single-mesh fedllm loop) this is a pure buffer alias;
        otherwise XLA reshards device-to-device.  Either way no host copy.
        The caller must treat its argument as consumed and serve from the
        returned tree.
        """
        return self.publish_fn(params)


def make_serve_step(arch: ArchConfig, mesh, batch: int, max_len: int,
                    decode_window: Optional[int] = None,
                    compute_dtype=jnp.bfloat16,
                    cache_dtype=jnp.bfloat16) -> ServeStep:
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    data_axes = tuple(a for a in mesh.axis_names if a != "model")
    model_size = axis_sizes.get("model", 1)
    aparams = abstract_params(arch)
    pspecs = param_specs(aparams, model_size)
    ns = lambda s: named_sharding_tree(mesh, s)            # noqa: E731
    param_sh = ns(pspecs)

    acache = jax.eval_shape(
        lambda: model_lib.init_decode_cache(arch, batch, max_len,
                                            cache_dtype, decode_window))
    cache_sh = jax.tree.map(
        lambda lf: ns(_cache_leaf_spec(lf.shape, data_axes, axis_sizes)),
        acache)
    tok_spec = ns(P(data_axes if len(data_axes) > 1 else data_axes[0])
                  if batch % max(int(np.prod([axis_sizes[a] for a in data_axes])), 1) == 0
                  and len(data_axes) else P())

    enc_sh = tok_spec if arch.encoder is not None else None  # batch over data

    @stage("serve")
    def decode(params, cache, token, pos, *args):
        enc_out = args[0] if args else None
        logits, new_cache = model_lib.decode_step(
            params, arch, token, cache, pos, enc_out=enc_out,
            compute_dtype=compute_dtype, decode_window=decode_window)
        return logits, new_cache

    @stage("serve")
    def prefill(params, cache, tokens, *args):
        # scan one decode step per prompt position: arch-generic (every
        # model family defines decode_step; the batched-forward fast path
        # is a per-family optimisation this contract leaves open) and one
        # compile regardless of prompt length
        enc_out = args[0] if args else None

        def body(cache, i):
            tok = jax.lax.dynamic_slice_in_dim(tokens, i, 1, axis=1)
            logits, cache = model_lib.decode_step(
                params, arch, tok, cache, i, enc_out=enc_out,
                compute_dtype=compute_dtype, decode_window=decode_window)
            return cache, logits
        cache, logits = jax.lax.scan(body, cache,
                                     jnp.arange(tokens.shape[1]))
        return logits[-1], cache

    in_sh = [param_sh, cache_sh, tok_spec, ns(P())]
    pre_sh = [param_sh, cache_sh, tok_spec]
    if arch.encoder is not None:
        in_sh.append(enc_sh)
        pre_sh.append(enc_sh)
    decode_fn = jax.jit(decode, in_shardings=tuple(in_sh),
                        out_shardings=(None, cache_sh),
                        donate_argnums=(1,))
    prefill_fn = jax.jit(prefill, in_shardings=tuple(pre_sh),
                         out_shardings=(None, cache_sh),
                         donate_argnums=(1,))
    publish_fn = jax.jit(stage("serve")(lambda p: p),
                         out_shardings=param_sh, donate_argnums=(0,))
    return ServeStep(arch=arch, mesh=mesh, batch=batch, max_len=max_len,
                     decode_window=decode_window, param_sharding=param_sh,
                     cache_sharding=cache_sh, decode_fn=decode_fn,
                     prefill_fn=prefill_fn, publish_fn=publish_fn)
