"""Names the program gives its work in a profiler trace.

Device side: the round's stages are ``jax.named_scope`` scopes, so every
HLO operation a stage lowers to carries the stage in its ``op_name``
metadata (``jit(_lambda_)/while/body/stream/decode/...``).  Scopes are
compile-time metadata only: the computation, and its speed with the
profiler off, are unchanged.  A trace reduction attributes each device
operation to the stages on its scope path; ``threshold`` nests inside
``encode``, and ``encode`` and ``decode`` inside ``stream`` on the streamed
path.

Host side: the program's own loops mark their phases with
``repro:<name>`` spans (``jax.profiler.TraceAnnotation``) on the
profiler's host plane, on the same clock as the device operations.  With
no profiler running a span costs a flag check.
"""
from __future__ import annotations

import jax

#: the round's stages, in the order a round runs them
STAGES = ("grads", "stream", "encode", "threshold", "decode", "optimizer",
          "eval", "serve")

#: prefix of the program's host spans
SPAN_PREFIX = "repro:"


def stage(name: str):
    """The scope of one of :data:`STAGES` (a context manager)."""
    if name not in STAGES:
        raise ValueError(f"unknown stage {name!r}; stages are {STAGES}")
    return jax.named_scope(name)


def span(name: str):
    """A host span ``repro:<name>`` (a context manager)."""
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)
