"""Checkpointing: pytree <-> npz with path-encoded keys (no orbax offline).

Dict-of-dict pytrees (our params/opt/delta states) round-trip exactly;
keys are '/'-joined paths.  Arrays are gathered to host (np.asarray) — at
real scale this would be a per-shard async write; the format keeps that
extension trivial (one npz per host).

Two round-trip edge cases are handled explicitly:

* **Extended dtypes** (bfloat16 and friends from ml_dtypes) are not native
  npz dtypes — ``np.savez`` degrades them to opaque void records that
  ``jnp.asarray`` rejects on load.  Leaves whose dtype has kind ``'V'``
  are stored as a same-width unsigned-int bit-pattern view with the dtype
  name appended to the key (``path::bfloat16``) and viewed back on load.
  Complex dtypes are native to npz and pass through untouched.
* **Empty containers** (``{}``, ``()``) produce no leaves, so a naive
  flatten drops them and the restored tree has a different structure.
  They are recorded as zero-length sentinel leaves and rebuilt exactly.

NamedTuples still degrade to plain tuples (npz keys carry no class); when
a restored subtree must feed a jit carry, rebuild it against a reference:
``jax.tree.unflatten(jax.tree.structure(ref), jax.tree.leaves(loaded))``.
"""
from __future__ import annotations

import os
from typing import Any, Dict

import jax.numpy as jnp
import ml_dtypes  # noqa: F401  (registers bfloat16 etc. with numpy)
import numpy as np

from repro.tracing import span

_EMPTY_DICT = "__empty_dict__"
_EMPTY_TUPLE = "__empty_tuple__"
_UINT_FOR_WIDTH = {1: np.uint8, 2: np.uint16, 4: np.uint32, 8: np.uint64}


def _encode_leaf(arr: np.ndarray) -> tuple:
    """(key_suffix, storable array): bit-pattern view for non-native dtypes."""
    if arr.dtype.kind == "V":  # ml_dtypes extension dtype (bfloat16, fp8, ...)
        raw = arr.view(_UINT_FOR_WIDTH[arr.dtype.itemsize])
        return f"::{arr.dtype.name}", raw
    return "", arr


def _decode_leaf(key: str, val: np.ndarray) -> tuple:
    """Invert :func:`_encode_leaf`: (path, array with original dtype)."""
    if "::" in key:
        path, name = key.rsplit("::", 1)
        return path, val.view(np.dtype(name))
    return key, val


def _flatten(tree: Any, prefix: str = "") -> Dict[str, np.ndarray]:
    out = {}
    if isinstance(tree, dict):
        if not tree:
            out[f"{prefix}{_EMPTY_DICT}"] = np.zeros((0,), np.int8)
        for k, v in tree.items():
            out.update(_flatten(v, f"{prefix}{k}/"))
    elif isinstance(tree, (list, tuple)):
        if not tree:
            out[f"{prefix}{_EMPTY_TUPLE}"] = np.zeros((0,), np.int8)
        for i, v in enumerate(tree):
            out.update(_flatten(v, f"{prefix}#{i}/"))
    else:
        suffix, arr = _encode_leaf(np.asarray(tree))
        out[prefix[:-1] + suffix] = arr
    return out


def _unflatten(flat: Dict[str, np.ndarray]) -> Any:
    root: Dict[str, Any] = {}
    for key, val in flat.items():
        key, val = _decode_leaf(key, val)
        parts = key.split("/")
        node = root
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = val

    def fix(node):
        if not isinstance(node, dict):
            return jnp.asarray(node)
        if _EMPTY_DICT in node:
            return {}
        if _EMPTY_TUPLE in node:
            return ()
        if node and all(k.startswith("#") for k in node):
            items = sorted(node.items(), key=lambda kv: int(kv[0][1:]))
            return tuple(fix(v) for _, v in items)
        return {k: fix(v) for k, v in node.items()}

    return fix(root)


def save_checkpoint(path: str, state: Any, step: int = 0) -> None:
    """Write ``state`` and ``step`` to ``path`` (host span
    ``repro:save_checkpoint``)."""
    with span("save_checkpoint"):
        flat = _flatten({"state": state,
                         "meta": {"step": np.asarray(int(step))}})
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        tmp = path + ".tmp.npz"
        np.savez(tmp, **flat)
        os.replace(tmp, path)


def load_checkpoint(path: str, shardings=None):
    """Returns ``(state, step)``; ``step`` is always the saved python int
    (0 for files written before the ``meta`` block existed).

    The step is read from the raw npz entry, not the rebuilt pytree —
    ``_unflatten`` routes leaves through ``jnp.asarray``, which truncates
    int64 to int32 under the default x64-disabled config.

    ``shardings`` (optional) is a pytree of shardings matching the saved
    state: leaves are ``device_put`` straight onto their placement so a
    resumed serve/train loop never round-trips a replicated copy through
    the default device (the fedllm mid-sweep resume path).  Its structure
    must match the *restored* tree (post npz round-trip, so tuples where
    NamedTuples were).  Host span ``repro:load_checkpoint``.
    """
    with span("load_checkpoint"):
        with np.load(path) as f:
            flat = {k: f[k] for k in f.files}
        step = int(flat.pop("meta/step")) if "meta/step" in flat else 0
        tree = _unflatten(flat)
        if isinstance(tree, dict):
            tree.pop("meta", None)
            tree = tree.get("state", tree)
        if shardings is not None:
            import jax
            tree = jax.tree.map(lambda x, s: jax.device_put(x, s), tree,
                                shardings)
        return tree, step
