"""Plain reference of the paper's model (arXiv 1901.00844, sec. VI).

A single-layer softmax classifier on 784-pixel inputs, 10 classes:
logits = x W + b, d = 784 * 10 + 10 = 7850 parameters, trained from zero
weights by cross-entropy.  ``codec`` is the precision of the product:
``"f32"`` (float32 at the highest matmul precision, the reference) or
``"bf16"`` (bfloat16 operands, the control).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference.ota import matmul


def init_params(cfg: dict):
    """Zero weights, as the paper's runs start."""
    return {"b": jnp.zeros((cfg["n_classes"],), jnp.float32),
            "w": jnp.zeros((cfg["dim"], cfg["n_classes"]), jnp.float32)}


def _logits(params, x, codec):
    return matmul("nd,dc->nc", x, params["w"], codec) + params["b"]


def loss(params, x, y, codec: str = "f32"):
    """Mean cross-entropy of (x, y) under ``params``."""
    logp = jax.nn.log_softmax(_logits(params, x, codec), -1)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], -1))


def flat_grad(params, x, y, codec: str = "f32"):
    """The gradient of ``loss`` on one device's batch, flattened in the
    parameter tree's leaf order (b, then w)."""
    g = jax.grad(lambda p: loss(p, x, y, codec))(params)
    return jnp.concatenate([g["b"].reshape(-1), g["w"].reshape(-1)])


def unflatten(flat, cfg: dict):
    c = cfg["n_classes"]
    return {"b": flat[:c], "w": flat[c:].reshape(cfg["dim"], c)}
