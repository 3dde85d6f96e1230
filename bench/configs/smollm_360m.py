"""Plain reference of SmolLM-360M (a Llama-architecture decoder).

From the published description (hf:HuggingFaceTB/SmolLM-360M): token
embedding tied to the output head, pre-norm decoder blocks with RMSNorm,
grouped-query attention with rotary embeddings (rotate-half form, theta
10000) and a causal mask, a SwiGLU feed-forward, a final RMSNorm, and
next-token cross-entropy.  No cache, no batching tricks, no kernels.

``mode`` is the precision of every matrix product and of the activations:

* ``"bf16"``: bfloat16 activations and products with float32 parameters,
  softmax, norms and rotary angles in float32 (the precision the
  configuration trains and serves in);
* ``"fp8"``: as bf16, but both operands of every product rounded to
  float8 e4m3 with one scale per tensor (the control);
* ``"f32"``: float32 throughout at the highest matmul precision.

The parameter tree has the layout the system under test stores: per-layer
weights stacked on a leading layer axis under ``blocks``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
_FP8_MAX = 448.0


def init_params(cfg: dict, key):
    """Random weights from ``key``: embedding N(0, 0.02^2), projections
    truncated normal with std 1/sqrt(fan_in), norm gains 1."""
    d, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    n, hd = cfg["num_hidden_layers"], cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    ks = iter(jax.random.split(key, 8))

    def dense(fan_in, fan_out):
        return (jax.random.truncated_normal(next(ks), -2.0, 2.0,
                                            (n, fan_in, fan_out))
                / math.sqrt(fan_in))

    ones = jnp.ones((n, d), jnp.float32)
    return {
        "blocks": {
            "attn": {"wq": dense(d, hq * hd), "wk": dense(d, hkv * hd),
                     "wv": dense(d, hkv * hd), "wo": dense(hq * hd, d)},
            "ln1": {"w": ones}, "ln2": {"w": ones},
            "mlp": {"w_gate": dense(d, f), "w_up": dense(d, f),
                    "w_down": dense(f, d)},
        },
        "embed": 0.02 * jax.random.normal(next(ks), (v, d), jnp.float32),
        "final_norm": {"w": jnp.ones((d,), jnp.float32)},
    }


def _fp8(x):
    xf = x.astype(jnp.float32)
    s = jax.lax.stop_gradient(jnp.max(jnp.abs(xf)) / _FP8_MAX)
    s = jnp.where(s > 0, s, 1.0)
    return ((xf / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
            ).astype(jnp.bfloat16)


def _ein(spec, a, b, mode):
    if mode == "f32":
        return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32),
                          precision=HIGHEST)
    if mode == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16))


def _rms(x, w, eps):
    xf = x.astype(jnp.float32)
    out = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps) * w
    return out.astype(x.dtype)


def _rope(x, theta):
    """Rotate-half rotary embedding at positions 0..L-1; x (B, L, H, hd)."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def _block(x, p, cfg, mode):
    B, L, _ = x.shape
    hd, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    h = _rms(x, p["ln1"]["w"], eps)
    q = _ein("bld,de->ble", h, p["attn"]["wq"], mode).reshape(B, L, hq, hd)
    k = _ein("bld,de->ble", h, p["attn"]["wk"], mode).reshape(B, L, hkv, hd)
    v = _ein("bld,de->ble", h, p["attn"]["wv"], mode).reshape(B, L, hkv, hd)
    q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    k = jnp.repeat(k, hq // hkv, axis=2)          # head i reads kv i // g
    v = jnp.repeat(v, hq // hkv, axis=2)
    scores = _ein("bqhd,bkhd->bhqk", q, k, mode).astype(jnp.float32)
    scores = scores / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((L, L), bool))
    scores = jnp.where(causal, scores, -1e30)
    probs = jax.nn.softmax(scores, -1).astype(x.dtype)
    att = _ein("bhqk,bkhd->bqhd", probs, v, mode).reshape(B, L, hq * hd)
    x = x + _ein("ble,ed->bld", att, p["attn"]["wo"], mode).astype(x.dtype)
    h = _rms(x, p["ln2"]["w"], eps)
    g = jax.nn.silu(_ein("bld,df->blf", h, p["mlp"]["w_gate"], mode))
    u = _ein("bld,df->blf", h, p["mlp"]["w_up"], mode)
    return x + _ein("blf,fd->bld", g * u, p["mlp"]["w_down"],
                    mode).astype(x.dtype)


def _hidden(params, tokens, cfg, mode):
    dt = jnp.float32 if mode == "f32" else jnp.bfloat16
    x = params["embed"].astype(dt)[tokens]
    x, _ = jax.lax.scan(lambda x, p: (_block(x, p, cfg, mode), None), x,
                        params["blocks"])
    return _rms(x, params["final_norm"]["w"], cfg["rms_norm_eps"])


def logits(params, tokens, cfg: dict, mode: str = "bf16"):
    """(B, L, vocab) float32 next-token logits."""
    h = _hidden(params, tokens, cfg, mode)
    return _ein("bld,vd->blv", h, params["embed"], mode).astype(jnp.float32)


def loss(params, tokens, cfg: dict, mode: str = "bf16"):
    """Mean next-token cross-entropy over a (B, L) batch."""
    h = _hidden(params, tokens, cfg, mode)[:, :-1]
    lg = _ein("bld,vd->blv", h, params["embed"], mode).astype(jnp.float32)
    logp = jax.nn.log_softmax(lg, -1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], -1))
