"""Streamed fedllm benchmark: rounds/sec and tokens/sec-while-training.

Runs the serve-while-train loop (``repro/train/fedllm.py``) at
smollm_360m scale and writes ``BENCH_llm.json`` at the repo root
(committed; gated by ``check_regression.py --strict`` in the llm-smoke CI
leg):

* ``train_us_per_round``        — one streamed OTA round (grads ->
                                  chunked encode/MAC/decode -> optimizer),
                                  steady-state (post-compile).
* ``serve_train_us_per_round``  — the same round plus the between-rounds
                                  serve traffic (publish + prefill +
                                  greedy decode batch): what a user of the
                                  live global params observes.
* ``compiled_cold_us_per_round``— first round including trace+compile
                                  (reported, never gated).
* ``rounds_per_sec`` / ``tokens_per_sec_while_training`` — the headline
                                  derived rates (not ``_us_per_round``
                                  keys, so reported-not-gated).

``SMOKE=1`` (CI) runs the ``.reduced()`` smollm_360m (2 layers, d_model
128 — the CPU-feasible stand-in at the same code path); the default/FULL
sizes raise rounds and chunk budget.  The demo's built-in acceptance
checks run either way: >= 2 OTA rounds, >= 1 decode batch between rounds,
published params bitwise-equal the decoded globals.

Usage:
    PYTHONPATH=src python benchmarks/bench_llm.py
    PYTHONPATH=src python benchmarks/run.py llm
"""
from __future__ import annotations

import json
import os
import sys
import time
from typing import Dict, Optional

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, REPO_ROOT)

OUT_PATH = os.path.join(REPO_ROOT, "BENCH_llm.json")

SMOKE = bool(int(os.environ.get("SMOKE", "0")))
FULL = bool(int(os.environ.get("FULL", "0")))


def bench_spec():
    """(reduced, rounds, m, chunk_size, decode_steps)."""
    if SMOKE:
        return True, 2, 3, 1 << 14, 2
    if FULL:
        # m = 2: at published widths one v5e holds the EF state and the
        # gradient block of two devices, not four (ROADMAP R2)
        return False, 3, 2, 1 << 18, 8
    return True, 3, 4, 1 << 15, 4


def main(collect: Optional[list] = None, out_path: str = OUT_PATH) -> Dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.configs.base import OTAConfig, TrainConfig, ota_overrides
    from repro.experiments.engine import round_keys
    from repro.launch.mesh import make_local_mesh
    from repro.train.fedllm import CompiledFedLLM, serve_while_train

    reduced, rounds, m, chunk_size, decode_steps = bench_spec()
    arch = get_config("smollm_360m")
    if reduced:
        arch = arch.reduced()
    base = ota_overrides("smollm_360m")
    block = min(base.block_size, max(chunk_size // 4, 256))
    ota = OTAConfig(projection="blocked", s_frac=base.s_frac,
                    k_frac=base.k_frac, rademacher=base.rademacher,
                    block_size=block)
    tc = TrainConfig(compute_dtype="float32" if reduced else "bfloat16")
    batch, seq_len, serve_batch, prompt_len = 2, 16, 2, 4

    # -- train-only: steady-state streamed round ---------------------------
    fed = CompiledFedLLM(arch, tc, ota, m=m, batch=batch, seq_len=seq_len,
                         chunk_size=chunk_size, seed=0)
    keys = round_keys(rounds + 1, 0)
    seg = jax.jit(lambda k, c, t: fed.run_segment({}, k, None, c, t),
                  donate_argnums=(1,))
    carry = fed.carry0()
    t0 = time.time()
    carry, _ = jax.block_until_ready(seg(keys[:1], carry, jnp.int32(0)))
    cold_s = time.time() - t0
    t0 = time.time()
    carry, _ = jax.block_until_ready(
        seg(keys[1:rounds + 1], carry, jnp.int32(1)))
    train_s = (time.time() - t0) / rounds

    # -- serve-while-train: the full demo loop -----------------------------
    mesh = make_local_mesh()
    t0 = time.time()
    out = serve_while_train(arch, rounds=rounds, ota=ota, train_cfg=tc,
                            m=m, batch=batch, seq_len=seq_len,
                            chunk_size=chunk_size, serve_batch=serve_batch,
                            prompt_len=prompt_len,
                            decode_steps=decode_steps, seed=0, mesh=mesh)
    swt_s = time.time() - t0
    assert len(out["served_tokens"]) == rounds >= 2, "demo did not serve"
    assert np.isfinite(out["losses"]).all(), "non-finite training loss"
    assert out["publish_bitwise"], "served params != decoded globals"
    served_tokens = rounds * serve_batch * (prompt_len + decode_steps)
    # the demo loop compiles its own jits inside the first round, so this
    # is an upper bound on the steady round+serve cost; the gate ratio
    # (2x) absorbs the amortisation difference across runners
    serve_round_s = swt_s / rounds

    doc = {
        "backend": jax.default_backend(),
        "smoke": SMOKE,
        "arch": "smollm_360m" + (".reduced" if reduced else ""),
        "d": fed.d,
        "n_chunks": fed.n_chunks,
        "chunk_len": fed.chunk_len,
        "m_devices": m,
        "rounds": rounds,
        "train_us_per_round": round(train_s * 1e6, 1),
        "serve_train_us_per_round": round(serve_round_s * 1e6, 1),
        "compiled_cold_us_per_round": round(cold_s * 1e6, 1),
        "rounds_per_sec": round(1.0 / train_s, 4),
        "tokens_per_sec_while_training": round(served_tokens / swt_s, 2),
    }
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=1)
        f.write("\n")
    print(json.dumps(doc, indent=1))
    if collect is not None:
        collect.append(("llm", doc["train_us_per_round"],
                        doc["tokens_per_sec_while_training"]))
    return doc


if __name__ == "__main__":
    main()
