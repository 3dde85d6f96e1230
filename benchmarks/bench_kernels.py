"""Kernel micro-benchmarks: blocked forward projection + full AMP decode.

Times the jnp (XLA) path against the Pallas kernel path at two problem
sizes and writes ``BENCH_kernels.json`` at the repo root — the start of the
kernel perf trajectory (each PR can diff against the committed numbers).

Sizes: ``SMOKE=1`` (or any non-TPU backend, where Pallas runs in interpret
mode and large shapes would measure the interpreter) uses two tiny CPU-safe
sizes; on TPU the default is two MXU-scale sizes.  Override with FULL=1.

Usage:
    PYTHONPATH=src python benchmarks/bench_kernels.py
    PYTHONPATH=src python benchmarks/run.py kernels
"""
from __future__ import annotations

import json
import os
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
OUT_PATH = os.path.join(REPO_ROOT, "BENCH_kernels.json")

#: (name, n_blocks, c, s_block, amp_iters)
SIZES_SMOKE = [
    ("tiny", 4, 128, 32, 4),
    ("small", 16, 256, 64, 8),
]
SIZES_FULL = [
    ("medium", 64, 1024, 256, 10),
    ("large", 256, 4096, 1024, 20),
]


def _time_us(fn, *args, warmup: int = 2, reps: int = 10) -> float:
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6


def bench_size(name: str, n_blocks: int, c: int, s_block: int,
               iters: int, seed: int = 7) -> List[Dict]:
    from repro.core.amp import amp_blocked_core
    from repro.kernels import ops

    x = jax.random.normal(jax.random.PRNGKey(1), (n_blocks, c), jnp.float32)
    yb = jax.random.normal(jax.random.PRNGKey(2), (n_blocks, s_block),
                           jnp.float32)
    entries = []
    for path in ("jnp", "kernel"):
        uk = path == "kernel"
        # jit every candidate: ops.* wrappers are jitted already, but the
        # jnp amp_blocked_core would otherwise dispatch eagerly op-by-op
        amp = jax.jit(lambda v: amp_blocked_core(v, seed, c, iters=iters,
                                                 chunk_blocks=8,
                                                 use_kernel=uk))
        ops_us = {
            "proj_fwd": _time_us(
                lambda v: ops.ota_project(v, seed=seed, s_block=s_block,
                                          rademacher=True, use_kernel=uk), x),
            "amp_decode": _time_us(amp, yb),
        }
        for op, us in ops_us.items():
            entries.append({"size": name, "n_blocks": n_blocks, "c": c,
                            "s_block": s_block, "amp_iters": iters,
                            "op": op, "path": path,
                            "us_per_call": round(us, 1)})
            print(f"  {name:8s} {op:10s} {path:6s} {us:10.1f} us/call",
                  flush=True)
    return entries


def main(collect: Optional[list] = None, out_path: str = OUT_PATH) -> Dict:
    smoke = bool(int(os.environ.get("SMOKE", "0"))) or (
        jax.default_backend() != "tpu"
        and not bool(int(os.environ.get("FULL", "0"))))
    sizes = SIZES_SMOKE if smoke else SIZES_FULL
    results = {
        "backend": jax.default_backend(),
        "interpret": jax.default_backend() != "tpu",
        "smoke": smoke,
        "entries": [],
    }
    for spec in sizes:
        results["entries"].extend(bench_size(*spec))
    with open(out_path, "w") as f:
        json.dump(results, f, indent=2)
        f.write("\n")
    print(f"wrote {out_path}")
    if collect is not None:
        for e in results["entries"]:
            if e["op"] == "amp_decode":
                collect.append((f"kernels/{e['size']}/{e['path']}",
                                e["us_per_call"], "amp_decode"))
    return results


if __name__ == "__main__":
    main()
