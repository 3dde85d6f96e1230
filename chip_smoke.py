"""Smoke run of the system's main path on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --four-chip  # the sharded trainer on four chips

One chip runs three phases in one process, at smollm_360m's published
widths (``get_config("smollm_360m")``, no ``.reduced()``):

1. fedllm + serve: ``serve_while_train`` for 2 rounds of the streamed
   OTA-DSGD round (A-DSGD with ``ota_overrides("smollm_360m")``: blocked
   Rademacher projection, s_frac 0.25, k_frac 0.5, block 4096, Pallas
   kernels on), serving a greedy batch from each round's published globals;
2. kernels: the compiled Pallas projection and fused AMP decode against
   the jnp path on one chunk at c = 4096, s_block = 1024;
3. paper: ``run_compiled`` on the paper's own model (d = 7850, M = 25,
   A-DSGD over the Gaussian MAC).

``--four-chip`` runs only the sharded trainer (``make_train_step``, the MAC
as a psum over the data axis) on a data=4, model=1 mesh, one OTA device per
chip: one ``ideal`` step against a single-chip reference and three
``a_dsgd`` steps.

There is no CPU branch: without a TPU the script exits non-zero before any
work.  A failed check raises, so the exit code is non-zero.  Times printed
are smoke timings of one cold and one warm call, not benchmark numbers.
The last line of stdout is one JSON object: ``{"ok": true, "device":
{...}}``.  The compile cache is ``$JAX_COMPILATION_CACHE_DIR`` when set,
else ``<checkout>/.jax_cache`` (``repro.launch.cache``).
"""
import argparse
import dataclasses
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(REPO, "src")

# bench_llm.py's FULL spec of the streamed round, except m: at m = 4 the EF
# state and the gradient block are 5.4 GiB each beside 4 GiB of params and
# Adam state, past one v5e's 15.75 GiB of HBM; m = 2 is the largest that
# fits (CHANGES.md holds the compiler's accounting)
FED = dict(m=2, batch=2, seq_len=16, chunk_size=1 << 18)
SERVE = dict(serve_batch=2, prompt_len=4, decode_steps=4)
ROUNDS = 2
# kernel vs jnp path (the latter at full f32 matmul precision): relative L2
# error.  Summation order alone moves f32 results by ~1e-6; a wrong tile,
# sign or block id moves them by O(1).
PROJ_TOL, AMP_TOL = 1e-4, 1e-3
# four-chip ideal step vs the single-chip reference: tests/test_distributed.py
IDEAL_RTOL, IDEAL_ATOL = 2e-3, 5e-4


def log(**kw):
    print(json.dumps(kw, default=float), flush=True)


def expect(cond, msg):
    if not cond:
        raise AssertionError(msg)


def timed(fn, *args):
    """(result, seconds) of ``fn(*args)`` run to completion."""
    import jax
    tic = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - tic


def rel_err(a, b):
    import numpy as np
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def peak_bytes(dev):
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------


def phase_fedllm():
    import numpy as np

    from repro.configs import get_config
    from repro.configs.base import TrainConfig, ota_overrides
    from repro.train.fedllm import serve_while_train

    ota = dataclasses.replace(ota_overrides("smollm_360m"), use_kernel=True)
    tic = time.perf_counter()
    out = serve_while_train(get_config("smollm_360m"), rounds=ROUNDS,
                            ota=ota, train_cfg=TrainConfig(), seed=0,
                            **FED, **SERVE)
    wall = time.perf_counter() - tic
    losses = np.asarray(out["losses"])
    served = out["served_tokens"]
    per_round = SERVE["serve_batch"] * SERVE["decode_steps"]
    log(phase="fedllm_serve", arch="smollm_360m", d=out["d"], m=FED["m"],
        chunk_len=FED["chunk_size"], n_chunks=out["n_chunks"],
        block_size=ota.block_size, use_kernel=ota.use_kernel,
        losses=losses.tolist(), publish_bitwise=out["publish_bitwise"],
        tokens_served=[int(s.size) for s in served],
        frame_power=[mt.get("frame_power") for mt in out["metrics"]],
        smoke_round_seconds=out["round_seconds"], smoke_phase_seconds=wall)
    expect(len(losses) == ROUNDS and np.isfinite(losses).all(),
           f"non-finite or missing losses: {losses}")
    expect(out["publish_bitwise"], "served params != decoded globals")
    expect(len(served) == ROUNDS
           and all(s.shape == (SERVE["serve_batch"], SERVE["decode_steps"])
                   for s in served),
           f"expected {per_round} tokens served per round: "
           f"{[s.shape for s in served]}")


def phase_kernels():
    import jax
    import jax.numpy as jnp

    from repro.core.amp import amp_blocked_core
    from repro.kernels import ops

    c, s_block, seed = 4096, 1024, 7
    n_blocks = FED["chunk_size"] // c
    kx, km, kn = jax.random.split(jax.random.PRNGKey(3), 3)
    # one chunk as the encoder sends it: sparse, s_block / 4 entries a block
    keep = jax.random.uniform(km, (n_blocks, c)) < 0.0625
    x = jnp.where(keep, jax.random.normal(kx, (n_blocks, c)), 0.0)

    def fwd(x, use_kernel):
        return ops.ota_project(x, seed=seed, s_block=s_block,
                               use_kernel=use_kernel)

    def amp(y, use_kernel):
        return amp_blocked_core(y, seed, c, iters=20, chunk_blocks=8,
                                use_kernel=use_kernel)

    with jax.default_matmul_precision("highest"):
        y_ref = jax.block_until_ready(fwd(x, False))
        y = y_ref + 0.01 * jnp.std(y_ref) * jax.random.normal(
            kn, y_ref.shape)
        a_ref = jax.block_until_ready(jax.jit(amp, static_argnums=1)(y,
                                                                     False))
    res = {}
    for name, fn, arg, want, tol in (("ota_project", fwd, x, y_ref, PROJ_TOL),
                                     ("amp_decode_fused",
                                      jax.jit(amp, static_argnums=1), y,
                                      a_ref, AMP_TOL)):
        got, cold = timed(fn, arg, True)
        _, warm = timed(fn, arg, True)
        err = rel_err(got, want)
        res[name] = dict(rel_err=err, tol=tol, smoke_cold_s=cold,
                         smoke_warm_s=warm)
    log(phase="kernels", c=c, s_block=s_block, n_blocks=n_blocks,
        reference="jnp path, matmul precision highest", **res)
    for name, r in res.items():
        expect(r["rel_err"] <= r["tol"],
               f"{name}: kernel vs jnp rel err {r['rel_err']} > {r['tol']}")


def phase_paper():
    import numpy as np

    from repro.configs.base import OTAConfig
    from repro.data.synthetic import federated_split, make_classification
    from repro.experiments import run_compiled

    (x_tr, y_tr), (x_te, y_te) = make_classification(
        n_train=10000, n_test=2000, noise=6.0, seed=3)
    x_dev, y_dev = federated_split(x_tr, y_tr, m=25, b=400, iid=True)
    steps = 30
    cfg = OTAConfig(scheme="a_dsgd", s_frac=0.5, k_frac=0.25, p_avg=500.0,
                    sigma2=1.0, total_steps=steps, projection="dense",
                    amp_iters=20, mean_removal_steps=10)
    tic = time.perf_counter()
    run = run_compiled(x_dev, y_dev, x_te, y_te, cfg, steps=steps, lr=1e-3,
                       eval_every=10)
    wall = time.perf_counter() - tic
    d = x_dev.shape[2] * 10 + 10
    log(phase="paper", model="mnist_mlp", d=d, M=x_dev.shape[0],
        scheme=cfg.scheme, accs=run.accs, losses=run.losses,
        smoke_phase_seconds=wall)
    expect(np.isfinite(run.losses).all(), f"non-finite losses {run.losses}")
    expect(run.accs[-1] > 0.2, f"accuracy {run.accs[-1]} not above chance")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------


def phase_four_chip():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.configs.base import OTAConfig, TrainConfig, ota_overrides
    from repro.launch.mesh import auto_mesh
    from repro.models import init_params, loss_fn
    from repro.optim.optim import Optimizer
    from repro.train.trainer import make_train_step

    expect(len(jax.devices()) == 4, f"need 4 chips: {jax.devices()}")
    mesh = auto_mesh((4, 1), ("data", "model"))
    arch = get_config("smollm_360m")
    tc = TrainConfig(optimizer="adam", lr=1e-3, warmup_steps=0,
                     total_steps=50, compute_dtype="float32", remat=True)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0,
                                          arch.vocab)}

    # -- ideal: psum / M over the data axis == one chip's full-batch step --
    # Both sides in true f32: a TPU's default f32 matmul is one bf16 pass,
    # whose rounding flips the sign of small gradient entries and with it
    # Adam's first step (+-lr) on ~0.5% of the parameters.
    with jax.default_matmul_precision("highest"):
        ts = make_train_step(arch, tc,
                             OTAConfig(scheme="ideal", total_steps=50),
                             mesh, ota_axes=("data",))
        state = ts.init_state(jax.random.PRNGKey(0))
        (p1, _, _, met), cold = timed(ts.jitted(batch), *state, batch,
                                      jnp.asarray(0), jax.random.PRNGKey(0))
        del state
        p1 = jax.tree.map(np.asarray, p1)

        params = init_params(arch, jax.random.PRNGKey(0))
        opt = Optimizer(name="adam", lr=1e-3)
        g = jax.jit(jax.grad(lambda p: loss_fn(
            p, arch, batch, remat=True, compute_dtype=jnp.float32,
            loss_chunk=2048)[0]))(params)
        p_ref, _ = jax.jit(opt.apply)(params, g, opt.init(params))
        del params, g
    worst, n_bad = 0.0, 0
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p_ref)):
        diff = np.abs(a - np.asarray(b))
        worst = max(worst, float(diff.max()))
        n_bad += int(np.sum(diff > IDEAL_ATOL + IDEAL_RTOL * np.abs(b)))
    del p1, p_ref
    log(phase="four_chip_ideal", d=ts.d, m=ts.m_devices,
        global_loss=met["global_loss"], max_abs_diff_vs_one_chip=worst,
        n_outside_tol=n_bad, rtol=IDEAL_RTOL, atol=IDEAL_ATOL,
        matmul_precision="highest", smoke_cold_s=cold)

    # -- a_dsgd: three steps of the paper's scheme, kernels on -------------
    ota = dataclasses.replace(ota_overrides("smollm_360m"), use_kernel=True,
                              total_steps=50)
    ts = make_train_step(arch, tc, ota, mesh, ota_axes=("data",))
    params, opt_state, delta = ts.init_state(jax.random.PRNGKey(0))
    jfn = ts.jitted(batch)
    losses, powers, secs = [], [], []
    for step in range(3):
        (params, opt_state, delta, met), s = timed(
            jfn, params, opt_state, delta, batch, jnp.asarray(step),
            jax.random.PRNGKey(step))
        losses.append(float(met["global_loss"]))
        powers.append(float(met["frame_power"]))
        secs.append(s)
    hlo = jfn.lower(params, opt_state, delta, batch, jnp.asarray(3),
                    jax.random.PRNGKey(3)).compile().as_text()
    spans = {"delta": len(delta.sharding.device_set),
             "params": min(len(x.sharding.device_set)
                           for x in jax.tree.leaves(params))}
    log(phase="four_chip_a_dsgd", d=ts.d, d_pad=ts.d_pad, m=ts.m_devices,
        block_size=ota.block_size, use_kernel=ota.use_kernel,
        losses=losses, frame_power=powers, p_avg=ota.p_avg,
        devices_spanned=spans, all_reduce_in_step="all-reduce" in hlo,
        smoke_step_seconds=secs)
    expect(n_bad == 0, f"ideal step: {n_bad} params outside rtol "
           f"{IDEAL_RTOL} / atol {IDEAL_ATOL} of the one-chip reference")
    expect(np.isfinite(losses).all(), f"non-finite losses {losses}")
    expect(all(abs(p - ota.p_avg) < 0.01 * ota.p_avg for p in powers),
           f"frame power {powers} != P_t {ota.p_avg}")
    expect(ts.m_devices == 4 and spans == {"delta": 4, "params": 4},
           f"state does not span the 4 chips: {spans}")
    expect("all-reduce" in hlo, "no all-reduce in the compiled step")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the sharded trainer on a 4-chip mesh")
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke.py: no repro package under {SRC}; run it from a "
              "checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke.py: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 1

    from repro.launch.cache import enable_compile_cache
    cache_dir, cache_counts = enable_compile_cache()
    log(phase="setup", device_kind=dev.device_kind,
        device_count=len(jax.devices()), jax=jax.__version__,
        compile_cache=cache_dir)

    phases = ((phase_four_chip,) if args.four_chip
              else (phase_fedllm, phase_kernels, phase_paper))
    for phase in phases:
        phase()
        log(phase=phase.__name__, peak_bytes_in_use=peak_bytes(dev),
            compile_cache=dict(cache_counts))

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
