"""End-to-end training driver.

Selects an architecture config (full or --reduced), builds the mesh, the OTA
aggregator, the token pipeline, and runs the distributed train step for
--steps steps with periodic checkpointing and metrics.

CPU-sized example (the container has one core; the production mesh path is
exercised by launch/dryrun.py):

  PYTHONPATH=src python -m repro.launch.train --arch smollm_360m --reduced \
      --devices 8 --mesh 4x2 --steps 200 --aggregator a_dsgd

On a real TPU slice drop --reduced/--devices and pass --mesh 16x16.
"""
import argparse
import os
import time


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm_360m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--devices", type=int, default=0,
                    help="force N host devices (CPU simulation)")
    ap.add_argument("--mesh", default="4x2", help="DxM or PxDxM")
    ap.add_argument("--aggregator", default="a_dsgd",
                    choices=["ideal", "a_dsgd"])
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--p-avg", type=float, default=500.0)
    ap.add_argument("--s-frac", type=float, default=0.25)
    ap.add_argument("--block-size", type=int, default=512)
    ap.add_argument("--site-ota", action="store_true",
                    help="ota_axes=('pod',): edge sites = pods")
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()

    if args.devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices}")
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.configs.base import OTAConfig, TrainConfig
    from repro.data.synthetic import TokenStream
    from repro.launch.mesh import auto_mesh
    from repro.train.checkpoint import save_checkpoint
    from repro.train.trainer import make_train_step

    dims = [int(x) for x in args.mesh.split("x")]
    names = ("pod", "data", "model")[-len(dims):]
    mesh = auto_mesh(dims, names)
    arch = get_config(args.arch)
    if args.reduced:
        arch = arch.reduced()
    train_cfg = TrainConfig(optimizer="adam", lr=args.lr, warmup_steps=10,
                            total_steps=args.steps,
                            compute_dtype="float32" if args.reduced
                            else "bfloat16", remat=True)
    ota = OTAConfig(scheme=args.aggregator, projection="blocked",
                    block_size=args.block_size, s_frac=args.s_frac,
                    k_frac=0.5, rademacher=True, p_avg=args.p_avg,
                    total_steps=args.steps, amp_iters=10,
                    mean_removal_steps=10)
    ota_axes = (("pod",) if args.site_ota and "pod" in names
                else tuple(a for a in names if a in ("pod", "data")))
    ts = make_train_step(arch, train_cfg, ota, mesh, ota_axes=ota_axes)
    print(f"[train] arch={arch.name} d={ts.d:,} M={ts.m_devices} "
          f"mesh={dict(zip(names, dims))} ota_axes={ota_axes}", flush=True)

    params, opt_state, delta = ts.init_state(jax.random.PRNGKey(0))
    stream = TokenStream(vocab=arch.vocab, seq_len=args.seq,
                         batch=args.batch, seed=0)
    jfn = ts.jitted({"tokens": jnp.zeros((args.batch, args.seq), jnp.int32)})
    t0 = time.time()
    for step in range(args.steps):
        batch = {"tokens": jnp.asarray(stream.batch_at(step)["tokens"])}
        params, opt_state, delta, met = jfn(params, opt_state, delta, batch,
                                            jnp.asarray(step),
                                            jax.random.PRNGKey(step))
        if step % args.log_every == 0 or step == args.steps - 1:
            print(f"step {step:5d}  loss {float(met['global_loss']):.4f}  "
                  f"ppl {float(met['ppl']):.1f}  "
                  f"{(time.time() - t0) / (step + 1):.2f}s/step", flush=True)
    if args.ckpt:
        save_checkpoint(args.ckpt, {"params": params, "opt": opt_state},
                        step=args.steps)
        print(f"[train] checkpoint -> {args.ckpt}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
