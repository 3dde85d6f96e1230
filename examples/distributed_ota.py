"""Distributed OTA training on a multi-device mesh (8 simulated devices).

Demonstrates the framework path: a real transformer (reduced SmolLM family),
data-parallel edge devices on the mesh's 'data' axis, tensor parallelism on
'model', and the A-DSGD aggregation (blocked projection + AMP) replacing the
gradient all-reduce inside a partial-manual shard_map.

Run:  PYTHONPATH=src python examples/distributed_ota.py
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax                                                     # noqa: E402
import jax.numpy as jnp                                        # noqa: E402

from repro.configs import get_config                           # noqa: E402
from repro.configs.base import OTAConfig, TrainConfig          # noqa: E402
from repro.data.synthetic import TokenStream                   # noqa: E402
from repro.launch.mesh import auto_mesh                        # noqa: E402
from repro.train.trainer import make_train_step                # noqa: E402

mesh = auto_mesh((4, 2), ("data", "model"))
arch = get_config("smollm_360m").reduced()
train_cfg = TrainConfig(optimizer="adam", lr=5e-3, warmup_steps=5,
                        total_steps=60, compute_dtype="float32", remat=True)
ota = OTAConfig(scheme="a_dsgd", projection="blocked", block_size=512,
                s_frac=0.25, k_frac=0.5, rademacher=True, p_avg=500.0,
                total_steps=60, amp_iters=10, mean_removal_steps=5)

ts = make_train_step(arch, train_cfg, ota, mesh, ota_axes=("data",))
print(f"model d={ts.d:,} padded={ts.d_pad:,}  OTA devices M={ts.m_devices}  "
      f"error-feedback state {ts.delta_shape}")

params, opt_state, delta = ts.init_state(jax.random.PRNGKey(0))
stream = TokenStream(vocab=arch.vocab, seq_len=64, batch=16, seed=0)
step_fn = ts.jitted({"tokens": jnp.zeros((16, 64), jnp.int32)})

for step in range(30):
    # cycle a small batch set so learning is visible within a short demo
    batch = {"tokens": jnp.asarray(stream.batch_at(step % 4)["tokens"])}
    params, opt_state, delta, metrics = step_fn(
        params, opt_state, delta, batch, jnp.asarray(step),
        jax.random.PRNGKey(step))
    if step % 5 == 0:
        print(f"step {step:3d}  loss {float(metrics['global_loss']):.4f}  "
              f"frame power {float(metrics['frame_power']):.1f}")
print("done — loss should be decreasing while every gradient exchange "
      "went through the simulated wireless MAC.")
