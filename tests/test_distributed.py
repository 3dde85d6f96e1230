"""Distributed train-step tests — spawned in subprocesses so the main pytest
process keeps its single CPU device (the 8-device XLA flag must be set
before jax initialises)."""
import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__),
                                                "..")))
from bench.stages import stages_on  # noqa: E402

_COMMON = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from repro.configs import get_config
from repro.configs.base import OTAConfig, TrainConfig
from repro.launch.mesh import auto_mesh
from repro.train.trainer import make_train_step
mesh = auto_mesh((4, 2), ("data", "model"))
arch = get_config("smollm_360m").reduced()
tc = TrainConfig(optimizer="adam", lr=1e-3, warmup_steps=0, total_steps=50,
                 compute_dtype="float32", remat=True)
batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0,
                                      arch.vocab)}
"""


def _run(snippet, timeout=560):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    r = subprocess.run([sys.executable, "-c", _COMMON + snippet],
                       capture_output=True, text=True, timeout=timeout,
                       env=env)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


@pytest.mark.slow
def test_adsgd_distributed_loss_decreases():
    out = _run(r"""
ota = OTAConfig(scheme="a_dsgd", projection="blocked", block_size=512,
                s_frac=0.25, k_frac=0.5, rademacher=True, p_avg=500.0,
                total_steps=50, amp_iters=10, mean_removal_steps=3)
ts = make_train_step(arch, tc, ota, mesh, ota_axes=("data",), donate=False)
params, opt_state, delta = ts.init_state(jax.random.PRNGKey(0))
jfn = ts.jitted(batch)
losses = []
for step in range(5):
    params, opt_state, delta, met = jfn(params, opt_state, delta, batch,
                                        jnp.asarray(step),
                                        jax.random.PRNGKey(step))
    losses.append(float(met["global_loss"]))
assert losses[-1] < losses[0], losses
assert float(jnp.abs(delta).sum()) > 0    # error feedback engaged
assert abs(float(met["frame_power"]) - 500.0) < 5.0
print("OK", losses)
""")
    assert "OK" in out


@pytest.mark.slow
def test_ideal_distributed_matches_single_device():
    """psum/M inside shard_map == the same model trained on one device."""
    out = _run(r"""
from repro.models import loss_fn, init_params
from repro.optim.optim import Optimizer
ota = OTAConfig(scheme="ideal", total_steps=50)
ts = make_train_step(arch, tc, ota, mesh, ota_axes=("data",), donate=False)
params, opt_state, delta = ts.init_state(jax.random.PRNGKey(0))
jfn = ts.jitted(batch)
p1, o1, d1, met = jfn(params, opt_state, delta, batch, jnp.asarray(0),
                      jax.random.PRNGKey(0))
# single-device reference
params_ref = init_params(arch, jax.random.PRNGKey(0))
opt = Optimizer(name="adam", lr=1e-3)
s_ref = opt.init(params_ref)
g = jax.grad(lambda p: loss_fn(p, arch, batch, remat=True,
                               compute_dtype=jnp.float32,
                               loss_chunk=2048)[0])(params_ref)
p_ref, _ = opt.apply(params_ref, g, s_ref)
import numpy as np
for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p_ref)):
    # accumulation-order differences pass through Adam's rsqrt: ~1e-4 abs
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3,
                               atol=5e-4)
print("OK")
""")
    assert "OK" in out


@pytest.mark.slow
def test_sliced_layout_matches_flat():
    """O1 optimisation: slice-local layout trains like the flat baseline."""
    out = _run(r"""
from repro.train.trainer import make_train_step_sliced
losses = {}
for layout in ("flat", "sliced"):
    ota = OTAConfig(scheme="a_dsgd", projection="blocked", block_size=512,
                    s_frac=0.25, k_frac=0.5, rademacher=True, p_avg=500.0,
                    total_steps=50, amp_iters=10, mean_removal_steps=3,
                    layout=layout)
    mk = make_train_step_sliced if layout == "sliced" else make_train_step
    ts = mk(arch, tc, ota, mesh, ota_axes=("data",), donate=False)
    params, opt_state, delta = ts.init_state(jax.random.PRNGKey(0))
    jfn = ts.jitted(batch)
    ls = []
    for step in range(4):
        params, opt_state, delta, met = jfn(params, opt_state, delta, batch,
                                            jnp.asarray(step),
                                            jax.random.PRNGKey(step))
        ls.append(float(met["global_loss"]))
    losses[layout] = ls
assert losses["sliced"][-1] < losses["sliced"][0]
# same math, different element order/noise keys: trajectories agree closely
assert abs(losses["sliced"][-1] - losses["flat"][-1]) < 0.02, losses
print("OK", losses)
""")
    assert "OK" in out


@pytest.mark.slow
def test_site_ota_axes_variant():
    """ota_axes=('data',) vs hierarchical num_groups pre-averaging lowers."""
    out = _run(r"""
ota = OTAConfig(scheme="a_dsgd", projection="blocked", block_size=512,
                s_frac=0.25, k_frac=0.5, p_avg=500.0, total_steps=50,
                amp_iters=5, num_groups=2)
ts = make_train_step(arch, tc, ota, mesh, ota_axes=("data",), donate=False)
params, opt_state, delta = ts.init_state(jax.random.PRNGKey(0))
jfn = ts.jitted(batch)
p, o, dl, met = jfn(params, opt_state, delta, batch, jnp.asarray(0),
                    jax.random.PRNGKey(0))
assert ts.m_devices == 2
print("OK", float(met["global_loss"]))
""")
    assert "OK" in out


@pytest.mark.parametrize("layout", ["flat", "sliced"])
def test_sharded_step_lowers_with_its_stages(layout):
    """The sharded train step carries the round's stage scopes."""
    out = _run(r"""
import json, re
from repro.train.trainer import make_train_step_sliced
ota = OTAConfig(scheme="a_dsgd", projection="blocked", block_size=512,
                s_frac=0.25, k_frac=0.5, p_avg=500.0, total_steps=50,
                amp_iters=2, layout=LAYOUT)
mk = make_train_step_sliced if LAYOUT == "sliced" else make_train_step
ts = mk(arch, tc, ota, mesh, ota_axes=("data",), donate=False)
state = jax.eval_shape(ts.init_state, jax.random.PRNGKey(0))
low = ts.jitted(batch).lower(*state, batch, jnp.asarray(0),
                             jax.random.PRNGKey(0))
print("LOCS", json.dumps(sorted(set(re.findall(
    r'loc\("([^"]+)"', low.as_text(debug_info=True))))))
""".replace("LAYOUT", repr(layout)))
    locs = json.loads(out.split("LOCS", 1)[1])
    found = set().union(*(stages_on(p) for p in locs))
    assert found == {"grads", "encode", "threshold", "decode", "optimizer"}
