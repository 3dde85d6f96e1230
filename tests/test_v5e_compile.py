"""The Pallas kernels compile for a TPU v5e at the widths the chip runs.

Interpret mode (every other kernel test) cannot see what Mosaic refuses:
block shapes off the (8, 128) tiling, casts it lacks, more VMEM than a
kernel may use.  These tests compile each kernel with ``interpret=False``
for a described ``v5e:2x2`` chip — nothing runs, no chip is needed — at
smollm_360m's OTA block (c = 4096, s_block = 1024) and over one streamed
chunk of 2^18 entries, and assert the Mosaic custom call is in the
compiled program.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro.core  # noqa: F401  (kernels.amp_fused imports core.amp)
from repro.kernels import ops
from repro.kernels.amp_fused import amp_decode_fused_pallas
from repro.kernels.ef_sparsify import ef_sparsify_pallas
from repro.kernels.ota_project import ota_project_pallas
from repro.kernels.quantile_select import quantile_select_pallas

C, S_BLOCK, CHUNK = 4096, 1024, 1 << 18
N_BLOCKS = CHUNK // C
#: the sampled threshold's sample and its ranks at q = 1 - k/d = 0.875
N_SAMPLES, LO_RANK = 1 << 16, 57343


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _cases():
    seed = ((), jnp.uint32)
    return {
        "ota_project": (
            lambda x, sd: ota_project_pallas(x, sd, S_BLOCK, interpret=False),
            [((N_BLOCKS, C), jnp.float32), seed]),
        # the streamed encode's m = 2 devices share each A tile
        "ota_project_stacked": (
            lambda x, sd: ota_project_pallas(x, sd, S_BLOCK, interpret=False),
            [((2, N_BLOCKS, C), jnp.float32), seed]),
        "amp_decode_fused": (
            lambda y, sd: amp_decode_fused_pallas(y, sd, C, iters=20,
                                                  interpret=False),
            [((N_BLOCKS, S_BLOCK), jnp.float32), seed]),
        "ef_sparsify": (
            lambda g, d: ef_sparsify_pallas(g, d, 0.5, interpret=False),
            [((CHUNK,), jnp.float32), ((CHUNK,), jnp.float32)]),
        # the streamed encode vmaps it over devices, with a per-device tau
        "ef_sparsify_vmapped": (
            jax.vmap(lambda g, d, tau: ef_sparsify_pallas(g, d, tau,
                                                          interpret=False)),
            [((2, CHUNK), jnp.float32), ((2, CHUNK), jnp.float32),
             ((2,), jnp.float32)]),
        # both devices' threshold samples of one streamed chunk
        "quantile_select": (
            lambda s: quantile_select_pallas(s, LO_RANK, LO_RANK + 1,
                                             interpret=False),
            [((2, N_SAMPLES), jnp.float32)]),
        # the wrapper the streamed encode vmaps over its devices
        "sample_quantile_vmapped": (
            jax.vmap(lambda s: ops.sample_quantile(s, 0.875,
                                                   use_kernel=True)),
            [((2, N_SAMPLES), jnp.float32)]),
    }


@pytest.mark.parametrize("name", sorted(_cases()))
def test_kernel_compiles_for_v5e(name, one_chip, monkeypatch):
    monkeypatch.setattr(ops, "interpret_default", lambda: False)
    jax.clear_caches()      # no wrapper trace cached in interpret mode
    fn, specs = _cases()[name]
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in specs]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _wrappers():
    seed = ((), jnp.uint32)
    return {
        "ota_project": (
            lambda x, sd: ops.ota_project(x, seed=sd, s_block=S_BLOCK,
                                          use_kernel=True),
            [((N_BLOCKS, C), jnp.float32), seed]),
        "amp_decode_fused": (
            lambda y, sd: ops.amp_decode_fused(y, seed=sd, c=C, iters=2),
            [((N_BLOCKS, S_BLOCK), jnp.float32), seed]),
        "ef_sparsify": (
            lambda g, d: ops.ef_sparsify(g, d, 0.5, use_kernel=True),
            [((CHUNK,), jnp.float32), ((CHUNK,), jnp.float32)]),
        # vmapped over the devices, as the streamed encode calls it
        "quantile_select": (
            jax.vmap(lambda s: ops.sample_quantile(s, 0.875,
                                                   use_kernel=True)),
            [((2, N_SAMPLES), jnp.float32)]),
    }


@pytest.mark.parametrize("name", sorted(_wrappers()))
def test_kernel_wrapper_names_its_kernel(name, one_chip, monkeypatch):
    """Each Pallas kernel is named, so a profile shows ``<name>.<n>``."""
    monkeypatch.setattr(ops, "interpret_default", lambda: False)
    jax.clear_caches()
    fn, specs = _wrappers()[name]
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in specs]
    text = jax.jit(fn).lower(*args).as_text()
    assert f'kernel_name = "{name}"' in text
