"""Streamed OTA-DSGD over the LLM param tree (train/fedllm.py).

Pins the acceptance criteria: >= 2 OTA rounds over reduced smollm_360m
with serving between rounds, served params bitwise-equal the decoded
globals, pipelined streaming bitwise-equal the per-chunk reference,
EF accumulators persisting per chunk, and mid-sweep checkpoint/resume
bitwise-equal to the uninterrupted run.
"""
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import OTAConfig, TrainConfig
from repro.experiments.engine import round_keys, run_checkpointed
from repro.train.fedllm import (CompiledFedLLM, serve_while_train,
                                stream_round, stream_round_masked,
                                stream_round_ref)


def _fed(chunk_size=1 << 14, m=3, scheme="a_dsgd", use_kernel=False):
    arch = get_config("smollm_360m").reduced()
    ota = OTAConfig(scheme=scheme, projection="blocked", s_frac=0.25,
                    k_frac=0.5, block_size=256, use_kernel=use_kernel)
    tc = TrainConfig(compute_dtype="float32")
    return CompiledFedLLM(arch, tc, ota, m=m, batch=2, seq_len=8,
                          chunk_size=chunk_size, seed=0)


def _chunked_grads(fed, key):
    carry = fed.carry0()
    g, _ = jax.jit(fed._grads)(carry[0], key)
    return carry, g.reshape(fed.m, fed.n_chunks, fed.chunk_len)


def test_two_rounds_smoke():
    fed = _fed()
    assert fed.n_chunks >= 2        # the stream is actually chunked
    outs = fed.run(round_keys(2, 0))
    losses = np.asarray(outs["loss"])
    assert losses.shape == (2,) and np.isfinite(losses).all()
    assert np.isfinite(np.asarray(outs["metrics"]["active_frac"])).all()


def test_pipelined_stream_matches_reference_bitwise():
    fed = _fed()
    key = round_keys(1, 0)[0]
    carry, gch = _chunked_grads(fed, key)
    a = jax.jit(lambda: stream_round(fed.scheme, gch, carry[2], 0, key,
                                     fed.ctx))()
    b = jax.jit(lambda: stream_round_ref(fed.scheme, gch, carry[2], 0, key,
                                         fed.ctx))()
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_masked_stream_all_ones_matches_pipelined_bitwise():
    fed = _fed()
    key = round_keys(1, 0)[0]
    carry, gch = _chunked_grads(fed, key)
    mask = jnp.ones((fed.m,), jnp.float32)
    a = jax.jit(lambda: stream_round(fed.scheme, gch, carry[2], 0, key,
                                     fed.ctx))()
    b = jax.jit(lambda: stream_round_masked(fed.scheme, gch, carry[2], 0,
                                            key, mask, fed.ctx))()
    # round_masked returns a superset of metrics; compare the shared core
    for i in range(2):
        np.testing.assert_array_equal(np.asarray(a[i]), np.asarray(b[i]))
    for k, v in a[2].items():
        np.testing.assert_array_equal(np.asarray(v), np.asarray(b[2][k]))


def test_ef_state_persists_per_chunk():
    fed = _fed()
    keys = round_keys(2, 0)
    seg = jax.jit(lambda k, c, t: fed.run_segment({}, k, None, c, t))
    carry1, _ = seg(keys[:1], fed.carry0(), jnp.int32(0))
    deltas1 = np.asarray(carry1[2])
    assert deltas1.shape == (fed.n_chunks, fed.m, fed.chunk_len)
    # a_dsgd banks sparsification error: EF must be live in every full
    # chunk (the tail chunk is mostly pad — its few real entries can all
    # survive top-k, banking exactly zero)
    per_chunk = np.abs(deltas1).sum(axis=(1, 2))
    assert (per_chunk[:-1] > 0).all()
    carry2, _ = seg(keys[1:], carry1, jnp.int32(1))
    assert not np.array_equal(deltas1, np.asarray(carry2[2]))


def test_kernel_encode_path_on_streamed_chunks():
    """use_kernel=True routes chunk encodes through ef_sparsify_pallas
    (prime-safe since the pad fix); parity with the jnp path."""
    key = round_keys(1, 0)[0]
    fed_k = _fed(use_kernel=True)
    fed_r = _fed(use_kernel=False)
    carry, gch = _chunked_grads(fed_r, key)
    gch1, dl1 = gch[:, :1], carry[2][:1]    # one chunk is enough
    a = jax.jit(lambda: stream_round(fed_k.scheme, gch1, dl1, 0, key,
                                     fed_k.ctx))()
    b = jax.jit(lambda: stream_round(fed_r.scheme, gch1, dl1, 0, key,
                                     fed_r.ctx))()
    np.testing.assert_allclose(np.asarray(a[0]), np.asarray(b[0]),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(a[1]), np.asarray(b[1]),
                               rtol=2e-5, atol=2e-5)


def _pallas_calls(jaxpr, name):
    """Every ``pallas_call`` named ``name`` in ``jaxpr`` and its sub-jaxprs."""
    found = []
    for eqn in jaxpr.eqns:
        if (eqn.primitive.name == "pallas_call"
                and eqn.params["name"] == name):
            found.append(eqn)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, jax.extend.core.Jaxpr):
                    found += _pallas_calls(sub, name)
    return found


def test_kernel_encode_lowers_one_projection_for_all_devices():
    """The vmapped per-device encode lowers to ONE ``ota_project`` kernel
    over the stack of m devices, with no device axis in its grid."""
    from repro.core.schemes import encode_round
    fed = _fed(use_kernel=True)
    key = round_keys(1, 0)[0]
    g = jnp.zeros((fed.m, fed.chunk_len), jnp.float32)
    dl = jnp.zeros((fed.m, fed.chunk_len), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda g, dl: encode_round(
        fed.scheme, g, dl, 0, key, fed.ctx))(g, dl).jaxpr
    (call,) = _pallas_calls(jaxpr, "ota_project")
    x = call.invars[1].aval
    n_blocks = fed.chunk_len // 256
    assert x.shape[0] == fed.m and x.shape[1] >= n_blocks
    assert len(call.params["grid_mapping"].grid) == 3
    assert call.params["grid_mapping"].grid[0] == -(-n_blocks // 8)


@pytest.mark.slow
def test_serve_while_train_demo():
    arch = get_config("smollm_360m").reduced()
    ota = OTAConfig(projection="blocked", s_frac=0.25, k_frac=0.5,
                    block_size=256)
    tc = TrainConfig(compute_dtype="float32")
    out = serve_while_train(arch, rounds=2, ota=ota, train_cfg=tc, m=3,
                            batch=2, seq_len=8, chunk_size=1 << 14,
                            serve_batch=2, prompt_len=3, decode_steps=2,
                            seed=0)
    # >= 2 OTA rounds completed, >= 1 decode batch served between rounds
    assert out["losses"].shape == (2,)
    assert np.isfinite(out["losses"]).all()
    assert len(out["served_tokens"]) == 2
    assert out["served_tokens"][0].shape == (2, 2)
    # params served after round t bitwise-equal the decoded globals
    assert out["publish_bitwise"]


@pytest.mark.slow
def test_checkpoint_resume_bitwise():
    fed = _fed()
    keys = round_keys(3, 0)
    with tempfile.TemporaryDirectory() as td1, \
            tempfile.TemporaryDirectory() as td2:
        full = run_checkpointed(fed, {}, keys, checkpoint_dir=td1,
                                checkpoint_every=2)
        half = run_checkpointed(fed, {}, keys, checkpoint_dir=td2,
                                checkpoint_every=2, stop_after_step=2)
        assert half is None                    # interrupted mid-sweep
        resumed = run_checkpointed(fed, {}, keys, checkpoint_dir=td2,
                                   checkpoint_every=2, resume=True)
    for a, b in zip(jax.tree.leaves(full), jax.tree.leaves(resumed)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
