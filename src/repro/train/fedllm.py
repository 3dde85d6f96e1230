"""Streamed OTA-DSGD over a real (sharded) LLM parameter tree.

The paper's federated round aggregates one d = 7850 vector; here the same
registered ``Scheme`` contract runs over the gradient pytree of any model
in the zoo (``repro/models``), streamed through the bandwidth-limited MAC
in fixed-size chunks (docs/DESIGN.md §13, docs/EXPERIMENTS.md):

* the param tree is flattened with the stable ``ravel_pytree`` leaf
  ordering (``train/trainer.py:ravel_meta``) — every device and the PS
  agree on which entry lands in which chunk;
* each chunk is one paper round of the registered scheme: per-device
  error-feedback accumulators persist *per chunk* across global rounds
  (the EF state is ``(n_chunks, m, chunk_len)``), so sparsification error
  in chunk ``i`` of round ``t`` is re-fed into chunk ``i`` of round
  ``t+1`` exactly as the MNIST-scale drivers do for their single vector;
* chunks are double-buffered: while the PS runs the AMP/decode of chunk
  ``i-1``, the devices encode + transmit chunk ``i``
  (``core.schemes.encode_round`` is the encode/MAC half split out of
  ``round_simulated``), as a ``jax.lax.scan`` whose carry is the
  in-flight MAC output — the dataflow XLA needs to overlap device
  compute with channel decode;
* per-chunk RNG is ``fold_in(fold_in(round_key, SALT_STREAM), chunk)``:
  derived from the round key, never from carried state, which keeps
  checkpoint/resume bitwise.

:class:`CompiledFedLLM` implements the ``carry0`` / ``run_segment``
segment contract, so :func:`repro.experiments.engine.run_checkpointed`
drives mid-sweep checkpoint/resume unchanged.  :func:`serve_while_train`
is the demo loop: every round's decoded globals are published into the
``ServeStep`` param sharding (donated-buffer swap) while ``decode_fn``
answers requests between rounds.
"""
from __future__ import annotations

import dataclasses
import os
import time
from typing import Any, Dict, Optional, Tuple

import jax
import jax.flatten_util
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig, OTAConfig, TrainConfig
from repro.core.schemes import (MACContext, Scheme, decode_round,
                                encode_round, get_scheme, round_simulated)
from repro.models import model as model_lib
from repro.optim.optim import make_optimizer
from repro.tracing import span, stage
from repro.train.trainer import _pad_multiple, abstract_params, ravel_meta

# RNG salts (extending the 0-7 layout in docs/ARCHITECTURE.md): chunk
# index inside a streamed round, and per-device synthetic-batch draws.
SALT_STREAM = 8
SALT_DATA = 9


def _chunk_key(key: jnp.ndarray, i) -> jnp.ndarray:
    """Per-chunk round key: chunk i is an independent paper round."""
    return jax.random.fold_in(jax.random.fold_in(key, SALT_STREAM), i)


def _chunk_metrics(metrics: Dict[str, jnp.ndarray], draw) -> Dict[str, Any]:
    """The per-chunk metric dict ``round_simulated`` would have produced."""
    met = {k: jnp.mean(v) for k, v in metrics.items()}
    met["active_frac"] = jnp.mean(draw.active.astype(jnp.float32))
    if draw.gain is not None:
        met["chan_gain"] = jnp.mean(draw.gain)
    if draw.noise_scale is not None:
        met["noise_scale"] = draw.noise_scale
    return met


def _chunk(grads: jnp.ndarray, i) -> jnp.ndarray:
    """Chunk ``i`` of every device, ``(m, chunk_len)``, read in place from
    the ``(m, n_chunks, chunk_len)`` gradient block (a reshape of the
    ``(m, d_pad)`` flat gradients — no transposed copy)."""
    return jax.lax.dynamic_index_in_dim(grads, i, axis=1, keepdims=False)


def _set(buf: jnp.ndarray, i, row: jnp.ndarray) -> jnp.ndarray:
    """``buf[i] = row`` as an in-place ``dynamic_update_slice``."""
    return jax.lax.dynamic_update_index_in_dim(buf, row, i, axis=0)


@stage("stream")
def stream_round(scheme: Scheme, grads: jnp.ndarray, deltas: jnp.ndarray,
                 t, key: jnp.ndarray, ctx: MACContext):
    """One federated round streamed chunk-by-chunk, double-buffered.

    ``grads``: (m, n_chunks, chunk_len); ``deltas``: (n_chunks, m,
    chunk_len).  Pipeline shape: the prologue encodes chunk 0; each scan
    iteration decodes the in-flight chunk ``i-1`` while encoding chunk
    ``i`` (one body, two independent dataflows — XLA overlaps them); the
    epilogue decodes the last chunk.  Bitwise-equal to
    :func:`stream_round_ref` (the straight per-chunk ``round_simulated``
    loop) because every chunk sees exactly the same ops with the same
    ``_chunk_key``; only the schedule differs.

    Chunks are read and the EF state and decoded chunks written in place
    (``dynamic_slice`` / ``dynamic_update_slice`` on the scan carry), so a
    round holds no copy of the ``(n_chunks, m, chunk_len)`` arrays — at
    smollm_360m's published widths each is 5.8 GB at m = 4.

    Returns ``(ghats, new_deltas, mets)`` stacked over chunks.
    """
    n_chunks, chunk_len = grads.shape[1], grads.shape[2]
    y0, nd0, met0, draw0 = encode_round(scheme, _chunk(grads, 0), deltas[0],
                                        t, _chunk_key(key, 0), ctx)
    met0 = _chunk_metrics(met0, draw0)
    ghats0 = jnp.zeros((n_chunks, chunk_len), jnp.float32)

    def body(carry, i):
        y_prev, dls, ghats = carry
        ghats = _set(ghats, i - 1,                             # PS: i-1
                     decode_round(scheme, y_prev, t, ctx))
        y_i, nd_i, met_i, draw_i = encode_round(               # devices: i
            scheme, _chunk(grads, i), dls[i], t, _chunk_key(key, i), ctx)
        return (y_i, _set(dls, i, nd_i), ghats), _chunk_metrics(met_i,
                                                                draw_i)

    (y_last, new_deltas, ghats), mets_tail = jax.lax.scan(
        body, (y0, _set(deltas, 0, nd0), ghats0), jnp.arange(1, n_chunks))
    ghats = _set(ghats, n_chunks - 1, decode_round(scheme, y_last, t, ctx))
    mets = jax.tree.map(lambda a, b: jnp.concatenate([a[None], b], axis=0),
                        met0, mets_tail)
    return ghats, new_deltas, mets


@stage("stream")
def _chunk_loop(round_fn, grads: jnp.ndarray, deltas: jnp.ndarray,
                key: jnp.ndarray):
    """Chunk ``i`` is ``round_fn(grads_i, deltas_i, _chunk_key(key, i))``,
    one after another, with the EF state updated in place."""
    def body(dls, i):
        ghat, nd, met = round_fn(_chunk(grads, i), dls[i], _chunk_key(key, i))
        return _set(dls, i, nd), (ghat, met)

    new_deltas, (ghats, mets) = jax.lax.scan(body, deltas,
                                             jnp.arange(grads.shape[1]))
    return ghats, new_deltas, mets


def stream_round_ref(scheme: Scheme, grads: jnp.ndarray,
                     deltas: jnp.ndarray, t, key: jnp.ndarray,
                     ctx: MACContext):
    """Non-pipelined reference: chunk i is literally ``round_simulated``
    under ``_chunk_key(key, i)``.  The parity pin for :func:`stream_round`
    (tests/test_fedllm.py)."""
    return _chunk_loop(
        lambda g, dl, k: round_simulated(scheme, g, dl, t, k, ctx),
        grads, deltas, key)


def stream_round_masked(scheme: Scheme, grads: jnp.ndarray,
                        deltas: jnp.ndarray, t, key: jnp.ndarray,
                        mask: jnp.ndarray, ctx: MACContext):
    """Masked-cohort variant: chunk i runs ``round_masked`` (participation
    masks, fault traces, guardrail metrics) with the same per-chunk keys.
    Not pipelined — the masked driver owns its own draw/fault plumbing;
    at the all-ones mask it is pinned bitwise to ``round_simulated`` and
    hence to :func:`stream_round`."""
    from repro.experiments.engine import round_masked
    return _chunk_loop(
        lambda g, dl, k: round_masked(scheme, g, dl, t, k, mask, ctx),
        grads, deltas, key)


@dataclasses.dataclass
class CompiledFedLLM:
    """Streamed federated rounds over a zoo model, segment-contract shaped.

    M simulated edge devices each draw a deterministic synthetic batch
    (``fold_in(round_key, SALT_DATA)`` split per device — nothing consumed
    from carried state), compute a local gradient, and stream the
    flattened tree through the OTA channel ``chunk_len`` entries at a
    time.  The PS unravels the concatenated decoded chunks and applies
    the optimizer.  ``run_segment`` scans rounds from an explicit carry,
    so :func:`repro.experiments.engine.run_checkpointed` checkpoints and
    resumes it bitwise.
    """
    arch: ArchConfig
    train_cfg: TrainConfig
    ota: OTAConfig
    m: int = 4
    batch: int = 2
    seq_len: int = 16
    chunk_size: int = 1 << 14
    seed: int = 0

    def __post_init__(self):
        aparams = abstract_params(self.arch)
        self.d, self.unravel = ravel_meta(aparams)
        unit = (self.ota.block_size if self.ota.projection == "blocked"
                else 1)
        self.chunk_len = _pad_multiple(max(min(self.chunk_size, self.d), 2),
                                       unit)
        self.n_chunks = -(-self.d // self.chunk_len)
        self.d_pad = self.n_chunks * self.chunk_len
        self.scheme = get_scheme(self.ota, self.chunk_len, self.m)
        self.ctx = MACContext(m=self.m, fading=self.ota.fading,
                              csi=self.scheme.csi)
        self.opt = make_optimizer(self.train_cfg)
        self.compute_dtype = jnp.dtype(self.train_cfg.compute_dtype)

    # ------------------------------------------------------------- carry
    def carry0(self) -> Tuple:
        params = model_lib.init_params(self.arch,
                                       jax.random.PRNGKey(self.seed))
        deltas = jnp.zeros((self.n_chunks, self.m, self.chunk_len),
                           jnp.float32)
        return (params, self.opt.init(params), deltas)

    _carry0 = carry0  # legacy spelling of the segment contract

    # ------------------------------------------------------------- round
    def _device_batch(self, key: jnp.ndarray) -> Dict[str, jnp.ndarray]:
        cfg = self.arch
        b = {"tokens": jax.random.randint(key, (self.batch, self.seq_len),
                                          0, cfg.vocab)}
        if cfg.mrope_sections is not None:
            p = cfg.n_vision_tokens
            b["extra"] = 0.02 * jax.random.normal(
                key, (self.batch, p, cfg.d_model))
            b["positions"] = jnp.broadcast_to(
                jnp.arange(p + self.seq_len)[None, :, None],
                (self.batch, p + self.seq_len, 3)).astype(jnp.int32)
        if cfg.encoder is not None:
            b["frames"] = 0.02 * jax.random.normal(
                key, (self.batch, cfg.encoder.n_frames,
                      cfg.encoder.d_model))
        return b

    @stage("grads")
    def _grads(self, params, key: jnp.ndarray):
        """(m, d_pad) per-device flat gradients + mean local loss.

        ``lax.map`` over devices: one device's activations live at a
        time — the (m, d_pad) gradient block is the only m-sized buffer.
        """
        def one(dev_key):
            batch = self._device_batch(dev_key)

            def local_loss(p):
                return model_lib.loss_fn(p, self.arch, batch,
                                         compute_dtype=self.compute_dtype,
                                         remat=self.train_cfg.remat)
            (loss, _), grads = jax.value_and_grad(
                local_loss, has_aux=True)(params)
            gflat, _ = jax.flatten_util.ravel_pytree(grads)
            gflat = jnp.pad(gflat.astype(jnp.float32),
                            (0, self.d_pad - self.d))
            return gflat, loss

        dev_keys = jax.random.split(
            jax.random.fold_in(key, SALT_DATA), self.m)
        gflat, losses = jax.lax.map(one, dev_keys)
        return gflat, jnp.mean(losses)

    def _round(self, sch: Scheme, carry, t, key, mask):
        params, opt_state, deltas = carry
        gflat, loss = self._grads(params, key)
        grads = gflat.reshape(self.m, self.n_chunks, self.chunk_len)
        if mask is None:
            ghats, new_deltas, mets = stream_round(sch, grads, deltas,
                                                   t, key, self.ctx)
        else:
            ghats, new_deltas, mets = stream_round_masked(
                sch, grads, deltas, t, key, mask, self.ctx)
        with stage("optimizer"):
            ghat = ghats.reshape(self.d_pad)[: self.d]
            params, opt_state = self.opt.apply(params, self.unravel(ghat),
                                               opt_state)
        out = {"loss": loss,
               "metrics": {k: jnp.mean(v) for k, v in mets.items()}}
        return (params, opt_state, new_deltas), out

    # ------------------------------------------------------- traced entry
    def run_segment(self, overrides: Dict[str, jnp.ndarray],
                    keys: jnp.ndarray, mask, carry, t0):
        """Scan rounds ``t0 .. t0 + len(keys)`` from an explicit carry;
        returns ``(carry, outs)`` — the checkpoint/resume building block
        (same contract as ``CompiledExperiment.run_segment``)."""
        sch = (self.scheme.with_overrides(**overrides) if overrides
               else self.scheme)

        def body(carry, inp):
            t, key = inp
            return self._round(sch, carry, t, key, mask)

        ts = t0 + jnp.arange(keys.shape[0])
        return jax.lax.scan(body, carry, (ts, keys))

    def run(self, keys: jnp.ndarray,
            overrides: Optional[Dict[str, jnp.ndarray]] = None):
        """One full (jitted) run from the initial carry."""
        seg = jax.jit(lambda ov, k, c, t: self.run_segment(ov, k, None,
                                                           c, t),
                      donate_argnums=(2,))
        carry, outs = seg(overrides or {}, keys, self.carry0(),
                          jnp.int32(0))
        outs["params"] = carry[0]
        return outs


def serve_while_train(arch: ArchConfig, rounds: int = 2, *,
                      ota: Optional[OTAConfig] = None,
                      train_cfg: Optional[TrainConfig] = None,
                      m: int = 4, batch: int = 2, seq_len: int = 16,
                      chunk_size: int = 1 << 14,
                      serve_batch: int = 2, prompt_len: int = 4,
                      decode_steps: int = 4, seed: int = 0,
                      mesh=None, checkpoint_dir: Optional[str] = None,
                      checkpoint_every: int = 0, resume: bool = False,
                      verify_publish: bool = True) -> Dict[str, Any]:
    """The serve-while-train demo loop.

    Alternates one-round training segments with serving: after round
    ``t`` the decoded global params are :meth:`ServeStep.publish`-ed into
    the serve sharding (donated device-side swap) and ``decode_fn``
    answers a prefill + ``decode_steps`` greedy batch before round
    ``t+1`` starts.  With ``checkpoint_dir`` the carry snapshots every
    ``checkpoint_every`` rounds through ``train/checkpoint.py`` and
    ``resume=True`` continues bitwise (per-round keys are absolute, the
    carry is explicit).

    Returns ``{"losses", "metrics", "served_tokens", "publish_bitwise",
    "params", "round_seconds", "d", "n_chunks"}``; ``publish_bitwise``
    stays True iff every round's served params were bitwise-equal to that
    round's decoded globals (``verify_publish``; the acceptance pin).
    ``round_seconds`` is each round's host wall time, training segment
    through served batch (the first includes compilation).

    Under ``jax.profiler.trace`` each round shows the host spans
    ``repro:round`` (the training segment and its loss read back),
    ``repro:publish``, ``repro:verify_publish``, ``repro:serve`` (prefill
    and greedy decode, each token read back) and ``repro:checkpoint``.
    """
    from repro.experiments.engine import round_keys
    from repro.launch.mesh import make_local_mesh
    from repro.train.checkpoint import load_checkpoint, save_checkpoint
    from repro.train.serve import make_serve_step

    ota = ota or OTAConfig(projection="blocked", s_frac=0.25, k_frac=0.5,
                           block_size=1024)
    train_cfg = train_cfg or TrainConfig()
    mesh = mesh or make_local_mesh()
    fed = CompiledFedLLM(arch, train_cfg, ota, m=m, batch=batch,
                         seq_len=seq_len, chunk_size=chunk_size, seed=seed)
    serve = make_serve_step(arch, mesh, serve_batch,
                            prompt_len + decode_steps)
    keys = round_keys(rounds, seed)
    seg = jax.jit(lambda k, c, t: fed.run_segment({}, k, None, c, t),
                  donate_argnums=(1,))
    dev_copy = jax.jit(lambda p: jax.tree.map(jnp.copy, p))

    carry, t0 = fed.carry0(), 0
    ckpt = (os.path.join(checkpoint_dir, "fedllm_ckpt.npz")
            if checkpoint_dir else None)
    if resume and ckpt and os.path.exists(ckpt):
        loaded, t0 = load_checkpoint(ckpt)
        carry = jax.tree.unflatten(jax.tree.structure(carry),
                                   jax.tree.leaves(loaded))

    prompt = jnp.zeros((serve_batch, prompt_len), jnp.int32)
    losses, mets, served, publish_ok, secs = [], [], [], True, []
    for t in range(t0, rounds):
        tic = time.perf_counter()
        with span("round"):
            carry, outs = seg(keys[t:t + 1], carry, jnp.int32(t))
            losses.append(float(outs["loss"][0]))
            mets.append({k: float(v[0]) for k, v in outs["metrics"].items()})

        # publish round t's decoded globals (device-side copy so the
        # trainer's live carry is not donated away), then serve from them
        with span("publish"):
            view = serve.publish(dev_copy(carry[0]))
        if verify_publish:
            with span("verify_publish"):
                same = all(
                    np.array_equal(np.asarray(a), np.asarray(b))
                    for a, b in zip(jax.tree.leaves(view),
                                    jax.tree.leaves(carry[0])))
            publish_ok = publish_ok and same
        with span("serve"):
            logits, cache = serve.prefill_fn(view, serve.init_cache(),
                                             prompt)
            toks = []
            tok = jnp.argmax(logits[:, -1, :], axis=-1)[:, None].astype(
                jnp.int32)
            for i in range(decode_steps):
                toks.append(np.asarray(tok)[:, 0])
                logits, cache = serve.decode_fn(view, cache, tok,
                                                jnp.int32(prompt_len + i))
                tok = jnp.argmax(logits[:, -1, :], axis=-1)[:, None].astype(
                    jnp.int32)
        served.append(np.stack(toks, axis=1))
        secs.append(time.perf_counter() - tic)

        if ckpt and checkpoint_every and (t + 1) % checkpoint_every == 0:
            with span("checkpoint"):
                save_checkpoint(ckpt, jax.tree.map(np.asarray, carry),
                                step=t + 1)

    return {"losses": np.asarray(losses), "metrics": mets,
            "served_tokens": served, "publish_bitwise": publish_ok,
            "params": carry[0], "round_seconds": secs, "d": fed.d,
            "n_chunks": fed.n_chunks}
