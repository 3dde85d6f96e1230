"""Serve while training: the streamed A-DSGD round at a model's published
widths, each round followed by serving from its published globals.

One iteration is what ``repro.train.fedllm.serve_while_train`` does per
round, on the program's own pieces:

* one round of ``CompiledFedLLM.run_segment``, jitted with the carry
  donated, and a host read of its loss;
* ``ServeStep.publish`` of a device copy of the round's globals;
* one greedy batch through ``prefill_fn`` and ``decode_fn``, each token
  read back to the host as it is produced.

The traffic file sets the simulated devices (``m``), their batch and
sequence length (the program draws the tokens from each round key), the
stream's chunk length, and the served batch, prompt and decode lengths.
Weights are made on the device from the seed in one call.  Set-up runs
the first ``check_steps`` iterations through the same calls as the
window; their losses, the first gradient as the optimizer got it and the
parameters' change are what the reference checks of training.  Every
token served, in set-up and in the window, is checked against the
reference's logits after the round that served it: the reference follows
the training through every round of the run.  Set-up also checks, on the
device, that the published tree is bitwise the round's globals.
"""
from __future__ import annotations

import gc
import math
import time
from typing import Dict, Optional

import jax
import jax.flatten_util
import jax.numpy as jnp
import numpy as np

from bench import compare, counts, generate
from bench.reference import ota as ref_ota

#: rounds whose keys and prompts are made up front (set-up + window)
MAX_ROUNDS = 256
#: chunks per step of the reference's maps over the stream
REF_CHUNKS = 128
#: Adam's first-moment decay (the configuration's optimizer)
B1 = 0.9


class Loop:
    def __init__(self, cell, seed: int, span):
        from repro.configs.base import ArchConfig, OTAConfig, TrainConfig
        from repro.launch.mesh import make_local_mesh
        from repro.train.fedllm import CompiledFedLLM
        from repro.train.serve import make_serve_step

        self.cell, self.seed, self.span = cell, seed, span
        cfg, tr = cell.config, cell.traffic
        self.cfg, self.tr = cfg, tr
        self.ref = cell.reference()
        arch = ArchConfig(
            name=cfg["name"], family="dense",
            n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
            n_heads=cfg["num_attention_heads"],
            n_kv_heads=cfg["num_key_value_heads"],
            d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
            head_dim=cfg["head_dim"],
            tie_embeddings=cfg["tie_word_embeddings"],
            norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"])
        self.ota = OTAConfig(**cfg["ota"])
        self.train = TrainConfig(**cfg["train"])
        self.fed = CompiledFedLLM(arch, self.train, self.ota, m=tr["m"],
                                  batch=tr["batch"], seq_len=tr["seq_len"],
                                  chunk_size=tr["chunk_size"])
        self.serve = make_serve_step(arch, make_local_mesh(),
                                     tr["serve_batch"],
                                     tr["prompt_len"] + tr["decode_steps"])
        fed = self.fed
        self.seg = jax.jit(lambda k, c, t: fed.run_segment({}, k, None, c, t),
                           donate_argnums=(1,))
        self.dev_copy = jax.jit(lambda p: jax.tree.map(jnp.copy, p))
        self.differ = jax.jit(lambda a, b: sum(
            jnp.logical_not(jnp.array_equal(x, y)).astype(jnp.int32)
            for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))))
        self.keys = generate.round_keys(seed, MAX_ROUNDS)
        self.prompts = generate.prompts(seed, MAX_ROUNDS, tr["serve_batch"],
                                        tr["prompt_len"], cfg["vocab_size"])
        self.wkey = generate.base_key(seed, generate.WEIGHTS)
        self.t = 0
        self.carry = None
        self.prog: Dict = {}

    # ----------------------------------------------------------- program
    def _init(self, key):
        params = self.ref.init_params(self.cfg, key)
        fed = self.fed
        return (params, fed.opt.init(params),
                jnp.zeros((fed.n_chunks, fed.m, fed.chunk_len), jnp.float32))

    def _one(self, check_publish: bool = False):
        """One round, then one served batch; returns (loss, tokens (B, n),
        leaves of the published tree that differ from the globals)."""
        t, tr, serve = self.t, self.tr, self.serve
        with self.span("round"):
            self.carry, outs = self.seg(self.keys[t:t + 1], self.carry,
                                        jnp.int32(t))
            loss = float(outs["loss"][0])
        with self.span("serve"):
            with self.span("publish"):
                view = serve.publish(self.dev_copy(self.carry[0]))
            with self.span("prefill"):
                logits, cache = serve.prefill_fn(view, serve.init_cache(),
                                                 self.prompts[t])
                tok = jnp.argmax(logits[:, -1, :], axis=-1)[:, None].astype(
                    jnp.int32)
                toks = [np.asarray(tok)[:, 0]]
            for i in range(tr["decode_steps"] - 1):
                with self.span("decode"):
                    logits, cache = serve.decode_fn(
                        view, cache, tok, jnp.int32(tr["prompt_len"] + i))
                    tok = jnp.argmax(logits[:, -1, :], axis=-1)[
                        :, None].astype(jnp.int32)
                    toks.append(np.asarray(tok)[:, 0])
        differ = int(self.differ(view, self.carry[0])) if check_publish else 0
        self.t += 1
        return loss, np.stack(toks, axis=1), differ

    def setup(self):
        t0 = time.perf_counter()
        self.carry = jax.block_until_ready(jax.jit(self._init)(self.wkey))
        self.setup_parts = {"weights_s": time.perf_counter() - t0,
                            "check_rounds_s": []}
        losses, served = [], []
        differ = 0
        for step in range(self.tr["check_steps"]):
            t0 = time.perf_counter()
            loss, toks, d = self._one(check_publish=True)
            self.setup_parts["check_rounds_s"].append(time.perf_counter() - t0)
            losses.append(loss)
            served.append(toks)
            differ += d
            if step == 0:
                grad = jax.device_get(jax.jit(
                    lambda m: ref_ota.leaf_norms(
                        jax.tree.map(lambda x: x / (1 - B1), m)))(
                            self.carry[1]["m"]))
        update = jax.device_get(jax.jit(
            lambda p, k: ref_ota.leaf_norms(jax.tree.map(
                lambda a, b: a - b, p, self.ref.init_params(self.cfg, k))))(
                    self.carry[0], self.wkey))
        self.prog = {"losses": losses, "grad": grad, "update": update,
                     "served": served, "publish_differ": differ}

    def iteration(self):
        loss, toks, _ = self._one()
        self.prog["served"].append(toks)
        return 1, int(not math.isfinite(loss))

    def finish(self) -> int:
        return 0

    def end_to_end(self, win) -> Dict[str, float]:
        return {"round_s": win.seconds / win.units}

    def counts(self) -> Dict[str, float]:
        fed, ota, tr = self.fed, self.ota, self.tr
        c = ota.block_size
        s_block = max(2, int(round(ota.s_frac * c)))
        n_blocks = fed.d_pad // c
        tokens = tr["m"] * tr["batch"] * tr["seq_len"]
        proj = counts.project_flops(tr["m"] * n_blocks, s_block, c)
        amp = counts.amp_blocked_flops(n_blocks, s_block, c, ota.amp_iters)
        return {
            "unit_flops": (counts.model_train_flops(fed.d, tokens)
                            + proj + amp),
            "amp_flops": amp,
            "amp_bytes": counts.amp_blocked_bytes(n_blocks, s_block, c),
            "project_flops": proj,
            "project_bytes": counts.project_bytes(tr["m"] * n_blocks,
                                                  s_block, c),
        }

    def info(self) -> Dict:
        return {"d": self.fed.d, "d_pad": self.fed.d_pad,
                "n_chunks": self.fed.n_chunks, "m": self.fed.m,
                "losses": self.prog.get("losses"),
                "setup_parts": self.setup_parts}

    def release(self):
        self.carry = None
        self.seg = self.dev_copy = self.serve = self.differ = None
        gc.collect()

    # --------------------------------------------------------- reference
    def reference(self, mode: str = "bf16", codec: str = "f32",
                  rows: Optional[int] = None, drop_device: bool = False,
                  served: Optional[np.ndarray] = None) -> Dict:
        """The reference's readings: over the first ``check_steps`` steps
        the losses, first gradient and change norms per leaf; after each
        step its logits at every served position of ``served`` (the
        program's served tokens by default, one ``(B, decode_steps)``
        array per round the run made; ``"greedy"``: the reference's own
        greedy tokens over the check steps, returned under ``served``).

        ``mode`` is the model's precision and ``codec`` the codec's
        (``f32`` at the highest matmul precision, or ``bf16``); ``rows``
        trains each device on its first rows only and ``drop_device``
        leaves the last device's frame out of the MAC sum: the faults a
        limit is held against."""
        cfg, tr, ota, fed = self.cfg, self.tr, self.ota, self.fed
        served = self.prog["served"] if served is None else served
        greedy = isinstance(served, str)
        steps = tr["check_steps"] if greedy else len(served)
        c = ota.block_size
        s_block = max(2, int(round(ota.s_frac * c)))
        nb = fed.chunk_len // c
        k = max(1, int(ota.k_frac * nb * s_block))
        A = jax.jit(ref_ota.rademacher_blocks, static_argnums=(0, 1, 2, 3))(
            ota.seed, nb, s_block, c)
        ref_round = jax.jit(self._ref_round, donate_argnums=(0, 1, 2),
                            static_argnames=("mode", "codec", "rows", "k",
                                             "drop_device"))
        ref_logits = jax.jit(lambda p, s: self.ref.logits(p, s, cfg, mode))
        change = jax.jit(lambda prm, key: ref_ota.leaf_norms(jax.tree.map(
            lambda a, b: a - b, prm, self.ref.init_params(cfg, key))))
        params = jax.jit(lambda key: self.ref.init_params(cfg, key))(
            self.wkey)
        opt = jax.jit(ref_ota.adam_init)(params)
        deltas = jnp.zeros((tr["m"], fed.n_chunks, fed.chunk_len),
                           jnp.float32)
        p = tr["prompt_len"]
        losses, logits, tokens = [], [], []
        for t in range(steps):
            params, opt, deltas, loss, grad = ref_round(
                params, opt, deltas, jnp.asarray(self.keys[t]), A,
                jnp.int32(t), mode=mode, codec=codec, rows=rows, k=k,
                drop_device=drop_device)
            if t < tr["check_steps"]:
                losses.append(float(loss))
            if t == 0:
                first = jax.device_get(grad)
            if t + 1 == tr["check_steps"]:
                update = jax.device_get(change(params, self.wkey))
            seq = np.asarray(self.prompts[t])
            if greedy:
                for _ in range(tr["decode_steps"] - 1):
                    nxt = np.asarray(ref_logits(params, seq)[:, -1]).argmax(-1)
                    seq = np.concatenate([seq, nxt[:, None]], axis=1)
            else:
                seq = np.concatenate([seq, served[t][:, :-1]], axis=1)
            lg = np.asarray(ref_logits(params, seq)[:, p - 1:], np.float32)
            logits.append(lg)
            tokens.append(lg.argmax(-1))
        del deltas, opt, A
        out = {"losses": losses, "grad": first, "update": update,
               "logits": np.stack(logits)}
        if greedy:
            out["served"] = np.stack(tokens)
        return out

    def _ref_round(self, params, opt, deltas, key, A, t, *, mode, codec,
                   rows, k, drop_device):
        cfg, tr, ota, fed = self.cfg, self.tr, self.ota, self.fed
        m, n_chunks, chunk_len = tr["m"], fed.n_chunks, fed.chunk_len
        nb, s_block, c = A.shape
        toks = ref_ota.batch_tokens(key, m, tr["batch"], tr["seq_len"],
                                    cfg["vocab_size"], rows)
        use_mr = t < ota.mean_removal_steps
        p_t = jnp.float32(ota.p_avg)
        flat0, unravel = jax.flatten_util.ravel_pytree(params)
        d = flat0.shape[0]
        senders = m - 1 if drop_device else m

        def encode(args):
            g, dl = args
            g_ec = g + dl
            tau = ref_ota.sampled_threshold(g_ec, k)
            sp = jnp.where(jnp.abs(g_ec) >= tau, g_ec, 0.0)
            y = ref_ota.block_matvec(A, sp.reshape(nb, c), codec)
            return ref_ota.frame(y.reshape(-1), p_t, use_mr), g_ec - sp

        def device(carry, j):
            y_acc, dls = carry
            loss, g = jax.value_and_grad(
                lambda p: self.ref.loss(p, toks[j], cfg, mode))(params)
            gflat = jax.flatten_util.ravel_pytree(g)[0].astype(jnp.float32)
            gflat = jnp.pad(gflat, (0, n_chunks * chunk_len - d))
            fr, nd = jax.lax.map(encode, (gflat.reshape(n_chunks, chunk_len),
                                          dls[j]), batch_size=REF_CHUNKS)
            dls = jax.lax.dynamic_update_index_in_dim(dls, nd, j, 0)
            sent = (j < senders).astype(jnp.float32)
            return (y_acc + sent * fr, dls), loss

        y0 = jnp.zeros((n_chunks, nb * s_block + 2), jnp.float32)
        (y, deltas), losses = jax.lax.scan(device, (y0, deltas),
                                           jnp.arange(m))
        chunk_keys = jax.vmap(lambda i: jax.random.fold_in(
            jax.random.fold_in(key, ref_ota.SALT_CHUNK), i))(
                jnp.arange(n_chunks))
        noise = jax.vmap(lambda kk: jax.random.normal(
            jax.random.fold_in(kk, ref_ota.SALT_NOISE), (y.shape[1],)))(
                chunk_keys)
        y = y + jnp.sqrt(jnp.float32(ota.sigma2)) * noise
        body = ref_ota.server_body(y, use_mr).reshape(n_chunks, nb, s_block)
        xhat = jax.lax.map(
            lambda yb: ref_ota.amp_blocks(yb, A, ota.amp_iters, codec), body,
            batch_size=REF_CHUNKS)
        ghat = unravel(xhat.reshape(-1)[:d])
        tc = self.train
        params, opt = ref_ota.adam_step(params, ghat, opt, lr=tc.lr,
                                        warmup=tc.warmup_steps,
                                        total=tc.total_steps)
        return params, opt, deltas, jnp.mean(losses), ref_ota.leaf_norms(ghat)

    def readings(self, ref: Dict, run: Dict) -> Dict[str, float]:
        """The compared numbers of ``run`` (the program's, or the
        reference's under a control or a fault) against ``ref``."""
        out = compare.training(run, ref)
        out["served_logit_gap"] = compare.served_gap(ref["logits"],
                                                     run["served"])
        out["publish_mismatch"] = float(run.get("publish_differ", 0))
        return out

    def reference_check(self) -> Dict[str, float]:
        return self.readings(self.reference(), self.prog)

    def control_readings(self, faults: bool = True) -> Dict[str, Dict]:
        """Readings of the control (the reference one precision step down:
        fp8 model, bf16 codec) and of the faults planted in the reference,
        each against the reference, on the reference's own greedy tokens.
        The control's served tokens are those it puts first at the same
        positions.  Needs no run of the program."""
        base = self.reference(served="greedy")
        served = base["served"]
        variants = {"control": {"mode": "fp8", "codec": "bf16"}}
        if faults:
            variants["half_batch"] = {"rows": max(1, self.tr["batch"] // 2)}
            if self.tr["m"] > 1:
                variants["no_exchange"] = {"drop_device": True}
        out = {}
        for name, kw in variants.items():
            run = self.reference(served=served, **kw)
            run["served"] = (run["logits"].argmax(-1) if name == "control"
                             else served)
            out[name] = self.readings(base, run)
        if faults:
            altered = dict(base, served=(served + 1) % self.cfg["vocab_size"])
            out["token_altered"] = self.readings(base, altered)
        return out
