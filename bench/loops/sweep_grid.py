"""A paper-scale sweep grid on the compiled experiment engine.

One program per scheme of the traffic's ``schemes``: the engine's traced
entry ``CompiledExperiment.run_segment``, vmapped over the power levels
``p_avg`` exactly as ``repro.experiments.run_sweep`` vmaps a grid (per
point power schedule, and for digital schemes the per point bit budget),
jitted with the carry donated.  Each call runs ``segment_rounds`` rounds
of every point, so training continues across calls; one iteration calls
every scheme's program once, then reads the previous iteration's test
accuracy and loss back to the host while the device works on this one.
The configuration fixes the model, the devices and their data (made on
the device from the seed), the channel and the matmul precision the
program is traced under.  Set-up runs the first iteration; its first
``check_steps`` test losses, the optimizer's first moment and the
parameters' change after the segment are what the reference checks.
"""
from __future__ import annotations

import gc
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import compare, counts, generate
from bench.reference import ota as ref_ota

#: Adam's first-moment decay (the configuration's optimizer)
B1 = 0.9


class Loop:
    def __init__(self, cell, seed: int, span):
        from repro.configs.base import OTAConfig
        from repro.core import power
        from repro.experiments.engine import CompiledExperiment, Experiment

        self.cell, self.seed, self.span = cell, seed, span
        cfg, tr = cell.config, cell.traffic
        self.cfg, self.tr = cfg, tr
        self.ref = cell.reference()
        jax.config.update("jax_default_matmul_precision",
                          cfg["matmul_precision"])
        self.data = generate.classification(
            seed, n_train=cfg["n_train"], n_test=cfg["n_test"],
            dim=cfg["dim"], n_classes=cfg["n_classes"],
            rank=cfg["data"]["rank"], noise=cfg["data"]["noise"],
            m=cfg["m"], b=cfg["b"])
        xd, yd, xt, yt = self.data
        self.base_key = generate.base_key(seed, generate.ROUNDS)
        self.grid = [float(p) for p in tr["p_avg"]]
        self.rounds = int(tr["segment_rounds"])
        self.runs: List[Dict] = []
        for scheme in tr["schemes"]:
            ota = OTAConfig(scheme=scheme, **cfg["ota"])
            exp = Experiment(cfg=ota, steps=ota.total_steps,
                             lr=cfg["train"]["lr"],
                             eval_every=cfg["train"]["eval_every"],
                             optimizer=cfg["train"]["optimizer"])
            ce = CompiledExperiment(xd, yd, xt, yt, exp)
            p_rows = [power.schedule_array(ota.total_steps, p,
                                           ota.power_schedule)
                      for p in self.grid]
            ov = {"p_sched": jnp.asarray(np.stack(p_rows), jnp.float32)}
            q_rows = None
            if hasattr(ce.scheme, "q_sched"):
                q_rows = np.stack([ce.scheme.build_q_schedule(cfg["m"], p)
                                   for p in p_rows])
                ce.scheme.q_max = int(max(int(q_rows.max()), 1))
                ov["q_sched"] = jnp.asarray(q_rows, jnp.int32)
            self.runs.append({"scheme": scheme, "ota": ota, "ce": ce,
                              "ov": ov, "q_rows": q_rows,
                              "seg": self._segment(ce, ov)})
        self._ref_step_jit = jax.jit(self._ref_step, static_argnames=(
            "ota", "k", "q", "codec", "senders"))
        self.t = 0
        self.carries = None
        self.pending = None
        self.accs: List[float] = []
        self.prog: Dict = {}

    def _keys(self, t0):
        """The round keys of rounds t0 .. t0 + segment_rounds."""
        return jax.vmap(lambda i: jax.random.fold_in(self.base_key, i))(
            t0 + jnp.arange(self.rounds))

    def _segment(self, ce, ov):
        ov_axes = {k: 0 for k in ov}
        keys_of = self._keys

        def seg(ov, carry, t0):
            keys = keys_of(t0)
            return jax.vmap(lambda o, c: ce.run_segment(o, keys, None, c, t0),
                            in_axes=(ov_axes, 0))(ov, carry)

        return jax.jit(seg, donate_argnums=(1,))

    def _carry0(self, ce):
        g = len(self.grid)
        return jax.jit(lambda: jax.tree.map(
            lambda x: jnp.broadcast_to(x, (g,) + x.shape), ce._carry0()))()

    # ----------------------------------------------------------- program
    def _dispatch(self):
        outs = []
        with self.span("segment"):
            for i, run in enumerate(self.runs):
                self.carries[i], out = run["seg"](run["ov"], self.carries[i],
                                                  jnp.int32(self.t))
                outs.append(out)
        self.t += self.rounds
        return outs

    def _read(self, outs):
        """Host read of a finished iteration's test accuracy and loss;
        returns (non-finite losses, losses per scheme (points, rounds))."""
        with self.span("read"):
            accs = [np.asarray(o["acc"]) for o in outs]
            losses = [np.asarray(o["loss"]) for o in outs]
        self.accs = [float(a[:, -1].mean()) for a in accs]
        return sum(int((~np.isfinite(v)).sum()) for v in losses), losses

    def setup(self):
        self.carries = [self._carry0(run["ce"]) for run in self.runs]
        _, losses = self._read(self._dispatch())
        moment = [jax.device_get(jax.jit(jax.vmap(
            lambda st: ref_ota.leaf_norms(self._mhat(st))))(c[1]))
            for c in self.carries]
        update = [jax.device_get(jax.jit(jax.vmap(ref_ota.leaf_norms))(c[0]))
                  for c in self.carries]
        n = self.tr["check_steps"]
        self.prog = {}
        for i, run in enumerate(self.runs):
            for g, p in enumerate(self.grid):
                self.prog[(run["scheme"], p)] = {
                    "losses": [float(v) for v in losses[i][g, :n]],
                    "grad": {k: float(v[g]) for k, v in moment[i].items()},
                    "update": {k: float(v[g]) for k, v in update[i].items()},
                }

    @staticmethod
    def _mhat(state):
        """Adam's bias-corrected first moment: the gradient as the optimizer
        got it, averaged over the steps taken."""
        c = state["count"].astype(jnp.float32)
        return jax.tree.map(lambda m: m / (1 - B1 ** c), state["m"])

    def iteration(self):
        outs = self._dispatch()
        bad = 0
        if self.pending is not None:
            bad, _ = self._read(self.pending)
        self.pending = outs
        return len(self.runs) * len(self.grid) * self.rounds, bad

    def finish(self) -> int:
        bad = 0
        if self.pending is not None:
            bad, _ = self._read(self.pending)
            self.pending = None
        return bad

    def end_to_end(self, win) -> Dict[str, float]:
        return {"grid_rounds_per_s": win.units / win.seconds}

    def counts(self) -> Dict[str, float]:
        cfg = self.cfg
        d, m, b = cfg["d"], cfg["m"], cfg["b"]
        per_point = {}
        for run in self.runs:
            ota = run["ota"]
            model = counts.softmax_regression_flops(m * b, cfg["dim"],
                                                    cfg["n_classes"])
            evals = counts.softmax_eval_flops(cfg["n_test"], cfg["dim"],
                                              cfg["n_classes"])
            codec = 0.0
            if ota.scheme == "a_dsgd":
                s_tilde = ota.s_for(d) - 2
                codec = (counts.dense_project_flops(s_tilde, d, m)
                         + counts.amp_dense_flops(s_tilde, d, ota.amp_iters))
            per_point[run["scheme"]] = model + evals + codec
        return {"unit_flops": sum(per_point.values()) / len(per_point),
                "per_scheme_flops": per_point}

    def info(self) -> Dict:
        return {"grid": self.grid, "schemes": [r["scheme"] for r in self.runs],
                "segment_rounds": self.rounds, "rounds_run": self.t,
                "q_t": {r["scheme"]: r["q_rows"][:, 0].tolist()
                        for r in self.runs if r["q_rows"] is not None},
                "final_acc": self.accs}

    def release(self):
        self.carries = self.pending = None
        for run in self.runs:
            run["seg"] = run["ce"] = None
        gc.collect()

    # --------------------------------------------------------- reference
    def reference(self, codec: str = "f32", rows: Optional[int] = None,
                  senders: Optional[int] = None) -> Dict:
        """The reference's readings per (scheme, point) over the first
        segment.  ``codec`` is the precision of every product (model and
        codec: the configuration states float32 throughout); ``rows``
        trains each device on its first rows only and ``senders`` keeps
        only the first devices' frames in the MAC sum: the faults a limit
        is held against."""
        out = {}
        for run in self.runs:
            for p in self.grid:
                out[(run["scheme"], p)] = self._ref_run(
                    run["ota"], p, codec, rows, senders)
        return out

    def _ref_run(self, ota, p_avg, codec, rows, senders):
        cfg, ref = self.cfg, self.ref
        xd, yd, xt, yt = self.data
        if rows is not None:
            xd, yd = xd[:, :rows], yd[:, :rows]
        d, m = cfg["d"], cfg["m"]
        s = ota.s_for(d)
        A = (ref_ota.gaussian_matrix(ota.seed, s - 2, d)
             if ota.scheme == "a_dsgd" else None)
        k = max(1, int(ota.k_frac * s))
        q = (ref_ota.ddsgd_budget(d, s, m, p_avg, ota.sigma2,
                                  min(d // 2, 1 << 16))
             if ota.scheme == "d_dsgd" else 0)
        params = ref.init_params(cfg)
        opt = ref_ota.adam_init(params)
        deltas = jnp.zeros((m, d), jnp.float32)
        step = self._ref_step_jit
        losses = []
        for t in range(self.rounds):
            key = jax.random.fold_in(self.base_key, t)
            params, opt, deltas = step(
                params, opt, deltas, xd, yd, key, A, jnp.int32(t),
                jnp.float32(p_avg), ota=ota, k=k, q=q, codec=codec,
                senders=senders)
            if t < self.tr["check_steps"]:
                losses.append(float(ref.loss(params, xt, yt, codec)))
        mhat = jax.tree.map(lambda v: v / (1 - B1 ** self.rounds), opt["m"])
        return {"losses": losses,
                "grad": {k_: float(v) for k_, v in
                         ref_ota.leaf_norms(mhat).items()},
                "update": {k_: float(v) for k_, v in
                           ref_ota.leaf_norms(params).items()}}

    def _ref_step(self, params, opt, deltas, xd, yd, key, A, t, p_t, *,
                  ota, k, q, codec, senders):
        cfg, ref = self.cfg, self.ref
        m = cfg["m"]
        grads = jax.vmap(lambda x, y: ref.flat_grad(params, x, y, codec))(
            xd, yd)
        g_ec = grads + deltas
        sent = (jnp.arange(m) < (m if senders is None else senders)
                ).astype(jnp.float32)[:, None]
        if ota.scheme == "a_dsgd":
            sp = jax.vmap(lambda v: ref_ota.top_k_keep(v, k))(g_ec)
            use_mr = t < ota.mean_removal_steps
            y = ref_ota.matmul("sd,md->ms", A, sp, codec)
            frames = ref_ota.frame(y, p_t, use_mr)
            noise = jax.random.normal(
                jax.random.fold_in(key, ref_ota.SALT_NOISE),
                (frames.shape[1],), jnp.float32)
            rx = jnp.sum(sent * frames, 0) + jnp.sqrt(
                jnp.float32(ota.sigma2)) * noise
            ghat = ref_ota.amp_dense(ref_ota.server_body(rx, use_mr), A,
                                     ota.amp_iters, codec)
        else:
            sp = jax.vmap(lambda v: ref_ota.sbc(v, q))(g_ec)
            ghat = jnp.sum(sent * sp, 0) / m
        params, opt = ref_ota.adam_step(params, ref.unflatten(ghat, cfg),
                                        opt, lr=cfg["train"]["lr"])
        return params, opt, g_ec - sp

    def readings(self, ref: Dict, run: Dict) -> Dict[str, float]:
        """The worst of each compared number over the grid's points."""
        worst: Dict[str, float] = {}
        for key, r in run.items():
            for name, v in compare.training(r, ref[key]).items():
                worst[name] = max(worst.get(name, 0.0), v)
        return worst

    def reference_check(self) -> Dict[str, float]:
        return self.readings(self.reference(), self.prog)

    def control_readings(self, faults: bool = True) -> Dict[str, Dict]:
        """Readings of the control (the reference in bfloat16) and of the
        faults planted in the reference, each against the reference.
        Needs no run of the program."""
        base = self.reference()
        variants = {"control": {"codec": "bf16"}}
        if faults:
            variants["half_batch"] = {"rows": self.cfg["b"] // 2}
            variants["no_exchange"] = {"senders": self.cfg["m"] // 2}
        return {name: self.readings(base, self.reference(**kw))
                for name, kw in variants.items()}
