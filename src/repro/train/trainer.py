"""Distributed train step: two partial-manual shard_map phases + auto update.

Phase 1 (manual = ota_axes, auto = rest): per-OTA-device gradients — the loss
is the LOCAL batch mean, so no cross-device reduction happens implicitly; the
gradient pytree is flattened to a padded d-vector sharded over the auto axes.

Phase 2 (manual = ota_axes + shard axes): the scheme's aggregation pipeline
on gradient *slices* — every device owns d_pad / n_shards entries of its
replica's vector, nothing d-sized is replicated or gathered.  The scheme is
resolved from the registry (repro.core.schemes.get_scheme) and run by the
generic slice driver (core/distributed.sharded_round) under a MACContext
describing the placement.  The MAC superposition is the psum over ota_axes;
AWGN is injected once per channel slice.

Phase 3 (auto): unravel ghat and apply the optimizer under GSPMD.

The error accumulator Delta is carried as a (M_1..M_k, d_pad) array split
over the manual axes and sharded over the auto axes along d — the paper's
M x d error-feedback memory is explicit, placed, and visible to the dry-run.

``ota_axes=('data',)`` (or ('pod','data')) maps one edge device per data
coordinate; ``ota_axes=('pod',)`` is the hierarchical "edge site" variant:
intra-pod aggregation is the ideal mean (emerges from auto data-parallel
grads), the MAC runs across pods.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Sequence, Tuple

import jax
import jax.flatten_util
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig, OTAConfig, TrainConfig
from repro.core import distributed
from repro.core.schemes import MACContext, get_scheme
from repro.models import model as model_lib
from repro.optim.optim import make_optimizer
from repro.sharding.specs import named_sharding_tree, param_specs
from repro.tracing import stage


def _pad_multiple(d: int, m: int) -> int:
    return -(-d // m) * m


def abstract_params(cfg: ArchConfig):
    return jax.eval_shape(lambda k: model_lib.init_params(cfg, k),
                          jax.random.PRNGKey(0))


def ravel_meta(aparams):
    """``(d, unravel)`` for an abstract param tree: total parameter count
    and the flat-vector -> pytree unraveller with a *stable leaf ordering*
    (ravel_pytree's canonical flatten order — the contract the streamed
    fedllm driver and the flat trainer layout both rely on: every device
    and the PS agree on which gradient entry lands in which chunk).

    The unraveller is built from an eval_shape tree via closure over
    abstract zeros, so nothing d-sized is materialised here.
    """
    d = int(sum(int(np.prod(x.shape)) for x in jax.tree.leaves(aparams)))

    def unravel(flat):
        zeros = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), aparams)
        _, unr = jax.flatten_util.ravel_pytree(zeros)
        return unr(flat)

    return d, unravel


@dataclasses.dataclass
class TrainStep:
    arch: ArchConfig
    train: TrainConfig
    ota: OTAConfig
    ota_axes: Tuple[str, ...]
    mesh: Any
    m_devices: int
    d: int
    d_pad: int
    delta_shape: Tuple[int, ...]
    delta_sharding: Any
    param_sharding: Any
    opt_sharding: Any
    batch_spec: Any
    _jit_cache: Dict[Any, Any] = dataclasses.field(default_factory=dict)
    _builder: Any = None

    def jitted(self, batch_tree):
        sig = tuple(sorted(batch_tree.keys()))
        if sig not in self._jit_cache:
            self._jit_cache[sig] = self._builder(batch_tree)
        return self._jit_cache[sig]

    def init_state(self, key):
        """``(params, opt_state, delta)`` created in their shardings, so
        nothing replicated or (M, d)-sized lands on one device first."""
        opt = make_optimizer(self.train)

        def init(key):
            params = model_lib.init_params(self.arch, key)
            delta = jnp.zeros(self.delta_shape,
                              jnp.dtype(self.ota.state_dtype))
            return params, opt.init(params), delta

        return jax.jit(init, out_shardings=(
            self.param_sharding, self.opt_sharding,
            self.delta_sharding))(key)


def make_train_step(arch: ArchConfig, train_cfg: TrainConfig, ota: OTAConfig,
                    mesh, ota_axes: Sequence[str] = ("data",),
                    donate: bool = True, loss_chunk: int = 2048) -> TrainStep:
    ota_axes = tuple(ota_axes)
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    m_manual = int(np.prod([axis_sizes[a] for a in ota_axes]))
    auto_axes = tuple(a for a in mesh.axis_names if a not in ota_axes)
    model_size = axis_sizes.get("model", 1)
    n_shards = int(np.prod([axis_sizes[a] for a in auto_axes])) if auto_axes else 1

    aparams = abstract_params(arch)
    d, unravel = ravel_meta(aparams)
    pad_unit = (ota.block_size * n_shards if ota.projection == "blocked"
                else max(n_shards, 1))
    d_pad = _pad_multiple(d, max(pad_unit, 1))

    groups = None
    m_eff = m_manual
    if ota.num_groups and ota.num_groups < m_manual:
        # the grouped psum runs over the LAST manual axis only (psum with
        # axis_index_groups is per-axis); distribute the requested group
        # count across the other manual axes (e.g. pods)
        m_last = axis_sizes[ota_axes[-1]]
        other = m_manual // m_last
        npg = max(1, ota.num_groups // other)
        gs = m_last // npg
        groups = [[g * gs + i for i in range(gs)] for g in range(npg)]
        m_eff = npg * other
    opt = make_optimizer(train_cfg)
    compute_dtype = jnp.dtype(train_cfg.compute_dtype)
    scheme = get_scheme(ota, d_pad, m_eff)
    agg_ctx = MACContext(
        m=m_eff, device_axes=ota_axes, shard_axes=auto_axes,
        groups=(tuple(tuple(g) for g in groups) if groups is not None
                else None),
        fading=ota.fading, csi=scheme.csi, d_pad=d_pad,
        frame_dtype=(jnp.dtype(ota.frame_dtype)
                     if ota.frame_dtype != "float32" else None),
        shard_decode=ota.shard_decode)
    inner_spec = P(auto_axes) if auto_axes else P()

    # ---------------- phase 1: per-device grads ---------------------------
    @stage("grads")
    def grads_body(params, batch):
        def local_loss(p):
            return model_lib.loss_fn(p, arch, batch,
                                     compute_dtype=compute_dtype,
                                     remat=train_cfg.remat,
                                     loss_chunk=loss_chunk)
        (loss, metrics), grads = jax.value_and_grad(local_loss,
                                                    has_aux=True)(params)
        gflat, _ = jax.flatten_util.ravel_pytree(grads)
        gflat = jnp.pad(gflat.astype(jnp.float32), (0, d_pad - d))
        gflat = jax.lax.with_sharding_constraint(gflat, inner_spec)
        loss_g = loss
        for ax in ota_axes:
            loss_g = jax.lax.psum(loss_g, ax)
        gflat = gflat.reshape((1,) * len(ota_axes) + (d_pad,))
        return gflat, dict(metrics, global_loss=loss_g / m_manual)

    # ---------------- phase 2: OTA aggregation on slices ------------------
    def agg_body(gflat_slice, delta_slice, step, key):
        ghat, new_delta, metrics = distributed.sharded_round(
            scheme, gflat_slice.reshape(-1), delta_slice.reshape(-1),
            step, key, agg_ctx)
        return (ghat.reshape(gflat_slice.shape),
                new_delta.reshape(delta_slice.shape), metrics)

    manual1 = set(ota_axes)
    manual2 = set(ota_axes) | set(auto_axes)
    pspecs = param_specs(aparams, model_size)
    opt_abstract = jax.eval_shape(opt.init, aparams)
    ospecs = {k: (pspecs if k in ("m", "v") else P())
              for k in opt_abstract}
    delta_spec_full = P(*ota_axes, auto_axes if auto_axes else None)
    batch_spec = P(ota_axes)
    # jit-level batch sharding also spreads over auto data-like axes
    batch_jit_spec = P(ota_axes + tuple(a for a in auto_axes if a != "model"))
    ns = lambda s: NamedSharding(mesh, s)                       # noqa: E731
    param_sh = named_sharding_tree(mesh, pspecs)
    opt_sh = named_sharding_tree(mesh, ospecs)
    delta_sh = ns(delta_spec_full)
    rep = lambda t: jax.tree.map(lambda _: P(), t)              # noqa: E731

    def builder(batch_tree):
        phase1 = jax.shard_map(
            grads_body, mesh=mesh,
            in_specs=(rep(aparams),
                      jax.tree.map(lambda _: batch_spec, batch_tree)),
            out_specs=(P(*ota_axes, None), P()),
            axis_names=manual1, check_vma=False)
        phase2 = jax.shard_map(
            agg_body, mesh=mesh,
            in_specs=(delta_spec_full, delta_spec_full, P(), P()),
            out_specs=(P(None, auto_axes if auto_axes else None),
                       delta_spec_full, P()),
            axis_names=manual2, check_vma=False)

        def step_fn(params, opt_state, delta, batch, step, key):
            gstacked, metrics = phase1(params, batch)
            gstacked = gstacked.reshape(
                tuple(axis_sizes[a] for a in ota_axes) + (d_pad,))
            gstacked = jax.lax.with_sharding_constraint(
                gstacked, ns(delta_spec_full))
            ghat_s, new_delta, agg_metrics = phase2(
                gstacked, delta, step, key)
            ghat = ghat_s.reshape(d_pad)
            ghat = jax.lax.with_sharding_constraint(
                ghat, ns(P(auto_axes) if auto_axes else P()))
            with stage("optimizer"):
                ghat_tree = unravel(ghat[:d])
                params, opt_state = opt.apply(params, ghat_tree, opt_state)
            return params, opt_state, new_delta, {**metrics, **agg_metrics}

        in_sh = (param_sh, opt_sh, delta_sh,
                 jax.tree.map(lambda _: ns(batch_jit_spec), batch_tree),
                 ns(P()), ns(P()))
        jfn = jax.jit(step_fn, in_shardings=in_sh,
                      out_shardings=(param_sh, opt_sh, delta_sh, None),
                      donate_argnums=(0, 1, 2) if donate else ())
        return jfn

    # phase-2 slice layout: (M_1..M_k, d_pad) where the last dim shards over
    # auto axes; the shard_map in_spec P(*ota_axes, auto) slices both.
    delta_shape = tuple(axis_sizes[a] for a in ota_axes) + (d_pad,)
    return TrainStep(arch=arch, train=train_cfg, ota=ota, ota_axes=ota_axes,
                     mesh=mesh, m_devices=m_eff, d=d, d_pad=d_pad,
                     delta_shape=delta_shape, delta_sharding=delta_sh,
                     param_sharding=param_sh, opt_sharding=opt_sh,
                     batch_spec=batch_spec, _builder=builder)


# ===========================================================================
# "sliced" layout (§Perf optimisation O1): slice-local leafwise aggregation
# ===========================================================================
#
# The flat layout pays ~3x d bytes of all-gather/collective-permute per step
# re-laying param-sharded gradient leaves into a linearly-sharded d-vector
# and back.  The OTA pipeline never needed a canonical element order: top-k
# is order-free and the block-diagonal projection indexes blocks by id.  So
# define the d-vector as "concatenation of each model shard's local leaf
# pieces": every device flattens ITS OWN gradient pieces — zero d-sized
# collectives remain; the only cross-device traffic is the s-sized MAC psum
# and scalar coordination.
#
# Leaves replicated over 'model' (norm gains, non-divisible embeddings) are
# aggregated by a second, shard-replicated OTA sub-frame with its own power
# share; both sub-frames satisfy sum = P_t.


def _classify_leaves(aparams, pspecs):
    """Returns (paths, specs, sharded_mask, sizes_local, sizes_rep)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(aparams)
    sflat = jax.tree.leaves(pspecs)
    info = []
    for (path, leaf), spec in zip(flat, sflat):
        sharded = any(e == "model" for e in spec)
        info.append((path, leaf, spec, sharded))
    return info, treedef


def make_train_step_sliced(arch: ArchConfig, train_cfg: TrainConfig,
                           ota: OTAConfig, mesh,
                           ota_axes: Sequence[str] = ("data",),
                           donate: bool = True,
                           loss_chunk: int = 2048) -> "TrainStep":
    ota_axes = tuple(ota_axes)
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    m_manual = int(np.prod([axis_sizes[a] for a in ota_axes]))
    auto_axes = tuple(a for a in mesh.axis_names if a not in ota_axes)
    assert auto_axes == ("model",), (
        "sliced layout supports ota_axes covering all but the model axis")
    model_size = axis_sizes["model"]

    aparams = abstract_params(arch)
    pspecs = param_specs(aparams, model_size)
    info, treedef = _classify_leaves(aparams, pspecs)
    c = ota.block_size

    def local_size(leaf, spec, sharded):
        n = int(np.prod(leaf.shape))
        return n // model_size if sharded else n

    d_sh = sum(local_size(lf, s, sh) for _, lf, s, sh in info if sh)
    d_rep = sum(local_size(lf, s, sh) for _, lf, s, sh in info if not sh)
    d_sh_pad = _pad_multiple(max(d_sh, c), c)
    d_rep_pad = _pad_multiple(max(d_rep, c), c)
    d_total = d_sh * model_size + d_rep
    p_share_sh = (d_sh * model_size) / d_total
    d = int(sum(int(np.prod(x.shape)) for x in jax.tree.leaves(aparams)))

    groups = None
    m_eff = m_manual
    if ota.num_groups and ota.num_groups < m_manual:
        m_last = axis_sizes[ota_axes[-1]]
        other = m_manual // m_last
        npg = max(1, ota.num_groups // other)
        gs = m_last // npg
        groups = [[g * gs + i for i in range(gs)] for g in range(npg)]
        m_eff = npg * other

    opt = make_optimizer(train_cfg)
    compute_dtype = jnp.dtype(train_cfg.compute_dtype)
    frame_dtype = (jnp.dtype(ota.frame_dtype)
                   if ota.frame_dtype != "float32" else None)
    state_dtype = jnp.dtype(ota.state_dtype)
    scheme = get_scheme(ota, d_sh_pad * model_size + d_rep_pad, m_eff)
    groups_t = (tuple(tuple(g) for g in groups) if groups is not None
                else None)
    # two sub-frames: the model-sharded pieces and the replicated pieces,
    # each with its own power share (sum = P_t) and decorrelated RNG salt
    ctx_sh = MACContext(
        m=m_eff, device_axes=ota_axes, shard_axes=("model",),
        groups=groups_t, fading=ota.fading, csi=scheme.csi,
        d_pad=d_sh_pad * model_size,
        p_scale=p_share_sh, frame_dtype=frame_dtype,
        shard_decode=ota.shard_decode)
    ctx_rep = MACContext(
        m=m_eff, device_axes=ota_axes, shard_axes=(),
        groups=groups_t, fading=ota.fading, csi=scheme.csi,
        d_pad=d_rep_pad,
        p_scale=1.0 - p_share_sh, key_salt=1789, frame_dtype=frame_dtype,
        shard_decode=ota.shard_decode)

    # ---------------- phase 1: per-device grads (tree out) ----------------
    @stage("grads")
    def grads_body(params, batch):
        def local_loss(p):
            return model_lib.loss_fn(p, arch, batch,
                                     compute_dtype=compute_dtype,
                                     remat=train_cfg.remat,
                                     loss_chunk=loss_chunk)
        (loss, metrics), grads = jax.value_and_grad(local_loss,
                                                    has_aux=True)(params)
        grads = jax.tree.map(
            lambda g, s: jax.lax.with_sharding_constraint(
                g.astype(jnp.float32), s),
            grads, pspecs)
        loss_g = loss
        for ax in ota_axes:
            loss_g = jax.lax.psum(loss_g, ax)
        grads = jax.tree.map(lambda g: g[None], grads)
        return grads, dict(metrics, global_loss=loss_g / m_manual)

    # ---------------- phase 2: slice-local OTA ----------------------------
    def _flatten_group(leaves):
        if not leaves:
            return jnp.zeros((0,), jnp.float32)
        return jnp.concatenate([lf.reshape(-1) for lf in leaves])

    def agg_body(grads, delta_sh, delta_rep, step, key):
        leaves = jax.tree.leaves(grads)
        sh_leaves = [lf[0] for lf, (_, _, _, sh) in zip(leaves, info) if sh]
        rep_leaves = [lf[0]
                      for lf, (_, _, _, sh) in zip(leaves, info) if not sh]
        g_sh = jnp.pad(_flatten_group(sh_leaves), (0, d_sh_pad - d_sh))
        g_rep = jnp.pad(_flatten_group(rep_leaves), (0, d_rep_pad - d_rep))
        dl_sh = delta_sh.reshape(-1)
        dl_rep = delta_rep.reshape(-1)
        ghat_sh, nd_sh, met = distributed.sharded_round(
            scheme, g_sh, dl_sh, step, key, ctx_sh)
        ghat_rep, nd_rep, _ = distributed.sharded_round(
            scheme, g_rep, dl_rep, step, key, ctx_rep)
        # unflatten back into the gradient tree (local shapes)
        out, i_sh, i_rep = [], 0, 0
        p_sh, p_rep = ghat_sh[:d_sh], ghat_rep[:d_rep]
        for lf, (_, _, _, sh) in zip(leaves, info):
            shape = lf.shape[1:]
            n = int(np.prod(shape))
            if sh:
                out.append(p_sh[i_sh:i_sh + n].reshape(shape))
                i_sh += n
            else:
                out.append(p_rep[i_rep:i_rep + n].reshape(shape))
                i_rep += n
        ghat_tree = jax.tree.unflatten(jax.tree.structure(grads), out)
        return (ghat_tree,
                nd_sh.astype(state_dtype).reshape(delta_sh.shape),
                nd_rep.astype(state_dtype).reshape(delta_rep.shape), met)

    manual2 = set(ota_axes) | {"model"}
    ospecs = {k: (pspecs if k in ("m", "v") else P())
              for k in jax.eval_shape(opt.init, aparams)}
    ns = lambda s: NamedSharding(mesh, s)                   # noqa: E731
    param_sh = named_sharding_tree(mesh, pspecs)
    opt_sh = named_sharding_tree(mesh, ospecs)
    rep = lambda t: jax.tree.map(lambda _: P(), t)          # noqa: E731
    batch_spec = P(ota_axes)

    def _stacked_spec(spec):
        return P(ota_axes if len(ota_axes) > 1 else ota_axes[0], *spec)

    grads_specs = jax.tree.unflatten(
        treedef, [_stacked_spec(s) for _, _, s, _ in info])
    delta_sh_spec = P(*ota_axes, "model", None)
    delta_rep_spec = P(*ota_axes, None)
    dims = tuple(axis_sizes[a] for a in ota_axes)
    delta_sh_shape = dims + (model_size, d_sh_pad)
    delta_rep_shape = dims + (d_rep_pad,)

    def builder(batch_tree):
        phase1 = jax.shard_map(
            grads_body, mesh=mesh,
            in_specs=(rep(aparams),
                      jax.tree.map(lambda _: batch_spec, batch_tree)),
            out_specs=(jax.tree.unflatten(
                treedef,
                [P(ota_axes if len(ota_axes) > 1 else ota_axes[0],
                   *([None] * len(lf.shape)))
                 for _, lf, _, _ in info]), P()),
            axis_names=set(ota_axes), check_vma=False)
        phase2 = jax.shard_map(
            agg_body, mesh=mesh,
            in_specs=(grads_specs, delta_sh_spec, delta_rep_spec, P(), P()),
            out_specs=(jax.tree.unflatten(treedef,
                                          [P(*s) for _, _, s, _ in info]),
                       delta_sh_spec, delta_rep_spec, P()),
            axis_names=manual2, check_vma=False)

        def step_fn(params, opt_state, delta, batch, step, key):
            gstacked, metrics = phase1(params, batch)
            gstacked = jax.tree.map(
                lambda g, s: jax.lax.with_sharding_constraint(g, ns(s)),
                gstacked, grads_specs)
            ghat_tree, nd_sh, nd_rep, met2 = phase2(
                gstacked, delta["sh"], delta["rep"], step, key)
            with stage("optimizer"):
                params, opt_state = opt.apply(params, ghat_tree, opt_state)
            return (params, opt_state, {"sh": nd_sh, "rep": nd_rep},
                    {**metrics, **met2})

        in_sh = (param_sh, opt_sh,
                 {"sh": ns(delta_sh_spec), "rep": ns(delta_rep_spec)},
                 jax.tree.map(lambda _: ns(batch_spec), batch_tree),
                 ns(P()), ns(P()))
        out_sh = (param_sh, opt_sh,
                  {"sh": ns(delta_sh_spec), "rep": ns(delta_rep_spec)}, None)
        return jax.jit(step_fn, in_shardings=in_sh, out_shardings=out_sh,
                       donate_argnums=(0, 1, 2) if donate else ())

    ts = TrainStep(arch=arch, train=train_cfg, ota=ota, ota_axes=ota_axes,
                   mesh=mesh, m_devices=m_eff, d=d,
                   d_pad=d_sh_pad * model_size + d_rep_pad,
                   delta_shape=(delta_sh_shape, delta_rep_shape),
                   delta_sharding={"sh": ns(delta_sh_spec),
                                   "rep": ns(delta_rep_spec)},
                   param_sharding=param_sh, opt_sharding=opt_sh,
                   batch_spec=batch_spec, _builder=builder)

    def init_state(key):
        params = model_lib.init_params(arch, key)
        opt_state = opt.init(params)
        delta = {"sh": jnp.zeros(delta_sh_shape, state_dtype),
                 "rep": jnp.zeros(delta_rep_shape, state_dtype)}
        return params, opt_state, delta

    ts.init_state = init_state
    return ts
