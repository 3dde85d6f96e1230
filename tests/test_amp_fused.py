"""Fused AMP decode kernel + chunk-batched projection kernels (interpret
mode) vs the jnp oracles, and the one-A-generation-per-decode guarantee."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.amp import (amp_blocked_core, amp_decode, amp_decode_blocked,
                            amp_decode_blocked_scan)
from repro.core.projection import BlockedProjector
from repro.kernels import ops, ref


def _block_sparse_signal(d, c, sb):
    xb = []
    for b in range(d // c):
        key = jax.random.PRNGKey(b)
        idx = jax.random.choice(key, c, (sb // 4,), replace=False)
        vals = jax.random.normal(jax.random.fold_in(key, 1), (sb // 4,))
        xb.append(jnp.zeros(c).at[idx].set(vals))
    return jnp.concatenate(xb)


# ---------------------------------------------------------------------------
# chunk-batched projection kernels: exact parity for Rademacher entries
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("nb,c,sb", [(1, 128, 32), (3, 256, 64),
                                     (12, 128, 32), (5, 64, 16)])
def test_batched_projection_exact_rademacher(nb, c, sb):
    """±1/sqrt(s) entries: the batched dot_general accumulates in the same
    order as the oracle matvec, so parity is exact, not just allclose."""
    x = jax.random.normal(jax.random.PRNGKey(nb), (nb, c), jnp.float32)
    yk = ops.ota_project(x, seed=11, s_block=sb, rademacher=True,
                         use_kernel=True)
    yr = ops.ota_project(x, seed=11, s_block=sb, rademacher=True,
                         use_kernel=False)
    np.testing.assert_array_equal(np.asarray(yk), np.asarray(yr))
    y = jax.random.normal(jax.random.PRNGKey(nb + 7), (nb, sb), jnp.float32)
    tk = ops.ota_project_t(y, seed=11, c=c, rademacher=True, use_kernel=True)
    tr = ops.ota_project_t(y, seed=11, c=c, rademacher=True,
                           use_kernel=False)
    np.testing.assert_array_equal(np.asarray(tk), np.asarray(tr))


def test_projection_kernel_traced_seed():
    """The SMEM seed operand accepts a traced uint32 (shard-folded seeds)."""
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 128), jnp.float32)

    @jax.jit
    def run(x, seed):
        return ops.ota_project(x, seed=seed, s_block=32, rademacher=True,
                               use_kernel=True)

    yk = run(x, ref.splitmix32(jnp.uint32(3)))
    yr = ops.ota_project(x, seed=ref.splitmix32(jnp.uint32(3)), s_block=32,
                         rademacher=True, use_kernel=False)
    np.testing.assert_array_equal(np.asarray(yk), np.asarray(yr))


def test_projection_kernel_nb_tile_padding():
    """n_blocks not divisible by nb_tile: padded rows are sliced off."""
    x = jax.random.normal(jax.random.PRNGKey(2), (7, 64), jnp.float32)
    yk = ops.ota_project(x, seed=3, s_block=16, rademacher=True,
                         use_kernel=True, nb_tile=4)
    yr = ops.ota_project(x, seed=3, s_block=16, rademacher=True,
                         use_kernel=False)
    np.testing.assert_array_equal(np.asarray(yk), np.asarray(yr))


# ---------------------------------------------------------------------------
# fused single-launch AMP decode
# ---------------------------------------------------------------------------


def test_fused_amp_matches_blocked_scan():
    d, c, sb = 4096, 256, 128
    proj = BlockedProjector(d=d, block_size=c, s_block=sb, seed=5,
                            rademacher=True)
    x = _block_sparse_signal(d, c, sb)
    yb = proj.project(x).reshape(proj.n_blocks, sb)
    x_scan = amp_decode_blocked_scan(yb, proj, iters=20)
    xb_fused = amp_blocked_core(yb, proj.seed, c, iters=20, chunk_blocks=4,
                                use_kernel=True)
    np.testing.assert_allclose(np.asarray(proj.from_blocks(xb_fused)),
                               np.asarray(x_scan), rtol=1e-4, atol=1e-5)
    # and both recover the signal
    rel = float(jnp.linalg.norm(x_scan - x) / jnp.linalg.norm(x))
    assert rel < 0.1, rel


def test_fused_amp_id_offset_decodes_subrange():
    """A device decoding a sub-range of blocks with the encoder's global
    block ids (shard_decode) gets the same answer as the full decode."""
    d, c, sb = 2048, 128, 64
    proj = BlockedProjector(d=d, block_size=c, s_block=sb, seed=9,
                            rademacher=True)
    x = _block_sparse_signal(d, c, sb)
    yb = proj.project(x).reshape(proj.n_blocks, sb)
    full = amp_blocked_core(yb, 9, c, iters=10, chunk_blocks=4,
                            use_kernel=True)
    half = proj.n_blocks // 2
    part = amp_blocked_core(yb[half:], 9, c, iters=10, chunk_blocks=4,
                            id_offset=half, use_kernel=True)
    np.testing.assert_array_equal(np.asarray(part), np.asarray(full[half:]))


def test_amp_decode_dispatches_to_fused_kernel(monkeypatch):
    """use_kernel=True on the projector routes amp_decode through the fused
    Pallas kernel (single launch), not the launch-per-op path."""
    d, c, sb = 1024, 128, 64
    x = _block_sparse_signal(d, c, sb)
    calls = {"fused": 0}
    real = ops.amp_decode_fused_pallas

    def spy(*a, **kw):
        calls["fused"] += 1
        return real(*a, **kw)

    # ops binds the kernel entry point at import time — patch ops' name
    monkeypatch.setattr(ops, "amp_decode_fused_pallas", spy)
    proj_k = BlockedProjector(d=d, block_size=c, s_block=sb, seed=2,
                              rademacher=True, use_kernel=True)
    proj_j = BlockedProjector(d=d, block_size=c, s_block=sb, seed=2,
                              rademacher=True, use_kernel=False)
    y = proj_j.project(x)
    # (jit stays on: Pallas interpret mode recurses under disable_jit; the
    # spy counts trace-time entries of the kernel wrapper)
    xk = amp_decode(y, proj_k, iters=8)
    assert calls["fused"] == 1
    xj = amp_decode(y, proj_j, iters=8)
    np.testing.assert_allclose(np.asarray(xk), np.asarray(xj),
                               rtol=1e-4, atol=1e-5)


# register tiles of the fused decode: several trips of every loop, parity
# with the jnp path for any tiling, and the tile chooser
# ---------------------------------------------------------------------------


# the cell's tiles, and tiles cut small so that a small block takes several
# trips of every loop: gen rows and columns, adjoint column tiles and row
# trips, forward row trips, lane chunks and lane reductions
_SMALL_TILES = {"_GEN_TILE": (16, 128), "_ADJ_TILE": (32, 256),
                "_FWD_TILE": (16, 256), "_RED_ROWS": 64}


@pytest.mark.parametrize("tiles", ["cell", "small"])
def test_fused_amp_tiled_matches_jnp(tiles, monkeypatch):
    """5 blocks of c = 1024, s_block = 256 in chunks of 2 (nb_tile > 1, the
    last chunk padded), seed and id_offset traced."""
    from repro.kernels import amp_fused
    if tiles == "small":
        for k, v in _SMALL_TILES.items():
            monkeypatch.setattr(amp_fused, k, v)
    t = amp_fused._amp_tiles(256, 1024)
    assert 256 // t.fwd_rows > 1 and 256 // t.gen_rows > 1
    if tiles == "small":
        assert 1024 // t.adj_cols > 1 and 256 // t.adj_rows > 1
        assert 256 // t.red_rows > 1
    d, c, sb, off = 5 * 1024, 1024, 256, 3
    proj = BlockedProjector(d=d, block_size=c, s_block=sb, seed=13,
                            rademacher=True)
    yb = proj.project(_block_sparse_signal(d, c, sb)).reshape(
        proj.n_blocks, sb)
    seed = ref.splitmix32(jnp.uint32(13))
    # the jnp path with the encoder's block ids off + 0 .. off + 4
    want = amp_blocked_core(yb, seed, c, iters=12, chunk_blocks=2,
                            id_offset=off)

    @jax.jit
    def run(yb, seed, off):
        return amp_fused.amp_decode_fused_pallas(
            yb, seed, c, iters=12, nb_tile=2, id_offset=off)

    got = run(yb, seed, jnp.uint32(off))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    assert float(jnp.max(jnp.abs(want))) > 0.1      # a nontrivial decode


@pytest.mark.parametrize("c,sb", [(4096, 1024), (1024, 256), (256, 128),
                                  (256, 64), (128, 64), (64, 32)])
def test_amp_tile_chooser(c, sb):
    """The cell's block and every block the kernel tests decode: each tile
    divides its dim, obeys the (8, 128) rule, and the estimated live vregs
    stay within the budget."""
    from repro.kernels import amp_fused
    t = amp_fused._amp_tiles(sb, c)
    assert t.sub == 8
    for r in (t.gen_rows, t.adj_rows, t.fwd_rows, t.red_rows):
        assert sb % r == 0 and r % 8 == 0 and r % t.sub == 0
    for w in (t.gen_cols, t.adj_cols, t.fwd_cols):
        assert c % w == 0 and (w % 128 == 0 or w == c)
    assert amp_fused._live_vregs(t) <= amp_fused._LIVE_VREGS
    if (c, sb) == (4096, 1024):               # the tiles chosen on the v5e
        assert (t.gen_rows, t.gen_cols) == amp_fused._GEN_TILE
        assert (t.adj_rows, t.adj_cols) == amp_fused._ADJ_TILE
        assert (t.fwd_rows, t.fwd_cols) == amp_fused._FWD_TILE
        assert t.red_rows == amp_fused._RED_ROWS


def test_amp_tile_chooser_takes_odd_dims_whole():
    """A dim with no divisor on the (8, 128) grid is one tile."""
    from repro.kernels import amp_fused
    t = amp_fused._amp_tiles(20, 192)
    assert t.sub == 20
    assert t.adj_rows == t.fwd_rows == t.gen_rows == t.red_rows == 20
    assert t.gen_cols == t.adj_cols == t.fwd_cols == 192


# ---------------------------------------------------------------------------
# the one-generation-per-block guarantee (acceptance criterion)
# ---------------------------------------------------------------------------


def test_amp_generator_invocations(monkeypatch):
    """The chunked decode generates each block's A exactly ONCE per decode;
    launch-per-op decoding regenerates it 2*amp_iters+1 times.

    Counted on the jnp oracle path under disable_jit: every invocation of
    ref.block_matrix_ref generates the A of each block in its (vmapped)
    chunk once, so the chunked scan makes ceil(n_blocks/chunk) invocations
    — one generation per block in total — while the unfused path makes one
    invocation per projection application (adjoint + forward per iteration,
    + the LS debias)."""
    d, c, sb, iters, chunk = 1024, 128, 64, 5, 4
    proj = BlockedProjector(d=d, block_size=c, s_block=sb, seed=4,
                            rademacher=True)
    x = _block_sparse_signal(d, c, sb)
    yb = proj.project(x).reshape(proj.n_blocks, sb)

    calls = {"n": 0}
    real = ref.block_matrix_ref

    def counting(*a, **kw):
        calls["n"] += 1
        return real(*a, **kw)

    monkeypatch.setattr(ref, "block_matrix_ref", counting)
    with jax.disable_jit():
        calls["n"] = 0
        x_scan = amp_blocked_core(yb, 4, c, iters=iters, chunk_blocks=chunk)
        n_chunks = -(-proj.n_blocks // chunk)
        assert calls["n"] == n_chunks, (calls["n"], n_chunks)

        calls["n"] = 0
        x_unfused = amp_decode_blocked(yb, proj, iters=iters)
        assert calls["n"] == 2 * iters + 1, calls["n"]

    # allclose parity between the fused structure and the unfused path
    np.testing.assert_allclose(np.asarray(proj.from_blocks(x_scan)),
                               np.asarray(x_unfused), rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# end-to-end: the sharded slice driver honours use_kernel
# ---------------------------------------------------------------------------


def test_sharded_round_kernel_path_matches_jnp():
    from jax.sharding import PartitionSpec as P

    from repro.configs.base import OTAConfig
    from repro.core import distributed
    from repro.core.schemes import MACContext, get_scheme
    from repro.launch.mesh import auto_mesh

    D = 512
    n_dev = jax.device_count()
    mesh = auto_mesh((n_dev,), ("dev",))
    grads = jnp.asarray(
        jax.random.normal(jax.random.PRNGKey(7), (n_dev, D)))
    deltas = jnp.zeros((n_dev, D))
    outs = {}
    for uk in (False, True):
        cfg = OTAConfig(scheme="a_dsgd", projection="blocked", block_size=64,
                        s_frac=0.5, k_frac=0.25, rademacher=True, p_avg=500.0,
                        total_steps=10, amp_iters=5, mean_removal_steps=0,
                        use_kernel=uk)
        sch = get_scheme(cfg, D, n_dev)
        ctx = MACContext(m=n_dev, device_axes=("dev",), d_pad=D,
                         chunk_blocks=4, use_kernel=uk)

        def body(g, dl):
            ghat, nd, _ = distributed.sharded_round(
                sch, g.reshape(-1), dl.reshape(-1), 0,
                jax.random.PRNGKey(3), ctx)
            return ghat

        outs[uk] = jax.shard_map(
            body, mesh=mesh, in_specs=(P("dev"), P("dev")),
            out_specs=P(), axis_names={"dev"},
            check_vma=False)(grads, deltas)
    np.testing.assert_allclose(np.asarray(outs[True]),
                               np.asarray(outs[False]),
                               rtol=1e-4, atol=1e-5)
