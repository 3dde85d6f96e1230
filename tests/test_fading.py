"""Channel-model subsystem tests: fading processes, CSI models, the two
imperfect-CSI schemes (csi_err / blind), and the truncated-inversion edge
cases (follow-ups arXiv:1907.09769 / arXiv:1907.03909)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import OTAConfig
from repro.core import channel, fading
from repro.core.schemes import MACContext, get_scheme, round_simulated

D, M = 256, 6


def _cfg(scheme="a_dsgd_fading", **kw):
    base = dict(scheme=scheme, s_frac=0.5, k_frac=0.25, p_avg=500.0,
                total_steps=10, projection="dense", amp_iters=8,
                mean_removal_steps=2)
    base.update(kw)
    return OTAConfig(**base)


# ---------------------------------------------------------------------------
# truncated channel inversion: edge cases (satellite task)
# ---------------------------------------------------------------------------


def test_truncated_inversion_threshold_exactly_at_gain():
    """|h| == threshold is *inclusive*: the device transmits (h >= thr)."""
    thr = 0.5
    h = jnp.asarray([thr, np.nextafter(thr, 0.0, dtype=np.float32),
                     np.nextafter(thr, 1.0, dtype=np.float32)])
    p, active = channel.truncated_inversion_power(h, thr)
    np.testing.assert_array_equal(np.asarray(active), [True, False, True])
    assert float(p[0]) == pytest.approx(thr * thr)
    assert float(p[1]) == 0.0


def test_truncated_inversion_all_deep_fade_zero_transmit_set():
    """Every device below threshold: the transmit set is empty (all factors
    0, all masks False) and a full round degrades to decoding pure AWGN
    while every device banks its whole update in the error state."""
    h = jnp.full((M,), 0.01)
    p, active = channel.truncated_inversion_power(h, 0.3)
    assert not bool(jnp.any(active))
    np.testing.assert_array_equal(np.asarray(p), np.zeros(M))

    cfg = _cfg(fading_threshold=1e9)
    sch = get_scheme(cfg, D, M)
    grads = jax.random.normal(jax.random.PRNGKey(0), (M, D))
    deltas = jnp.zeros((M, D))
    ghat, nd, met = round_simulated(sch, grads, deltas, 0,
                                    jax.random.PRNGKey(1))
    assert float(met["active_frac"]) == 0.0
    # silent devices accumulate g + Delta (here Delta = 0)
    np.testing.assert_allclose(np.asarray(nd), np.asarray(grads), rtol=1e-6)
    assert bool(jnp.all(jnp.isfinite(ghat)))


def test_truncated_inversion_huge_gain_power_sanity():
    """h -> huge stays sane: the received-power factor is exactly h^2 (the
    transmit side pre-inverts, so transmit power never exceeds P_t) and
    stays finite up to the f32 horizon."""
    h = jnp.asarray([1.0, 1e3, 1e18])
    p, active = channel.truncated_inversion_power(h, 0.3)
    assert bool(jnp.all(active))
    np.testing.assert_allclose(np.asarray(p), np.asarray(h) ** 2, rtol=1e-6)
    assert bool(jnp.all(jnp.isfinite(p)))
    # and the frame a device builds under that factor carries P_t * h^2
    g = jax.random.normal(jax.random.PRNGKey(2), (32,))
    frame, _ = channel.make_frame(g, 100.0 * 1e6, False)   # P_t * h^2, h=1e3
    np.testing.assert_allclose(float(channel.frame_power(frame)), 1e8,
                               rtol=1e-4)


# ---------------------------------------------------------------------------
# fading processes
# ---------------------------------------------------------------------------


def _draws(process, steps, m=512, rho=0.9, window=64):
    spec = fading.FadingSpec(process=process, window=window)
    fkey = fading.fading_base_key(0)
    out = []
    for t in range(steps):
        rkey = jax.random.fold_in(jax.random.PRNGKey(100 + t), 2)
        re, im = fading.process_gains(spec, fkey, rkey, t, m, rho=rho)
        out.append(np.asarray(re) + 1j * np.asarray(im))
    return np.stack(out)                                   # (steps, m)


def test_static_process_is_block_flat():
    h = _draws("static", 5)
    for t in range(1, 5):
        np.testing.assert_array_equal(h[t], h[0])


def test_iid_process_redraws_and_matches_legacy_rayleigh():
    h = _draws("iid", 3)
    assert not np.array_equal(h[0], h[1])
    # bitwise the legacy channel.rayleigh_gains magnitudes
    key = jax.random.fold_in(jax.random.PRNGKey(100), 2)
    spec = fading.FadingSpec(process="iid")
    re, im = fading.process_gains(spec, fading.fading_base_key(0), key, 0, 16)
    np.testing.assert_array_equal(np.asarray(fading.magnitude(re, im)),
                                  np.asarray(channel.rayleigh_gains(key, 16)))


def test_gauss_markov_stationary_and_correlated():
    """Unit marginal variance; autocorrelation ~ rho^|dt| and decaying."""
    rho = 0.8
    h = _draws("gauss_markov", 12, m=4096, rho=rho)
    var = np.mean(np.abs(h) ** 2)
    assert 0.9 < var < 1.1
    corr = [np.mean((h[0] * np.conj(h[dt])).real) / var for dt in (1, 4, 8)]
    assert corr[0] == pytest.approx(rho, abs=0.1)
    assert corr[0] > corr[1] > corr[2] - 0.05
    assert corr[2] < 0.35


def test_gauss_markov_rho_is_traced_data():
    """rho enters only as a traced weight vector -> vmappable axis."""
    spec = fading.FadingSpec(process="gauss_markov", window=16)
    fkey = fading.fading_base_key(0)
    rkey = jax.random.PRNGKey(3)

    def f(rho):
        re, im = fading.process_gains(spec, fkey, rkey, 2, 8, rho=rho)
        return re
    res = jax.vmap(f)(jnp.asarray([0.1, 0.9]))
    assert res.shape == (2, 8)
    assert not np.array_equal(np.asarray(res[0]), np.asarray(res[1]))


# ---------------------------------------------------------------------------
# CSI models
# ---------------------------------------------------------------------------


def test_csi_estimate_zero_error_is_exact():
    re, im = fading.complex_normals(jax.random.PRNGKey(0), 64)
    er, ei = fading.csi_estimate(re, im, jax.random.PRNGKey(1), 0.0)
    np.testing.assert_array_equal(np.asarray(er), np.asarray(re))
    np.testing.assert_array_equal(np.asarray(ei), np.asarray(im))
    g = fading.misalignment_gain(re, im, er, ei, 0.0)
    np.testing.assert_array_equal(np.asarray(g), np.ones(64, np.float32))


def test_csi_estimate_error_degrades_alignment():
    re, im = fading.complex_normals(jax.random.PRNGKey(0), 4096)
    er, ei = fading.csi_estimate(re, im, jax.random.PRNGKey(1), 0.5)
    g = fading.misalignment_gain(re, im, er, ei, 0.5)
    # Re(h / h_hat) scatters around ~1 with heavy spread; no exact ones
    assert float(jnp.mean(jnp.abs(g - 1.0))) > 0.05
    assert not bool(jnp.all(g == 1.0))


def test_blind_combiner_channel_hardening():
    """As K grows the combiner gains -> 1 and the noise scale -> 0 — the
    blind MAC hardens into the ideal link (1907.03909's asymptotic)."""
    m = 8
    stats = {}
    for k in (8, 128, 2048):
        re, im = fading.complex_normals(jax.random.PRNGKey(5), m * k)
        gain, ns = fading.blind_combiner_stats(re.reshape(m, k),
                                               im.reshape(m, k))
        stats[k] = (float(jnp.mean(jnp.abs(gain - 1.0))), float(ns))
    assert stats[8][0] > stats[128][0] > stats[2048][0]
    assert stats[2048][0] < 0.1
    assert stats[8][1] > stats[128][1] > stats[2048][1]
    assert stats[2048][1] < 0.05


# ---------------------------------------------------------------------------
# the imperfect-CSI schemes on the generic drivers
# ---------------------------------------------------------------------------


def test_csi_err_scheme_recovery_degrades_with_error():
    """Gradient-recovery error grows with the CSI error variance, averaged
    over channel seeds (a single draw can swing either way: the estimate's
    |h_hat|^2 power boost sometimes offsets the misalignment).  The
    zero-error point is the perfect-CSI scheme bitwise, which
    tests/test_schemes.py pins against the golden."""
    grads = jnp.broadcast_to(
        jax.random.normal(jax.random.PRNGKey(7), (D,)), (M, D))
    errs = {}
    for ev in (0.0, 1.0):
        sch = get_scheme(_cfg("a_dsgd_csi_err", csi_err_var=ev,
                              fading_threshold=0.2), D, M)
        se = 0.0
        for s in range(8):
            deltas = jnp.zeros((M, D))
            for t in range(3):
                ghat, deltas, _ = round_simulated(
                    sch, grads, deltas, t, jax.random.PRNGKey(37 * s + t))
                se += float(jnp.sum((ghat - grads[0]) ** 2))
        errs[ev] = se
    assert errs[1.0] > 1.1 * errs[0.0]


def test_blind_scheme_all_devices_transmit():
    sch = get_scheme(_cfg("a_dsgd_blind", ps_antennas=16), D, M)
    grads = jax.random.normal(jax.random.PRNGKey(8), (M, D))
    ghat, nd, met = round_simulated(sch, grads, jnp.zeros((M, D)), 0,
                                    jax.random.PRNGKey(9))
    assert float(met["active_frac"]) == 1.0
    assert float(met["noise_scale"]) > 0.0
    assert bool(jnp.all(jnp.isfinite(ghat)))


def test_blind_many_antennas_approaches_awgn_adsgd():
    """With a huge antenna array the blind round converges to the plain
    AWGN A-DSGD round: gains -> 1, noise enhancement -> 0 (< sigma2)."""
    grads = jnp.broadcast_to(
        jax.random.normal(jax.random.PRNGKey(7), (D,)), (M, D))
    deltas = jnp.zeros((M, D))
    ref_sch = get_scheme(_cfg("a_dsgd"), D, M)
    ghat_ref, _, _ = round_simulated(ref_sch, grads, deltas, 0,
                                     jax.random.PRNGKey(11))
    blind = get_scheme(_cfg("a_dsgd_blind", ps_antennas=4096), D, M)
    ghat_b, _, met = round_simulated(blind, grads, deltas, 0,
                                     jax.random.PRNGKey(11))
    assert float(met["noise_scale"]) < 0.1
    # both reconstruct the same (shared) gradient to similar accuracy
    err_ref = float(jnp.linalg.norm(ghat_ref - grads[0]))
    err_b = float(jnp.linalg.norm(ghat_b - grads[0]))
    assert err_b < 1.5 * err_ref + 1e-3


def test_blind_channel_draw_mask_excludes_phantom_devices():
    """m_active padding: masked-out devices' channel rows must not enter
    the blind PS combiner — the masked draw equals the combiner statistics
    of the live subset, and an all-ones mask is bitwise the unmasked draw."""
    sch = get_scheme(_cfg("a_dsgd_blind", ps_antennas=8), D, M)
    key = jax.random.PRNGKey(3)
    full = sch.channel_draw(key, 0, M)
    ones = sch.channel_draw(key, 0, M, mask=jnp.ones((M,), bool))
    np.testing.assert_array_equal(np.asarray(full.gain),
                                  np.asarray(ones.gain))
    np.testing.assert_array_equal(np.asarray(full.noise_scale),
                                  np.asarray(ones.noise_scale))
    mask = jnp.arange(M) < 2
    masked = sch.channel_draw(key, 0, M, mask=mask)
    # reproduce by hand: zero the phantom rows, recompute the stats
    k_ant = sch.fading_spec.ps_antennas
    re, im = sch.gains(key, 0, M * k_ant)
    live = mask.astype(jnp.float32)[:, None]
    g_ref, ns_ref = fading.blind_combiner_stats(
        re.reshape(M, k_ant) * live, im.reshape(M, k_ant) * live)
    np.testing.assert_array_equal(np.asarray(masked.gain),
                                  np.asarray(g_ref))
    np.testing.assert_array_equal(np.asarray(masked.noise_scale),
                                  np.asarray(ns_ref))
    # fewer live transmitters -> strictly less combiner interference
    assert float(masked.noise_scale) < float(full.noise_scale)


@pytest.mark.parametrize("scheme", ["a_dsgd_csi_err", "a_dsgd_blind"])
def test_imperfect_csi_schemes_on_sharded_drivers(scheme):
    """Both new schemes run through round_sharded and the slice driver
    (sharded_round) — the channel draw is evaluated from the shared round
    key and indexed per device, so it works at any mesh size."""
    from jax.sharding import PartitionSpec as P
    from repro.core import distributed
    from repro.launch.mesh import auto_mesh

    n_dev = jax.device_count()
    mesh = auto_mesh((n_dev,), ("dev",))
    grads = jax.random.normal(jax.random.PRNGKey(1), (n_dev, D))
    deltas = jnp.zeros((n_dev, D))
    cfg = _cfg(scheme, projection="blocked", block_size=64, amp_iters=4,
               csi_err_var=0.2, ps_antennas=8, fading_threshold=0.1)
    sch = get_scheme(cfg, D, n_dev)
    ctx = MACContext(m=n_dev, device_axes=("dev",), d_pad=D,
                     fading="rayleigh", csi=sch.csi)

    def psum_body(g, dl):
        ghat, _, _ = round_sharded_wrap(g.reshape(-1), dl.reshape(-1))
        return ghat

    from repro.core import schemes as schemes_mod

    def round_sharded_wrap(g, dl):
        return schemes_mod.round_sharded(sch, g, dl, 0,
                                         jax.random.PRNGKey(5), ctx)

    ghat = jax.shard_map(
        psum_body, mesh=mesh, in_specs=(P("dev"), P("dev")),
        out_specs=P(), axis_names={"dev"},
        check_vma=False)(grads, deltas)
    assert bool(jnp.all(jnp.isfinite(ghat)))

    def slice_body(g, dl):
        ghat_s, _, _ = distributed.sharded_round(sch, g.reshape(-1),
                                                 dl.reshape(-1), 0,
                                                 jax.random.PRNGKey(5), ctx)
        return ghat_s.reshape(1, -1)

    ghat_s = jax.shard_map(
        slice_body, mesh=mesh, in_specs=(P("dev"), P("dev")),
        out_specs=P("dev"), axis_names={"dev"},
        check_vma=False)(grads, deltas)
    assert bool(jnp.all(jnp.isfinite(ghat_s)))


def test_unknown_fading_process_raises():
    with pytest.raises(ValueError, match="unknown fading_process"):
        get_scheme(_cfg(fading_process="warp"), D, M).fading_spec
