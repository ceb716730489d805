"""repro.phy scenario engine: correlated fading, geometry, imperfect CSI,
deep-fade truncation — and their end-to-end integration through the
participation-aware transport (flat ADMM + packed LLM trainer)."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import cplx, make, transport
from repro.core.admm import AdmmConfig
from repro.core.channel import ChannelConfig, rayleigh
from repro.core.tree_ota import init_channel_packed, step_channel_packed
from repro.launch.mesh import make_mesh
from repro.phy import (GeometryConfig, bessel_j0, doppler_rho,
                       gauss_markov_step, list_scenarios, make_scenario,
                       participation_mask)
from repro.phy.geometry import (init_positions, path_gain, uniform_disk,
                                waypoint_step, worker_gains)
from repro.train import train

from helpers import default_cfgs, make_linreg, make_solver

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# fading: Jakes/AR(1) statistics + the fused kernel
# ---------------------------------------------------------------------------

def test_bessel_j0_reference_values():
    # A&S tables: J0(1) = 0.76519769, first zero at 2.404826
    assert bessel_j0(0.0) == 1.0
    assert abs(bessel_j0(1.0) - 0.76519769) < 1e-6
    assert abs(bessel_j0(2.404826)) < 1e-5
    assert abs(bessel_j0(5.0) - (-0.17759677)) < 1e-6


def test_doppler_rho_limits():
    assert doppler_rho(0.0, 1e-3) == 1.0          # static worker
    assert doppler_rho(50.0, 1e-3) == pytest.approx(
        bessel_j0(2 * math.pi * 0.05), abs=1e-7)
    # past the first Bessel zero: clamped to 0 (i.i.d.), never negative
    assert doppler_rho(500.0, 1e-3) == 0.0


def test_gauss_markov_stationary_and_correlated():
    rho = 0.9
    h = rayleigh(KEY, (4, 20_000))
    h2 = gauss_markov_step(jax.random.fold_in(KEY, 1), h, rho)
    var = float(jnp.mean(cplx.abs2(h2)))
    corr = float(jnp.mean(h.re * h2.re + h.im * h2.im)
                 / jnp.mean(cplx.abs2(h)))
    assert abs(var - 1.0) < 0.05        # CN(0,1) preserved
    assert abs(corr - rho) < 0.05       # per-step correlation = rho


def test_gauss_markov_rho0_is_iid_redraw():
    h = rayleigh(KEY, (2, 64))
    k = jax.random.fold_in(KEY, 7)
    got = gauss_markov_step(k, h, 0.0)
    want = rayleigh(k, (2, 64))          # exact legacy draw, bitwise
    assert np.array_equal(np.asarray(got.re), np.asarray(want.re))
    assert np.array_equal(np.asarray(got.im), np.asarray(want.im))


@pytest.mark.parametrize("rho", [0.0, 0.7])
@pytest.mark.parametrize("redraw", [True, False])
@pytest.mark.parametrize("shape", [(3, 1024), (5, 1024 + 37)])
def test_fading_step_kernel_parity(rho, redraw, shape):
    """Pallas channel-step kernel vs jnp reference <= 1e-6 (incl. the
    hold branch and non-LANE-aligned tails)."""
    h = rayleigh(jax.random.fold_in(KEY, shape[1]), shape)
    k = jax.random.fold_in(KEY, 3)
    rd = jnp.asarray(redraw)
    jn = gauss_markov_step(k, h, rho, rd, backend="jnp")
    pl = gauss_markov_step(k, h, rho, rd, backend="pallas")
    assert float(jnp.max(jnp.abs(jn.re - pl.re))) <= 1e-6
    assert float(jnp.max(jnp.abs(jn.im - pl.im))) <= 1e-6
    if not redraw:  # hold branch: both backends return h untouched
        assert np.array_equal(np.asarray(pl.re), np.asarray(h.re))


# ---------------------------------------------------------------------------
# geometry
# ---------------------------------------------------------------------------

def test_path_gain_monotone_and_normalised():
    g = GeometryConfig(cell_radius_m=500.0, pathloss_exp=3.0)
    d = jnp.asarray([1.0, 50.0, 250.0, 500.0])
    gains = path_gain(d, g)
    assert bool(jnp.all(gains[:-1] > gains[1:]))   # farther = weaker
    assert float(gains[2]) == pytest.approx(1.0)   # unit gain mid-cell
    # saturates below the reference distance
    assert float(path_gain(jnp.asarray(0.01), g)) \
        == float(path_gain(jnp.asarray(1.0), g))


def test_uniform_disk_in_bounds():
    pts = uniform_disk(KEY, 2000, 100.0)
    r = jnp.sqrt(jnp.sum(pts * pts, axis=-1))
    assert float(jnp.max(r)) <= 100.0
    # uniform over the disk: mean radius = 2R/3
    assert abs(float(jnp.mean(r)) - 200.0 / 3.0) < 3.0


def test_waypoint_step_moves_toward_dest():
    g = GeometryConfig(cell_radius_m=100.0, speed_mps=5.0, slot_seconds=1.0)
    pos, dest = init_positions(KEY, 64, g)
    gap0 = jnp.sqrt(jnp.sum((dest - pos) ** 2, axis=-1))
    pos2, dest2 = waypoint_step(jax.random.fold_in(KEY, 1), pos, dest, g)
    gap1 = jnp.sqrt(jnp.sum((dest2 - pos2) ** 2, axis=-1))
    far = gap0 > 5.0  # not arriving this step: distance shrinks by the step
    np.testing.assert_allclose(np.asarray(gap0 - gap1)[np.asarray(far)],
                               5.0, rtol=1e-4)
    # arrivals teleport onto the waypoint and redraw it inside the cell
    r = jnp.sqrt(jnp.sum(pos2 * pos2, axis=-1))
    assert float(jnp.max(r)) <= 100.0 + 1e-3


def test_worker_gains_composes_shadowing():
    g = GeometryConfig(cell_radius_m=100.0)
    pos = jnp.asarray([[50.0, 0.0], [25.0, 0.0]])
    shadow = jnp.asarray([1.0, 4.0])
    gains = worker_gains(pos, shadow, g)
    assert float(gains[0]) == pytest.approx(1.0)          # mid-cell, no shadow
    assert float(gains[1]) == pytest.approx(8.0 * 4.0)    # (50/25)^3 * shadow


# ---------------------------------------------------------------------------
# scenario registry + bit-compat with the legacy channel
# ---------------------------------------------------------------------------

def test_registry_names_and_unknown():
    assert set(list_scenarios()) == {
        "static-iid", "block-fading", "markov-doppler", "urban-mobility",
        "deep-fade-truncation"}
    with pytest.raises(ValueError, match="unknown scenario"):
        make_scenario("rayleigh-disco")


def test_block_fading_scenario_bitwise_equals_legacy_channel():
    """The pinned contract: scenario="block-fading" reproduces
    init_channel_packed/step_channel_packed draw-for-draw, bit-for-bit."""
    W, d = 4, 129
    ccfg = ChannelConfig(n_workers=W, coherence_iters=3)
    scn = make_scenario("block-fading", ccfg)
    st = scn.init(KEY, W, d)
    legacy = init_channel_packed(KEY, W, d)
    for i in range(8):
        assert np.array_equal(np.asarray(st.h.re), np.asarray(legacy.h.re)), i
        assert np.array_equal(np.asarray(st.h.im), np.asarray(legacy.h.im)), i
        assert int(st.age) == int(legacy.age)
        k = jax.random.fold_in(KEY, i)
        st = scn.step(k, st)
        legacy, _ = step_channel_packed(k, legacy, ccfg)
    # and the simple scenario carries no dead state
    assert st.h_small is None and st.h_hat is None and st.mask is None
    assert st.gain is None and st.pos is None


def test_static_iid_never_redraws():
    scn = make_scenario("static-iid")
    st = scn.init(KEY, 2, 16)
    h0 = np.asarray(st.h.re)
    for i in range(5):
        st = scn.step(jax.random.fold_in(KEY, i), st)
    assert np.array_equal(np.asarray(st.h.re), h0)


def test_markov_doppler_updates_every_round():
    ccfg = ChannelConfig(n_workers=2, coherence_iters=10)
    scn = make_scenario("markov-doppler", ccfg, doppler_hz=80.0)
    assert scn.cfg.coherence_iters == 1       # preset overrides ccfg block
    assert 0.0 < scn.cfg.rho < 1.0
    st = scn.init(KEY, 2, 512)
    h0 = st.h
    st = scn.step(jax.random.fold_in(KEY, 1), st)
    assert not np.array_equal(np.asarray(st.h.re), np.asarray(h0.re))
    corr = float(jnp.mean(h0.re * st.h.re + h0.im * st.h.im)
                 / jnp.mean(cplx.abs2(h0)))
    assert abs(corr - scn.cfg.rho) < 0.1


def test_changed_flags_block_redraws_not_continuous_evolution():
    """``Scenario.changed`` drives the flip rule, whose premise is a
    discontinuous block redraw.  Continuous AR(1)/mobility drift must NOT
    trip it — ``flip_on_change=True`` would then freeze θ every round
    (regression: markov-doppler rounds left θ bit-identical to θ0)."""
    ccfg = ChannelConfig(n_workers=4)
    for name in ("markov-doppler", "urban-mobility"):
        scn = make_scenario(name, ccfg)
        st = scn.step(jax.random.fold_in(KEY, 1), scn.init(KEY, 4, 8))
        assert not bool(scn.changed(st))
    # the rho=0 coherence-boundary redraw IS a discontinuity (legacy rule)
    scn = make_scenario("block-fading", ccfg)
    st = scn.init(KEY, 4, 8)
    flags = []
    for r in range(ccfg.coherence_iters + 1):
        st = scn.step(jax.random.fold_in(KEY, r), st)
        flags.append(bool(scn.changed(st)))
    assert flags == [False] * (ccfg.coherence_iters - 1) + [True, False]

    # end-to-end: flip_on_change training makes primal progress under
    # correlated fading
    prob = make_linreg(KEY, W=4, d=6)
    acfg, ccfg2, plan = default_cfgs(4, 6, flip=True)
    alg = make("afadmm", acfg, ccfg2, plan,
               scenario=make_scenario("markov-doppler", ccfg2))
    solver = make_solver(prob, acfg.rho)
    st = alg.init(jax.random.PRNGKey(1), prob["theta0"])
    for r in range(3):
        st, _ = alg.round(jax.random.fold_in(KEY, r), st, solver,
                          prob["grad_fn"])
    assert float(jnp.max(jnp.abs(st.theta - prob["theta0"]))) > 0.0


def test_csi_error_statistics_and_split():
    scn = make_scenario("markov-doppler", csi_err=0.2)
    st = scn.init(KEY, 4, 20_000)
    err = st.h_hat - st.h
    sig = float(jnp.sqrt(jnp.mean(cplx.abs2(err))))
    assert abs(sig - 0.2) < 0.02              # CN(0, sigma_e^2)
    # perfect-CSI scenarios carry no h_hat at all
    assert make_scenario("markov-doppler").init(KEY, 2, 8).h_hat is None


def test_urban_mobility_evolves_gains():
    ccfg = ChannelConfig(n_workers=8)
    scn = make_scenario("urban-mobility", ccfg)
    st = scn.init(KEY, 8, 64)
    assert st.gain.shape == (8,) and st.pos.shape == (8, 2)
    # effective |h|^2 average equals the per-worker gain (over many coeffs)
    st_big = scn.init(KEY, 8, 20_000)
    mean_h2 = np.asarray(jnp.mean(cplx.abs2(st_big.h), axis=-1))
    np.testing.assert_allclose(mean_h2, np.asarray(st_big.gain), rtol=0.1)
    st2 = scn.step(jax.random.fold_in(KEY, 1), st)
    assert not np.array_equal(np.asarray(st2.pos), np.asarray(st.pos))
    assert not np.array_equal(np.asarray(st2.gain), np.asarray(st.gain))
    assert np.array_equal(np.asarray(st2.shadow), np.asarray(st.shadow))


def test_deep_fade_mask_is_scalar_rule():
    scn = make_scenario("deep-fade-truncation", h_min=0.5)
    st = scn.init(KEY, 32, 64)
    # freq-flat: the RMS rule is exactly |h_n| >= h_min on the scalar fade
    scalar_amp = np.asarray(jnp.sqrt(cplx.abs2(st.h_small))[:, 0])
    np.testing.assert_array_equal(np.asarray(st.mask), scalar_amp >= 0.5)
    assert 0 < int(np.sum(np.asarray(st.mask))) < 32   # some, not all


def test_participation_mask_rms():
    h = cplx.Complex(jnp.asarray([[3.0, 0.0], [0.1, 0.1]]),
                     jnp.zeros((2, 2)))
    m = participation_mask(h, 1.0)
    np.testing.assert_array_equal(np.asarray(m), [True, False])


def test_scenario_step_is_scan_and_jit_safe():
    scn = make_scenario("urban-mobility", csi_err=0.05, h_min=0.3)
    st = scn.init(KEY, 4, 32)

    def body(carry, k):
        nxt = scn.step(k, carry)
        return nxt, jnp.mean(cplx.abs2(nxt.h))

    ks = jax.random.split(KEY, 5)
    final, means = jax.jit(lambda s: jax.lax.scan(body, s, ks))(st)
    assert means.shape == (5,) and bool(jnp.all(jnp.isfinite(means)))


# ---------------------------------------------------------------------------
# masked transport: superposition, min-alpha, degenerate rounds
# ---------------------------------------------------------------------------

def _problem(W, d, seed=0):
    k = jax.random.fold_in(KEY, seed)
    k1, k2, k3, k4 = jax.random.split(k, 4)
    theta = jax.random.normal(k1, (W, d))
    lam = cplx.Complex(0.3 * jax.random.normal(k2, (W, d)),
                       0.3 * jax.random.normal(k3, (W, d)))
    h = rayleigh(k4, (W, d))
    return theta, lam, h


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_masked_uplink_equals_active_subset(backend):
    """Masked workers contribute EXACTLY zero: the masked W-worker round
    equals the unmasked round over the active subset (same noise draw)."""
    W, d = 6, 1024 + 13
    theta, lam, h = _problem(W, d, seed=1)
    mask = jnp.asarray([True, False, True, True, False, True])
    ccfg = ChannelConfig(n_workers=W, noisy=True, snr_db=20.0)
    kn = jax.random.fold_in(KEY, 9)
    T_m, ia_m = transport.ota_uplink(theta, lam, h, kn, 0.5, ccfg,
                                     mask=mask, backend=backend)
    idx = jnp.asarray([0, 2, 3, 5])
    sub = lambda c: cplx.Complex(c.re[idx], c.im[idx])
    T_s, ia_s = transport.ota_uplink(
        theta[idx], sub(lam), sub(h), kn, 0.5,
        ChannelConfig(n_workers=4, noisy=True, snr_db=20.0), backend="jnp")
    np.testing.assert_allclose(np.asarray(T_m), np.asarray(T_s),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(float(ia_m), float(ia_s), rtol=1e-5)


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_masked_uplink_ignores_garbage_in_masked_rows(backend):
    """NaN/Inf in a dropped worker's buffers must never leak (the mask is
    applied with `where`, not multiplication)."""
    W, d = 4, 200
    theta, lam, h = _problem(W, d, seed=2)
    theta = theta.at[1].set(jnp.nan)
    h = cplx.Complex(h.re.at[1].set(jnp.inf), h.im)
    mask = jnp.asarray([True, False, True, True])
    ccfg = ChannelConfig(n_workers=W, noisy=True, snr_db=20.0)
    T, ia = transport.ota_uplink(theta, lam, h, KEY, 0.5, ccfg,
                                 mask=mask, backend=backend)
    assert bool(jnp.all(jnp.isfinite(T))) and bool(jnp.isfinite(ia))


def test_min_alpha_over_active_workers_only():
    W, d = 4, 64
    theta, lam, h = _problem(W, d, seed=3)
    signals = transport.modulate(theta, lam, h, 0.5)
    # make worker 0 the binding (max-energy) worker, then mask it out
    signals = cplx.Complex(signals.re.at[0].mul(100.0), signals.im)
    e = transport.worker_energy(signals)
    ia_all = transport.inv_alpha_from_energy(e, 1.0)
    ia_masked = transport.inv_alpha_from_energy(
        e, 1.0, mask=jnp.asarray([False, True, True, True]))
    ia_sub = transport.inv_alpha_from_energy(e[1:], 1.0)
    assert float(ia_masked) == float(ia_sub) < float(ia_all)


def test_all_masked_round_is_noop():
    """Every worker in a deep fade -> the round must keep Θ and λ."""
    prob = make_linreg(KEY, W=4, d=6)
    acfg, ccfg, plan = default_cfgs(4, 6, noisy=True, snr_db=30.0,
                                    flip=False, power_control=True)
    scn = make_scenario("deep-fade-truncation", ccfg, h_min=100.0)  # nobody
    alg = make("afadmm", acfg, ccfg, plan, scenario=scn)
    solver = make_solver(prob, acfg.rho)
    st = alg.init(jax.random.PRNGKey(1), prob["theta0"])
    st2, m = alg.round(KEY, st, solver, prob["grad_fn"])
    assert float(m["participation"]) == 0.0
    assert float(m["inv_alpha"]) == 0.0
    np.testing.assert_array_equal(np.asarray(st2.Theta), np.asarray(st.Theta))
    np.testing.assert_array_equal(np.asarray(st2.lam.re),
                                  np.asarray(st.lam.re))
    assert bool(jnp.all(jnp.isfinite(st2.Theta)))


# ---------------------------------------------------------------------------
# end-to-end: flat ADMM + packed LLM trainer
# ---------------------------------------------------------------------------

def test_flat_afadmm_truncation_end_to_end():
    """Deep-fade truncation through the flat ADMM: loss decreases, masked
    workers' duals are frozen, participation < 100%."""
    prob = make_linreg(KEY)
    acfg, ccfg, plan = default_cfgs(prob["W"], prob["d"], noisy=True,
                                    snr_db=30.0, flip=False,
                                    power_control=True)
    scn = make_scenario("deep-fade-truncation", ccfg)
    alg = make("afadmm", acfg, ccfg, plan, scenario=scn)
    solver = make_solver(prob, acfg.rho)
    eval_fn = lambda th: {"loss": prob["f_total"](th)}
    hist = train(alg, prob["theta0"], solver, prob["grad_fn"], 40,
                 jax.random.PRNGKey(1), eval_fn=eval_fn, driver="scan")
    part = hist.extra["participation"]
    assert hist.loss[-1] < hist.loss[0] * 0.1
    assert np.mean(part) < 1.0 and np.min(part) > 0.0

    # dual freezing, round by round
    st = alg.init(jax.random.PRNGKey(1), prob["theta0"])
    round_j = jax.jit(lambda s, k: alg.round(k, s, solver, prob["grad_fn"]))
    saw_masked = False
    for r in range(10):
        st2, _ = round_j(st, jax.random.fold_in(KEY, r))
        mask = np.asarray(st2.phys.mask)
        if (~mask).any():
            saw_masked = True
            np.testing.assert_array_equal(
                np.asarray(st2.lam.re)[~mask], np.asarray(st.lam.re)[~mask])
            np.testing.assert_array_equal(
                np.asarray(st2.lam.im)[~mask], np.asarray(st.lam.im)[~mask])
        st = st2
    assert saw_masked


def test_flat_afadmm_scenario_scan_equals_loop():
    """The scenario state threads through the scan driver bit-for-bit."""
    prob = make_linreg(KEY, W=4, d=6)
    acfg, ccfg, plan = default_cfgs(4, 6, noisy=True, snr_db=30.0,
                                    flip=False, power_control=True)
    scn = make_scenario("deep-fade-truncation", ccfg)
    alg = make("afadmm", acfg, ccfg, plan, scenario=scn)
    solver = make_solver(prob, acfg.rho)
    eval_fn = lambda th: {"loss": prob["f_total"](th)}
    kw = dict(eval_fn=eval_fn, eval_every=1)
    h_loop = train(alg, prob["theta0"], solver, prob["grad_fn"], 12,
                   jax.random.PRNGKey(2), driver="loop", **kw)
    h_scan = train(alg, prob["theta0"], solver, prob["grad_fn"], 12,
                   jax.random.PRNGKey(2), driver="scan", **kw)
    assert h_loop.loss == h_scan.loss
    assert h_loop.extra["participation"] == h_scan.extra["participation"]


def test_flat_afadmm_imperfect_csi_converges_noisily():
    """CSI error degrades but does not break convergence; the air always
    applies the true h while workers act on h_hat."""
    prob = make_linreg(KEY)
    acfg, ccfg, plan = default_cfgs(prob["W"], prob["d"], noisy=False,
                                    flip=False)
    solver = make_solver(prob, acfg.rho)
    eval_fn = lambda th: {"loss": prob["f_total"](th)}
    losses = {}
    for err in (0.0, 0.3):
        scn = make_scenario("markov-doppler", ccfg, csi_err=err)
        alg = make("afadmm", acfg, ccfg, plan, scenario=scn)
        hist = train(alg, prob["theta0"], solver, prob["grad_fn"], 30,
                     jax.random.PRNGKey(3), eval_fn=eval_fn, driver="scan")
        losses[err] = hist.loss[-1]
        assert hist.loss[-1] < hist.loss[0]
    assert losses[0.3] > losses[0.0]   # imperfect CSI costs accuracy


def test_llm_trainer_block_fading_scenario_bitwise():
    """FLConfig(scenario="block-fading") == the legacy packed trainer,
    state-for-state, bitwise (acceptance criterion)."""
    from repro.models import get_model
    from repro.train.llm_trainer import FLConfig, make_fl_train

    W, B, S = 4, 2, 16
    m = get_model("granite-8b", reduced=True)
    batch = {"tokens": jax.random.randint(KEY, (W, B, S), 0,
                                          m.cfg.vocab_size)}
    acfg = AdmmConfig(rho=0.5, flip_on_change=False)
    ccfg = ChannelConfig(n_workers=W, snr_db=40.0)
    states = []
    for scenario, packed in ((None, True), ("block-fading", None)):
        flcfg = FLConfig(mode="replicated", n_workers=W, local_steps=2,
                         local_lr=1e-2, scenario=scenario,
                         packed_uplink=packed)
        init_fn, train_step = make_fl_train(m, flcfg, acfg, ccfg)
        st = init_fn(KEY)
        step = jax.jit(train_step)
        for i in range(3):
            st, _ = step(st, batch, jax.random.fold_in(KEY, i))
        states.append(st)
    legacy, scnr = states
    for a, b in zip(jax.tree_util.tree_leaves(legacy.theta),
                    jax.tree_util.tree_leaves(scnr.theta)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(legacy.lam.re),
                                  np.asarray(scnr.lam.re))
    np.testing.assert_array_equal(np.asarray(legacy.chan.h.re),
                                  np.asarray(scnr.chan.h.re))


def test_llm_trainer_deep_fade_truncation_end_to_end():
    """Packed LLM trainer under truncation: loss decreases, participation
    dips below 100%, masked workers' packed duals are frozen (acceptance
    criterion) — this is also the CI markov+truncation smoke."""
    from repro.models import get_model
    from repro.train.llm_trainer import FLConfig, make_fl_train

    W, B, S = 4, 2, 16
    m = get_model("granite-8b", reduced=True)
    batch = {"tokens": jax.random.randint(KEY, (W, B, S), 0,
                                          m.cfg.vocab_size)}
    flcfg = FLConfig(mode="replicated", n_workers=W, local_steps=2,
                     local_lr=1e-2, scenario="deep-fade-truncation")
    acfg = AdmmConfig(rho=0.5, flip_on_change=False)
    ccfg = ChannelConfig(n_workers=W, snr_db=40.0)
    init_fn, train_step = make_fl_train(m, flcfg, acfg, ccfg)
    st = init_fn(KEY)
    step = jax.jit(train_step)
    losses, parts = [], []
    for i in range(10):
        prev_lam_re = np.asarray(st.lam.re)
        st, met = step(st, batch, jax.random.fold_in(KEY, i))
        mask = np.asarray(st.chan.mask)
        if (~mask).any():
            np.testing.assert_array_equal(np.asarray(st.lam.re)[~mask],
                                          prev_lam_re[~mask])
        losses.append(float(met["loss"]))
        parts.append(float(met["participation"]))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]
    assert min(parts) < 1.0


def test_llm_trainer_scenario_rejects_leafwise_layout():
    from repro.models import get_model
    from repro.train.llm_trainer import FLConfig, make_fl_train

    m = get_model("granite-8b", reduced=True)
    flcfg = FLConfig(mode="replicated", n_workers=2,
                     scenario="markov-doppler", packed_uplink=False)
    with pytest.raises(ValueError, match="packed"):
        make_fl_train(m, flcfg, AdmmConfig(),
                      ChannelConfig(n_workers=2))


def test_llm_trainer_scenario_model_parallel_uses_shard_local_layout():
    """Scenario + model-parallel mesh is no longer rejected: the state
    comes up in the SHARD-LOCAL packed layout ((W, d_pad) with the packed
    axis split over the model shards) and the round runs per shard inside
    shard_map.  The multi-device execution contract (bitwise leafwise
    parity, masked training) lives in ``tests/test_shard_local.py``; here
    we pin the layout decision itself, which needs no devices."""
    from repro.core.cplx import Complex
    from repro.core.packing import build_shard_packspec
    from repro.launch.shardings import model_shard_dims
    from repro.models import get_model
    from repro.train.llm_trainer import FLConfig, make_fl_train

    m = get_model("granite-8b", reduced=True)
    flcfg = FLConfig(mode="replicated", n_workers=2,
                     scenario="markov-doppler")

    # model=1 mesh: the canonical single-buffer packed layout
    mesh1 = make_mesh((1, 1), ("data", "model"))
    init1, _ = make_fl_train(m, flcfg, AdmmConfig(),
                             ChannelConfig(n_workers=2), mesh=mesh1)
    st1 = jax.eval_shape(init1, KEY)
    assert isinstance(st1.lam, Complex)

    # model=2 mesh (abstract — the layout decision needs no devices): the
    # shard-local (W, d_pad) layout, PhyState fading planes included
    mesh2 = jax.sharding.AbstractMesh((1, 2), ("data", "model"))
    init2, _ = make_fl_train(m, flcfg, AdmmConfig(),
                             ChannelConfig(n_workers=2), mesh=mesh2)
    st2 = jax.eval_shape(init2, KEY)
    assert isinstance(st2.lam, Complex)
    dims = model_shard_dims(st2.theta, m.cfg, mesh2, multi_pod=False)
    sspec = build_shard_packspec(st2.theta, dims, 2, batch_dims=1)
    assert any(d is not None for d in dims)     # the model axis is real
    assert sspec.d_pad >= sspec.spec.d
    assert st1.lam.re.shape[-1] == sspec.spec.d
    assert st2.lam.re.shape[-1] == sspec.d_pad
    assert st2.chan.h.re.shape[-1] == sspec.d_pad


def test_trainer_built_without_mesh_refuses_model_parallel_trace():
    """The dual/fading layout is latched when the trainer is BUILT; tracing
    a mesh-less (global (W, D) packed) trainer under a model-parallel mesh
    would quietly recreate the GSPMD reshard storm — it must raise and tell
    the caller to pass mesh= instead."""
    from repro.models import get_model
    from repro.models.sharding import axis_rules
    from repro.train.llm_trainer import FLConfig, make_fl_train

    m = get_model("granite-8b", reduced=True)
    flcfg = FLConfig(mode="replicated", n_workers=2, local_steps=1)
    init_fn, step = make_fl_train(m, flcfg, AdmmConfig(),
                                  ChannelConfig(n_workers=2))   # no mesh
    st = jax.eval_shape(init_fn, KEY)
    batch = jax.ShapeDtypeStruct((2, 1, 8), jnp.int32)
    mesh = jax.sharding.AbstractMesh((1, 2), ("data", "model"))
    with axis_rules(mesh):
        with pytest.raises(ValueError, match="pass mesh="):
            jax.eval_shape(step, st, {"tokens": batch}, KEY)


# ---------------------------------------------------------------------------
# launch specs: scenario threading
# ---------------------------------------------------------------------------

def test_build_train_spec_with_scenario():
    from repro.launch.specs import build_train_spec

    mesh = make_mesh((1, 1), ("data", "model"))
    spec = build_train_spec("granite-8b", mesh, multi_pod=False,
                            reduced=True, scenario="markov-doppler")
    assert spec.meta["scenario"] == "markov-doppler"
    # chan is a PhyState ShapeDtypeStruct tree: (W, D) fading + scalar age
    chan = spec.args[0].chan
    assert chan.h.re.ndim == 2
    assert chan.age.shape == ()
    # and its sharding spec exists for every populated leaf
    n_leaves = len(jax.tree_util.tree_leaves(chan))
    n_specs = len(jax.tree_util.tree_leaves(spec.in_shardings[0].chan))
    assert n_specs == n_leaves


def test_build_train_spec_sketched_accepts_scenario():
    """The re-homed sketched path rides the packed transport, so phy
    scenarios thread straight through — the channel/scenario state lives
    on the (W, d_s) sketch planes instead of the full packed dim."""
    from repro.launch.specs import build_train_spec

    mesh = make_mesh((1, 1), ("data", "model"))
    spec = build_train_spec("granite-8b", mesh, multi_pod=False,
                            reduced=True, scenario="markov-doppler",
                            fl_mode="sketched", sketch_ratio=64)
    assert spec.meta["fl_mode"] == "sketched"
    assert spec.meta["scenario"] == "markov-doppler"
    assert spec.meta["sketch_ratio"] == 64
    state = spec.args[0]
    d_s = state.lam.re.shape[-1]
    # scenario channel state is sized to the sketch planes, not the full
    # packed dimension
    assert state.chan.h.re.shape[-1] == d_s
    assert state.chan.age.shape == ()


def test_truncation_decision_uses_worker_csi():
    """Under imperfect CSI the worker only knows h_hat, so the skip rule
    must run on h_hat — not on the true h it cannot observe."""
    scn = make_scenario("deep-fade-truncation", csi_err=1.0, h_min=0.5)
    st = scn.init(KEY, 64, 8)
    want = np.asarray(participation_mask(st.h_hat, 0.5))
    np.testing.assert_array_equal(np.asarray(st.mask), want)
    # with sigma_e this large the genie (true-h) rule must disagree
    genie = np.asarray(participation_mask(st.h, 0.5))
    assert (want != genie).any()


def test_freq_flat_csi_error_is_per_worker():
    """Narrowband (freq-flat) links have ONE coefficient per worker, so the
    CSI error is one draw per worker — h_hat must be constant across the
    packed dimension (a per-element draw would wash out of the RMS
    truncation statistic at large D, making the skip rule deterministic)."""
    scn = make_scenario("deep-fade-truncation", csi_err=0.5, h_min=0.5)
    st = scn.init(KEY, 256, 1024)
    hat_re = np.asarray(st.h_hat.re)
    hat_im = np.asarray(st.h_hat.im)
    assert (hat_re == hat_re[:, :1]).all()
    assert (hat_im == hat_im[:, :1]).all()
    # the per-worker scalar error keeps its CN(0, sigma_e^2) statistics
    err = st.h_hat - st.h
    sig = float(jnp.sqrt(jnp.mean(cplx.abs2(err))))
    assert abs(sig - 0.5) < 0.05
    # and the skip decision stays stochastic: the genie rule must disagree
    assert (np.asarray(st.mask)
            != np.asarray(participation_mask(st.h, 0.5))).any()


def test_flip_rule_masked_duals_frozen_at_pre_round_value():
    """With flip_on_change=True a truncated worker's dual must freeze at
    the PRE-round state.lam — the channel-redraw flip belongs to workers
    that actually take part in the round."""
    prob = make_linreg(KEY)
    acfg, ccfg, plan = default_cfgs(prob["W"], prob["d"], noisy=True,
                                    snr_db=30.0, flip=True,
                                    power_control=True, coherence=1)
    scn = make_scenario("deep-fade-truncation", ccfg)
    alg = make("afadmm", acfg, ccfg, plan, scenario=scn)
    solver = make_solver(prob, acfg.rho)
    st = alg.init(jax.random.PRNGKey(1), prob["theta0"])
    round_j = jax.jit(lambda s, k: alg.round(k, s, solver, prob["grad_fn"]))
    saw_masked = False
    for r in range(12):
        st2, _ = round_j(st, jax.random.fold_in(KEY, r))
        mask = np.asarray(st2.phys.mask)
        if (~mask).any():
            saw_masked = True
            np.testing.assert_array_equal(
                np.asarray(st2.lam.re)[~mask], np.asarray(st.lam.re)[~mask])
            np.testing.assert_array_equal(
                np.asarray(st2.lam.im)[~mask], np.asarray(st.lam.im)[~mask])
        st = st2
    assert saw_masked


def test_make_scenario_syncs_geometry_slot_to_channel_config():
    """ONE slot clock: the mobility step must advance by the same slot the
    Doppler->rho conversion uses, or a ChannelConfig slot override would
    silently desynchronise fading decorrelation from worker movement."""
    ccfg = ChannelConfig(n_workers=8, slot_seconds=1e-2)
    scn = make_scenario("urban-mobility", ccfg)
    assert scn.cfg.geometry.slot_seconds == pytest.approx(1e-2)
    # an explicit GeometryConfig is re-synced too, not silently kept
    scn2 = make_scenario("urban-mobility", ccfg,
                         geometry=GeometryConfig(speed_mps=5.0))
    assert scn2.cfg.geometry.slot_seconds == pytest.approx(1e-2)
    assert scn2.cfg.geometry.speed_mps == pytest.approx(5.0)


def test_fl_config_rejects_orphan_scenario_overrides():
    from repro.models import get_model
    from repro.train.llm_trainer import FLConfig, make_fl_train

    m = get_model("granite-8b", reduced=True)
    acfg, ccfg = AdmmConfig(), ChannelConfig(n_workers=2)
    with pytest.raises(ValueError, match="scenario overrides"):
        make_fl_train(m, FLConfig(n_workers=2, h_min=0.5), acfg, ccfg)
    with pytest.raises(ValueError, match="scenario overrides"):
        make_fl_train(m, FLConfig(n_workers=2, slots_per_round=4),
                      acfg, ccfg)
    # sketched + scenario is legal now that the sketched path rides the
    # packed transport — it must build, not raise
    init_fn, _ = make_fl_train(
        m, FLConfig(mode="sketched", n_workers=2, sketch_ratio=64,
                    scenario="markov-doppler"), acfg, ccfg)
    st = init_fn(jax.random.PRNGKey(0))
    assert st.chan.h.re.shape == st.lam.re.shape


# ---------------------------------------------------------------------------
# slots_per_round: visible physics in short runs
# ---------------------------------------------------------------------------

def test_slots_per_round_scales_the_shared_clock():
    """One knob, one clock: k slots per round scales BOTH the mobility step
    and the Doppler update period — rho decorrelates faster, geometry
    advances k slots of distance, and the two stay in lock-step."""
    ccfg = ChannelConfig(n_workers=8, slot_seconds=1e-3)
    s1 = make_scenario("urban-mobility", ccfg)
    s8 = make_scenario("urban-mobility", ccfg, slots_per_round=8)
    assert s1.cfg.slots_per_round == 1 and s8.cfg.slots_per_round == 8
    assert s8.cfg.geometry.slot_seconds == pytest.approx(8e-3)
    assert s8.cfg.rho < s1.cfg.rho      # longer update period -> lower J0
    with pytest.raises(ValueError, match="slots_per_round"):
        make_scenario("urban-mobility", ccfg, slots_per_round=0)


def test_slots_per_round_gains_drift_monotonically_faster():
    """ROADMAP PR 4 note: one slot per round is physically honest but too
    slow to see gain evolution in short runs.  More slots per round must
    move the workers (and therefore their path-loss gains) monotonically
    faster over the same number of rounds."""
    ccfg = ChannelConfig(n_workers=16, slot_seconds=1e-3)
    rounds, d = 6, 32
    disp, gain_drift = [], []
    for spr in (1, 8, 64):
        scn = make_scenario("urban-mobility", ccfg, slots_per_round=spr)
        st = scn.init(KEY, 16, d)
        pos0, gain0 = np.asarray(st.pos), np.asarray(st.gain)
        for i in range(rounds):
            st = scn.step(jax.random.fold_in(KEY, i), st)
        disp.append(float(np.mean(np.linalg.norm(
            np.asarray(st.pos) - pos0, axis=-1))))
        gain_drift.append(float(np.mean(np.abs(
            np.asarray(st.gain) - gain0))))
    assert disp[0] < disp[1] < disp[2], disp
    assert gain_drift[0] < gain_drift[1] < gain_drift[2], gain_drift
