"""Fused one-pass OTA round (`transport.ota_round_fused` /
`kernels/ota_round.py`) — ISSUE 6 contracts:

* the jnp oracle is BITWISE equal to the composed modulate → power-scale →
  receive → demodulate path, noise-free AND noisy (the fused noise draw
  `matched_filter_noise_re` samples the same bits `receive` reads), across
  participation masks, imperfect CSI, deep-fade truncation masks, and both
  power-control modes;
* the pallas kernel path matches the oracle to tight allclose (the kernel
  multiplies by 1/ρ where the oracle divides — same contract as `ota.py`);
* the worker-chunked streamed variant (cohort scan, O(chunk·D) peak signal
  memory) matches the monolithic pass to tight allclose for chunk sizes
  including 1 and non-dividing chunks, runs a W=256 round, and its jaxpr
  provably never materialises an O(W·D) compute intermediate;
* the optional fused AR(1) channel step equals `gauss_markov_step` followed
  by the round, bitwise on the jnp path.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend.core import ClosedJaxpr, Jaxpr

from repro.core import cplx, transport
from repro.core.channel import ChannelConfig, matched_filter_noise, rayleigh
from repro.core.cplx import Complex
from repro.phy.scenario import participation_mask

KEY = jax.random.PRNGKey(0)
TOL = dict(rtol=1e-5, atol=1e-6)


def _problem(W, d, seed=0):
    k = jax.random.fold_in(KEY, seed)
    kt, kl, kh, kx = jax.random.split(k, 4)
    theta = jax.random.normal(kt, (W, d), jnp.float32)
    lam = rayleigh(kl, (W, d))
    h = rayleigh(kh, (W, d))
    h_hat = Complex(h.re + 0.1 * jax.random.normal(kx, (W, d)), h.im - 0.05)
    return theta, lam, h, h_hat


def _composed(theta, lam, h, key, rho, ccfg, **kw):
    return transport.ota_uplink(theta, lam, h, key, rho, ccfg, **kw)


RHO = 0.7


@pytest.mark.parametrize("noisy", [False, True])
@pytest.mark.parametrize("power_control", [False, True])
@pytest.mark.parametrize("scenario", ["plain", "mask", "csi", "mask+csi",
                                      "deep-fade"])
def test_fused_oracle_bitwise_vs_composed(noisy, power_control, scenario):
    """jnp fused round == composed uplink, bit for bit, noisy included."""
    W, d = 4, 97
    theta, lam, h, h_hat = _problem(W, d, seed=1)
    ccfg = ChannelConfig(n_workers=W, noisy=noisy, snr_db=20.0)
    # the phy engine's truncation rule: RMS |h| per worker >= h_min; pick
    # h_min between the per-worker extremes so the mask always splits
    rms = jnp.sqrt(jnp.mean(cplx.abs2(h), axis=tuple(range(1, h.re.ndim))))
    h_min = float((jnp.min(rms) + jnp.max(rms)) / 2)
    mask = {"plain": None, "csi": None,
            "mask": jnp.array([True, False, True, True]),
            "mask+csi": jnp.array([True, False, True, True]),
            "deep-fade": participation_mask(h, h_min)}[scenario]
    h_tx = h_hat if "csi" in scenario else None
    if scenario == "deep-fade":
        assert bool(jnp.any(mask)) and not bool(jnp.all(mask))
    T0, ia0 = _composed(theta, lam, h, KEY, RHO, ccfg,
                        power_control=power_control, mask=mask, h_tx=h_tx,
                        backend="jnp")
    T1, ia1, h_air = transport.ota_round_fused(
        theta, lam, h, KEY, RHO, ccfg, power_control=power_control,
        mask=mask, h_tx=h_tx, backend="jnp")
    np.testing.assert_array_equal(np.asarray(T0), np.asarray(T1))
    np.testing.assert_array_equal(np.asarray(ia0), np.asarray(ia1))
    np.testing.assert_array_equal(np.asarray(h_air.re), np.asarray(h.re))


def test_noise_re_is_bitwise_re_of_complex_draw():
    """matched_filter_noise_re == matched_filter_noise(...).re exactly."""
    ccfg = ChannelConfig(n_workers=2, noisy=True)
    for seed in range(3):
        k = jax.random.fold_in(KEY, seed)
        full = matched_filter_noise(k, (257,), ccfg)
        re = transport.matched_filter_noise_re(k, (257,), ccfg)
        np.testing.assert_array_equal(np.asarray(full.re), np.asarray(re))
    off = ChannelConfig(n_workers=2, noisy=False)
    np.testing.assert_array_equal(
        np.asarray(transport.matched_filter_noise_re(KEY, (5,), off)),
        np.zeros(5, np.float32))


@pytest.mark.parametrize("power_control", [False, True])
@pytest.mark.parametrize("scenario", ["plain", "mask", "mask+csi"])
def test_fused_pallas_noise_free_theta(power_control, scenario):
    """Noise-free Θ from the pallas one-pass kernel matches the jnp oracle
    to tight tolerance across a multi-block column grid with padding (the
    kernel multiplies by 1/ρ where the oracle divides, so exact-bit equality
    is not the contract — `ota.py` pins the same tolerance)."""
    W, d = 4, 1024 + 37            # force a multi-block column grid + padding
    theta, lam, h, h_hat = _problem(W, d, seed=2)
    ccfg = ChannelConfig(n_workers=W, noisy=False)
    mask = None if scenario == "plain" else jnp.array([True, False, True,
                                                       True])
    h_tx = h_hat if "csi" in scenario else None
    T1, _, _ = transport.ota_round_fused(
        theta, lam, h, KEY, RHO, ccfg, power_control=power_control,
        mask=mask, h_tx=h_tx, backend="jnp", block_cols=256)
    T2, _, _ = transport.ota_round_fused(
        theta, lam, h, KEY, RHO, ccfg, power_control=power_control,
        mask=mask, h_tx=h_tx, backend="pallas", block_cols=256)
    np.testing.assert_allclose(np.asarray(T1), np.asarray(T2), **TOL)


@pytest.mark.parametrize("power_control", [False, True])
def test_fused_pallas_noisy_allclose(power_control):
    W, d = 3, 500
    theta, lam, h, _ = _problem(W, d, seed=3)
    ccfg = ChannelConfig(n_workers=W, noisy=True, snr_db=20.0)
    T1, ia1, _ = transport.ota_round_fused(
        theta, lam, h, KEY, RHO, ccfg, power_control=power_control,
        backend="jnp")
    T2, ia2, _ = transport.ota_round_fused(
        theta, lam, h, KEY, RHO, ccfg, power_control=power_control,
        backend="pallas")
    np.testing.assert_allclose(np.asarray(T1), np.asarray(T2), **TOL)
    np.testing.assert_allclose(np.asarray(ia1), np.asarray(ia2), **TOL)


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("rho_fad,redraw", [(0.0, True), (0.0, False),
                                            (0.9, True), (0.9, False)])
def test_fused_chan_step_equals_gauss_markov_then_round(backend, rho_fad,
                                                        redraw):
    """chan_step fusion == gauss_markov_step(h) then the round, and the
    returned h_air is the stepped channel (jnp: bitwise)."""
    from repro.phy.fading import gauss_markov_step

    W, d = 3, 300
    theta, lam, h, _ = _problem(W, d, seed=4)
    ccfg = ChannelConfig(n_workers=W, noisy=True, snr_db=20.0)
    kw = jax.random.fold_in(KEY, 99)
    w = rayleigh(kw, (W, d))       # the innovations gauss_markov_step draws
    h2 = gauss_markov_step(kw, h, rho_fad, redraw, backend="jnp")
    T_ref, ia_ref, _ = transport.ota_round_fused(
        theta, lam, h2, KEY, RHO, ccfg, backend="jnp")
    T, ia, h_air = transport.ota_round_fused(
        theta, lam, h, KEY, RHO, ccfg,
        chan_step=(w, rho_fad, jnp.asarray(redraw)), backend=backend)
    if backend == "jnp":
        np.testing.assert_array_equal(np.asarray(T_ref), np.asarray(T))
        np.testing.assert_array_equal(np.asarray(h2.re),
                                      np.asarray(h_air.re))
        np.testing.assert_array_equal(np.asarray(h2.im),
                                      np.asarray(h_air.im))
    else:
        np.testing.assert_allclose(np.asarray(T_ref), np.asarray(T), **TOL)
        np.testing.assert_allclose(np.asarray(h2.re), np.asarray(h_air.re),
                                   **TOL)


# ---------------------------------------------------------------------------
# streamed worker cohorts
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("chunk", [1, 2, 3, 5, 7])
@pytest.mark.parametrize("masked", [False, True])
def test_streamed_equals_monolithic(chunk, masked):
    """Cohort-streamed round == monolithic for dividing AND non-dividing
    chunk sizes (W=7: chunks 2, 3, 5 pad the worker axis), with masks."""
    W, d = 7, 230
    theta, lam, h, _ = _problem(W, d, seed=5)
    ccfg = ChannelConfig(n_workers=W, noisy=True, snr_db=20.0)
    mask = jnp.array([True, False, True, True, False, True, True]) \
        if masked else None
    T0, ia0, _ = transport.ota_round_fused(
        theta, lam, h, KEY, RHO, ccfg, mask=mask, backend="jnp")
    T1, ia1, _ = transport.ota_round_fused(
        theta, lam, h, KEY, RHO, ccfg, mask=mask, worker_chunk=chunk,
        backend="jnp")
    np.testing.assert_allclose(np.asarray(T0), np.asarray(T1), **TOL)
    np.testing.assert_allclose(np.asarray(ia0), np.asarray(ia1), **TOL)


def test_streamed_chan_step_roundtrips_h():
    """Streaming + fused channel step: the re-assembled h_air matches the
    unchunked gauss_markov result.  Tolerance, not bitwise: the scan-compiled
    cohort body may emit a fused multiply-add for ρ·h + s·w that the eager
    monolithic path does not."""
    from repro.phy.fading import gauss_markov_step

    W, d = 5, 120
    theta, lam, h, _ = _problem(W, d, seed=6)
    ccfg = ChannelConfig(n_workers=W, noisy=False)
    kw = jax.random.fold_in(KEY, 7)
    w = rayleigh(kw, (W, d))
    h2 = gauss_markov_step(kw, h, 0.8, True, backend="jnp")
    T_ref, _, _ = transport.ota_round_fused(theta, lam, h2, KEY, RHO, ccfg,
                                            backend="jnp")
    T, _, h_air = transport.ota_round_fused(
        theta, lam, h, KEY, RHO, ccfg, worker_chunk=2,
        chan_step=(w, 0.8, jnp.asarray(True)), backend="jnp")
    np.testing.assert_allclose(np.asarray(h2.re), np.asarray(h_air.re),
                               **TOL)
    np.testing.assert_allclose(np.asarray(h2.im), np.asarray(h_air.im),
                               **TOL)
    np.testing.assert_allclose(np.asarray(T_ref), np.asarray(T), **TOL)


_LAYOUT_PRIMS = {
    "reshape", "transpose", "broadcast_in_dim", "convert_element_type",
    "squeeze", "slice", "concatenate", "pad", "copy", "dynamic_slice",
    "dynamic_update_slice",
}


def _max_compute_out_size(fn, *args):
    """Largest output aval (elements) of any NON-layout equation in the
    jaxpr of ``fn``, recursing into scan/cond/pjit bodies.  Layout ops
    (reshape/pad/slice/...) are excluded: they restructure existing buffers
    rather than create live compute intermediates — the streamed round's
    signal-plane claim is about COMPUTE working set."""
    jaxpr = jax.make_jaxpr(fn)(*args)
    worst = 0

    def walk(j):
        nonlocal worst
        for eqn in j.eqns:
            for v in eqn.params.values():
                if isinstance(v, ClosedJaxpr):
                    walk(v.jaxpr)
                elif isinstance(v, Jaxpr):
                    walk(v)
                elif isinstance(v, (list, tuple)):
                    for vv in v:
                        if isinstance(vv, ClosedJaxpr):
                            walk(vv.jaxpr)
                        elif isinstance(vv, Jaxpr):
                            walk(vv)
            # container eqns (pjit-wrapped jnp.pad etc.) re-report their
            # inner output; the recursion above already scored the body
            if eqn.primitive.name in _LAYOUT_PRIMS or any(
                    isinstance(v, (ClosedJaxpr, Jaxpr))
                    for v in eqn.params.values()):
                continue
            for ov in eqn.outvars:
                worst = max(worst, ov.aval.size)

    walk(jaxpr.jaxpr)
    return worst


def test_w256_streamed_smoke_and_peak_memory():
    """W=256 cohort round runs, matches the monolithic result, and the
    streamed jaxpr's largest compute intermediate is O(chunk·D) — the
    monolithic pass provably materialises O(W·D)."""
    W, d, chunk = 256, 512, 32
    theta, lam, h, _ = _problem(W, d, seed=8)
    ccfg = ChannelConfig(n_workers=W, noisy=True, snr_db=20.0)
    mask = participation_mask(h, 0.5)

    def mono(t, l, hh, k):
        return transport.ota_round_fused(t, l, hh, k, RHO, ccfg, mask=mask,
                                         backend="jnp")[0]

    def streamed(t, l, hh, k):
        return transport.ota_round_fused(t, l, hh, k, RHO, ccfg, mask=mask,
                                         worker_chunk=chunk,
                                         backend="jnp")[0]

    T0 = jax.jit(mono)(theta, lam, h, KEY)
    T1 = jax.jit(streamed)(theta, lam, h, KEY)
    np.testing.assert_allclose(np.asarray(T0), np.asarray(T1),
                               rtol=1e-4, atol=1e-5)

    worst_mono = _max_compute_out_size(mono, theta, lam, h, KEY)
    worst_stream = _max_compute_out_size(streamed, theta, lam, h, KEY)
    assert worst_mono >= W * d, worst_mono            # O(W·D) baseline
    assert worst_stream <= 4 * chunk * d, worst_stream  # O(chunk·D) pinned
    assert worst_stream * 2 <= worst_mono


def test_streamed_zero_pad_workers_never_bind_alpha():
    """Padded (all-zero) cohort rows carry zero energy -> α=+inf there, so
    padding never throttles real workers; a fully-padded final chunk still
    matches the monolithic α exactly."""
    W, d = 5, 64
    theta, lam, h, _ = _problem(W, d, seed=9)
    ccfg = ChannelConfig(n_workers=W, noisy=False)
    _, ia0, _ = transport.ota_round_fused(theta, lam, h, KEY, RHO, ccfg,
                                          backend="jnp")
    _, ia1, _ = transport.ota_round_fused(theta, lam, h, KEY, RHO, ccfg,
                                          worker_chunk=4, backend="jnp")
    np.testing.assert_allclose(np.asarray(ia0), np.asarray(ia1), **TOL)
    assert np.isfinite(np.asarray(ia1))


@pytest.mark.parametrize("dead_chunk", [0, 1])
def test_streamed_all_masked_chunk_nan_safe(dead_chunk):
    """A chunk-aligned fully-faded cohort must not poison the per-chunk
    stats with 0/0 (ISSUE 7 satellite): the cohort scan's masked stats are
    NaN-safe `where`s, so an empty chunk contributes exact zeros and the
    streamed result still matches the monolithic masked receive."""
    W, d, chunk = 8, 64, 4
    theta, lam, h, _ = _problem(W, d, seed=11)
    ccfg = ChannelConfig(n_workers=W, noisy=True, snr_db=20.0)
    dead = np.zeros(W, bool)
    dead[dead_chunk * chunk:(dead_chunk + 1) * chunk] = True
    mask = jnp.asarray(~dead)
    T0, ia0, _ = transport.ota_round_fused(theta, lam, h, KEY, RHO, ccfg,
                                           mask=mask, backend="jnp")
    T1, ia1, _ = transport.ota_round_fused(theta, lam, h, KEY, RHO, ccfg,
                                           mask=mask, worker_chunk=chunk,
                                           backend="jnp")
    assert np.isfinite(np.asarray(T1)).all()
    assert np.isfinite(np.asarray(ia1))
    np.testing.assert_allclose(np.asarray(T0), np.asarray(T1), **TOL)
    np.testing.assert_allclose(np.asarray(ia0), np.asarray(ia1), **TOL)


def test_streamed_fully_masked_round_stays_finite():
    """EVERY chunk empty (the all-masked round): no 0/0 anywhere — the
    degenerate round demodulates to finite values the round driver's
    keep-previous-Θ logic then discards."""
    W, d = 8, 64
    theta, lam, h, _ = _problem(W, d, seed=12)
    ccfg = ChannelConfig(n_workers=W, noisy=True, snr_db=20.0)
    none = jnp.zeros((W,), bool)
    for chunk in (None, 4):
        T, ia, _ = transport.ota_round_fused(theta, lam, h, KEY, RHO, ccfg,
                                             mask=none, worker_chunk=chunk,
                                             backend="jnp")
        assert np.isfinite(np.asarray(T)).all(), chunk
        assert np.isfinite(np.asarray(ia)), chunk


def test_autotune_sweep_returns_usable_config():
    res = transport.autotune_ota_round(4, 256, iters=2,
                                       block_cols_grid=(256,),
                                       worker_chunks=(0, 2))
    assert {"block_cols", "worker_chunk", "us"} <= set(res["best"])
    assert res["best"] in res["table"] and len(res["table"]) == 2
