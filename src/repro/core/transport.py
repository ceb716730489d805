"""Unified OTA transport layer — ONE implementation of the paper's analog
signal path (Alg. 1: modulate → power-scale → superpose → matched-filter →
demodulate), shared by the flat ``(W, d)`` path (``core.admm``), the pytree
path (``core.tree_ota``), and the sketched LLM trainer.

Backend dispatch
----------------
Every signal primitive takes ``backend=`` ∈ {``None``, ``"jnp"``,
``"pallas"``}:

* ``"jnp"``    — pure-jnp reference (the correctness contract; bit-identical
                 to the historical ``core.admm`` / ``core.tree_ota`` math).
* ``"pallas"`` — fused kernels from ``kernels/ota.py`` /
                 ``kernels/admm_update.py``: one HBM pass per primitive, and
                 the whole superpose→filter→demodulate receive chain in a
                 single kernel (interpret mode off-TPU, Mosaic on TPU).
* ``None``     — resolve from the ``REPRO_USE_PALLAS`` env var at trace
                 time (same switch the model kernels use); default jnp.

Worker-axis reductions stay pluggable: ``reduce_fn`` (superposition — the
single analog "channel use", a psum under shard_map) and ``min_reduce_fn``
(the power-control min-α consensus, a pmin under shard_map).  When a
cross-device ``reduce_fn`` is supplied the pallas backend composes the
modulate/demodulate kernels around it; when the reduction is local the whole
receive chain runs fused.

All OTA arithmetic runs in f32 regardless of parameter dtype (the analog
signal path); duals are f32.  The matched-filter receiver only ever samples
the REAL plane (Θ = Re{y}/Σ|h|², Eq. 24), so :func:`receive` superposes the
real plane alone — what ``optflags`` used to gate behind ``ota_re`` is now
simply how the transport works (it is bit-identical to taking Re{y} of the
full complex superposition).
"""
from __future__ import annotations

import math
import os
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import cplx
from repro.core.channel import ChannelConfig, matched_filter_noise
from repro.core.cplx import Complex
from repro.core.power import alpha_from_energy
from repro.obs.profiling import layer

Array = jax.Array
ReduceFn = Callable[[Array], Array]

BACKENDS = ("jnp", "pallas")


def resolve_backend(backend: Optional[str] = None) -> str:
    """Explicit ``backend=`` wins; else the ``REPRO_USE_PALLAS`` env var."""
    if backend is None:
        backend = "pallas" if os.environ.get("REPRO_USE_PALLAS", "0") == "1" \
            else "jnp"
    if backend not in BACKENDS:
        raise ValueError(f"unknown OTA backend {backend!r}; want one of {BACKENDS}")
    return backend


def _interpret() -> bool:
    """Pallas kernels run interpreted off the TPU (the CPU tests), and
    compile to Mosaic on it."""
    return jax.default_backend() != "tpu"


def _f32(x: Array) -> Array:
    return x.astype(jnp.float32)


# ---------------------------------------------------------------------------
# Signal primitives (backend-dispatched)
# ---------------------------------------------------------------------------

def modulate(theta: Array, lam: Complex, h: Complex, rho: float,
             *, backend: Optional[str] = None) -> Complex:
    """Worker TX signal s = h*·θ + λ*/ρ  (Alg. 1 line 14).  Shapes (W, ...)."""
    if resolve_backend(backend) == "pallas":
        from repro.kernels import ota as _k
        shape = theta.shape
        sre, sim = _k.ota_modulate(
            theta.reshape(-1), lam.re.reshape(-1), lam.im.reshape(-1),
            h.re.reshape(-1), h.im.reshape(-1), float(rho),
            interpret=_interpret())
        return Complex(sre.reshape(shape), sim.reshape(shape))
    tf = _f32(theta)
    return Complex(h.re * tf + lam.re / rho, -h.im * tf - lam.im / rho)


def superpose(signals: Complex, h: Complex,
              reduce_fn: Optional[ReduceFn] = None) -> Tuple[Complex, Array]:
    """The air: y = Σ_n h_n ⊙ s_n ; also the pilot aggregate Σ_n |h_n|².

    Both complex planes, for callers that inspect the full observation (the
    privacy harness).  The hot path (:func:`receive`) superposes Re only.
    """
    rx = cplx.cmul(h, signals)
    sumh2 = cplx.abs2(h)
    if reduce_fn is None:
        reduce_fn = lambda x: jnp.sum(x, axis=0)
    return Complex(reduce_fn(rx.re), reduce_fn(rx.im)), reduce_fn(sumh2)


def demodulate(y: Complex, sumh2: Array, noise: Complex,
               inv_alpha: Array | float = 1.0,
               *, backend: Optional[str] = None) -> Array:
    """PS global update Θ = Re{y + z/α} / Σ|h|²  (Eq. 24)."""
    y_re = y.re if isinstance(y, Complex) else y
    n_re = noise.re if isinstance(noise, Complex) else noise
    if resolve_backend(backend) == "pallas":
        from repro.kernels import ota as _k
        shape = y_re.shape
        out = _k.ota_demodulate_dyn(
            y_re.reshape(-1), jnp.broadcast_to(n_re, shape).reshape(-1),
            sumh2.reshape(-1), inv_alpha, interpret=_interpret())
        return out.reshape(shape)
    return (y_re + n_re * inv_alpha) / jnp.maximum(sumh2, 1e-12)


def _mask_planes(x: Complex, mask: Array) -> Complex:
    """Zero a masked worker's planes via ``where`` (NOT multiplication:
    a dropped worker's buffers may hold anything, and NaN·0 = NaN would
    leak it into the superposition).  mask: (W,) -> broadcast over (W, ...)."""
    mb = mask.reshape((mask.shape[0],) + (1,) * (x.re.ndim - 1))
    return cplx.cwhere(mb, x, cplx.czero(x.re.shape, x.re.dtype))


@layer("ota_receive")
def receive(signals: Complex, h: Complex, key: Array, ccfg: ChannelConfig,
            inv_alpha: Array | float = 1.0, *,
            reduce_fn: Optional[ReduceFn] = None,
            mask: Optional[Array] = None,
            backend: Optional[str] = None) -> Array:
    """Fused superpose → matched-filter → demodulate.  (W, ...) -> (...).

    Only the real plane is superposed: Θ never reads Im{y} (Eq. 24), and
    Re{Σ h⊙s} is computed with the same elementwise expression either way,
    so this is bit-identical to the full complex superposition — but halves
    the reduce bytes (the all-reduce the roofline counts as the channel use).

    ``mask`` ((W,) bool) drops workers from the round: a masked worker
    contributes exactly zero to both the superposition and the pilot
    aggregate Σ|h|² (deep-fade truncation — ``repro.phy``).  An all-masked
    round divides zero signal by the ε-clamped zero pilot: callers holding
    the previous global model must guard it (the round drivers do).
    """
    backend = resolve_backend(backend)
    out_shape = signals.re.shape[1:]
    noise = matched_filter_noise(key, out_shape, ccfg)
    if backend == "pallas" and reduce_fn is None:
        W = signals.re.shape[0]
        if mask is not None:
            from repro.kernels import phy_channel as _pk
            out = _pk.ota_receive_masked(
                signals.re.reshape(W, -1), signals.im.reshape(W, -1),
                h.re.reshape(W, -1), h.im.reshape(W, -1),
                mask.reshape(W), noise.re.reshape(-1), inv_alpha,
                interpret=_interpret())
            return out.reshape(out_shape)
        from repro.kernels import ota as _k
        out = _k.ota_receive(
            signals.re.reshape(W, -1), signals.im.reshape(W, -1),
            h.re.reshape(W, -1), h.im.reshape(W, -1),
            noise.re.reshape(-1), inv_alpha, interpret=_interpret())
        return out.reshape(out_shape)
    if mask is not None:
        signals = _mask_planes(signals, mask)
        h = _mask_planes(h, mask)
    rx_re = h.re * signals.re - h.im * signals.im
    sumh2 = cplx.abs2(h)
    red = reduce_fn or (lambda x: jnp.sum(x, axis=0))
    y_re, p2 = red(rx_re), red(sumh2)
    return demodulate(y_re, p2, noise.re, inv_alpha, backend=backend)


class OtaAccumulator(NamedTuple):
    """Running receiver state for a worker-at-a-time uplink.

    When workers are time-multiplexed (the sketched LLM trainer's worker
    ``lax.scan``) the superposition Σ_n h_n⊙s_n cannot be a single axis-0
    reduction — it is an accumulation across scan steps.  The accumulator
    carries the two running sums the receiver needs; the fused demodulate
    (:func:`ota_receive_accumulated`) then runs ONCE per round.
    """

    y_re: Array    # running Re{Σ_n h_n ⊙ s_n}
    sumh2: Array   # running Σ_n |h_n|² (the pilot aggregate)


def ota_accumulate_init(shape, dtype=jnp.float32) -> OtaAccumulator:
    return OtaAccumulator(y_re=jnp.zeros(shape, dtype),
                          sumh2=jnp.zeros(shape, dtype))


def ota_accumulate(acc: OtaAccumulator, signal: Complex, h: Complex,
                   *, backend: Optional[str] = None) -> OtaAccumulator:
    """Add ONE worker's contribution to the running superposition.

    y_re += Re{h ⊙ s};  Σ|h|² += |h|².  Elementwise over the worker's
    signal shape — the pallas backend fuses both updates into a single
    HBM pass over the four input planes.
    """
    if resolve_backend(backend) == "pallas":
        from repro.kernels import ota as _k
        shape = acc.y_re.shape
        y, p2 = _k.ota_accumulate(
            acc.y_re.reshape(-1), acc.sumh2.reshape(-1),
            signal.re.reshape(-1), signal.im.reshape(-1),
            h.re.reshape(-1), h.im.reshape(-1), interpret=_interpret())
        return OtaAccumulator(y.reshape(shape), p2.reshape(shape))
    return OtaAccumulator(
        y_re=acc.y_re + (h.re * signal.re - h.im * signal.im),
        sumh2=acc.sumh2 + cplx.abs2(h))


@layer("ota_receive")
def ota_receive_accumulated(acc: OtaAccumulator, key: Array,
                            ccfg: ChannelConfig,
                            inv_alpha: Array | float = 1.0, *,
                            backend: Optional[str] = None) -> Array:
    """Demodulate an accumulated superposition: Θ = (y + z/α)/Σ|h|².

    The worker-at-a-time twin of :func:`receive` — one fused kernel, one
    noise draw over the full (packed) vector, per round.
    """
    noise = matched_filter_noise(key, acc.y_re.shape, ccfg)
    return demodulate(acc.y_re, acc.sumh2, noise.re, inv_alpha,
                      backend=backend)


@layer("ota_dual")
def dual_update(lam: Complex, h: Complex, theta: Array, Theta: Array,
                rho: float, noise_re: Array | float = 0.0,
                *, backend: Optional[str] = None) -> Complex:
    """Eq. (11): λ' = λ + ρ h (θ − Θ) − ρ Re{z}  (noise only under analog
    downlink).  Θ broadcasts over the leading worker dim."""
    if resolve_backend(backend) == "pallas":
        from repro.kernels import admm_update as _k
        shape = lam.re.shape
        # the kernel reads Θ once per worker: no (W, ...) broadcast in HBM
        W = shape[0] if len(shape) > 1 and jnp.shape(Theta) == shape[1:] \
            else 1
        if W == 1:
            Theta = jnp.broadcast_to(Theta, shape)
        flat = lambda x: x.reshape(W, -1)
        nz = None
        if not (isinstance(noise_re, (int, float)) and noise_re == 0.0):
            nz = flat(jnp.broadcast_to(jnp.asarray(noise_re, jnp.float32),
                                       shape))
        ore, oim = _k.admm_dual_update(
            flat(lam.re), flat(lam.im), flat(h.re), flat(h.im), flat(theta),
            Theta.reshape(-1), float(rho), nz, interpret=_interpret())
        return Complex(ore.reshape(shape), oim.reshape(shape))
    r = _f32(theta) - _f32(Theta)
    return Complex(lam.re + rho * (h.re * r - noise_re),
                   lam.im + rho * h.im * r)


def flip_lambda(grad_f: Array, theta: Array, Theta_prev: Array, h: Complex,
                rho: float, *, backend: Optional[str] = None) -> Complex:
    """Re-solve stationarity (Eq. 6) for λ when the channel changed.

    Target: λ* h = t := −(∂f(θ) + ρ|h|²(θ − Θ^k)).  The minimum-norm complex
    solution is λ = t · h / |h|²  (then λ* h = t, real, exactly).
    """
    if resolve_backend(backend) == "pallas":
        from repro.kernels import admm_update as _k
        shape = theta.shape
        Th = jnp.broadcast_to(_f32(Theta_prev), shape)
        ore, oim = _k.admm_flip_lambda(
            grad_f.reshape(-1), theta.reshape(-1), Th.reshape(-1),
            h.re.reshape(-1), h.im.reshape(-1), float(rho),
            interpret=_interpret())
        return Complex(ore.reshape(shape), oim.reshape(shape))
    t = -(grad_f + rho * cplx.abs2(h) * (_f32(theta) - _f32(Theta_prev)))
    scale = t / jnp.maximum(cplx.abs2(h), 1e-12)
    return Complex(h.re * scale, h.im * scale)


def penalty_grad(theta: Array, lam: Complex, h: Complex, Theta: Array,
                 rho: float) -> Array:
    """∇ of the augmented-Lagrangian terms added to f_n (prox local steps):
    Re{λ* h} + ρ|h|²(θ − Θ).  Returns theta's dtype (leafwise-safe)."""
    mu = cplx.cmul_conj(h, lam).re  # Re{λ* h} == Re{h λ*}
    g = mu + rho * cplx.abs2(h) * (_f32(theta) - _f32(Theta))
    return g.astype(theta.dtype)


# ---------------------------------------------------------------------------
# Power control (min-α protocol, paper Sec. 2)
# ---------------------------------------------------------------------------

def worker_energy(signals: Complex) -> Array:
    """Σ over all elements of |s|² per worker: (W, ...) -> (W,)."""
    e = cplx.abs2(signals)
    return jnp.sum(e.reshape(e.shape[0], -1), axis=1)


def inv_alpha_from_energy(energy: Array, budget: float,
                          min_reduce_fn: Optional[ReduceFn] = None,
                          mask: Optional[Array] = None) -> Array:
    """1/α with α = min_n sqrt(P_budget / E_n) over the *active* workers.

    Guards (regression-tested in ``tests/test_channel_power.py``):

    * zero-energy rows — a worker with nothing to send imposes no power
      constraint; its α_n is +inf so it never binds the min (the historical
      1e-30 clamp instead produced α ≈ sqrt(P·1e30), which dominated any
      per-worker α statistic and made `tx_energy` reports meaningless).
    * ``mask`` ((W,) bool) — truncated (non-participating) workers are
      excluded from the min-α consensus: they don't transmit this round, so
      they must not throttle the workers that do.
    * all rows masked/zero — α = +inf, so 1/α = 0 exactly: demodulate adds
      zero noise and the round drivers degenerate to a no-op update.
    """
    alphas = alpha_from_energy(energy, budget)
    if mask is not None:
        alphas = jnp.where(mask, alphas, jnp.inf)
    a = jnp.min(alphas)
    if min_reduce_fn is not None:
        a = min_reduce_fn(a)
    return 1.0 / a


def power_scale(signals: Complex, ccfg: ChannelConfig,
                min_reduce_fn: Optional[ReduceFn] = None,
                mask: Optional[Array] = None) -> Array:
    """inv_alpha for a single-leaf uplink.  Budget: per-subcarrier power P
    (the paper's SNR is per-subcarrier: SNR = P|h|²/(N0 W)) × elements
    uploaded per worker."""
    d = int(signals.re.size // signals.re.shape[0])
    budget = ccfg.transmit_power * d
    return inv_alpha_from_energy(worker_energy(signals), budget,
                                 min_reduce_fn=min_reduce_fn, mask=mask)


# ---------------------------------------------------------------------------
# The full uplink (Alg. 1, the "transport" entry point)
# ---------------------------------------------------------------------------

@layer("ota_receive")
def ota_uplink(theta: Array, lam: Complex, h: Complex, key: Array,
               rho: float, ccfg: ChannelConfig, *,
               power_control: bool = True,
               reduce_fn: Optional[ReduceFn] = None,
               min_reduce_fn: Optional[ReduceFn] = None,
               mask: Optional[Array] = None,
               h_tx: Optional[Complex] = None,
               backend: Optional[str] = None) -> Tuple[Array, Array]:
    """modulate → power-scale → superpose → matched-filter → demodulate.

    Args:
      theta/lam/h: (W, ...) worker-major; Θ returned with the worker dim
        reduced away.
      key: PRNG key for the matched-filter AWGN (ignored if noise-free).
      mask: optional (W,) participation mask (``repro.phy`` deep-fade
        truncation): masked workers contribute exactly zero to the
        superposition/pilot aggregate and are excluded from min-α.
      h_tx: the channel the *workers* precode with (imperfect CSI
        ``h_hat``); the air still applies ``h``.  None = perfect CSI.

    Returns (Theta, inv_alpha).
    """
    backend = resolve_backend(backend)
    signals = modulate(theta, lam, h if h_tx is None else h_tx, rho,
                       backend=backend)
    if power_control:
        inv_alpha = power_scale(signals, ccfg, min_reduce_fn=min_reduce_fn,
                                mask=mask)
    else:
        # f32 like the rest of the analog path (a bf16 theta must not
        # down-cast the noise/α arithmetic in demodulate)
        inv_alpha = jnp.asarray(1.0, jnp.float32)
    Theta = receive(signals, h, key, ccfg, inv_alpha,
                    reduce_fn=reduce_fn, mask=mask, backend=backend)
    return Theta, inv_alpha


# ---------------------------------------------------------------------------
# Fused one-pass round (ISSUE 6 / ROADMAP item 1): each worker plane read
# from HBM exactly once per round
# ---------------------------------------------------------------------------

def snr_db_from_power(sig: Array, npow: Array) -> Array:
    """Effective receive SNR in dB from signal/noise power sums.

    The division-free formula the round health guard uses
    (``repro.faults.guards``): both operands are clamped to 1e-30 before
    the ratio so an all-masked round (zero signal, zero effective noise)
    yields 0 dB instead of NaN, and the result is clamped to ±1e3 dB.
    Shared by the guard verdicts and ``obs/rx_snr_db`` telemetry so the
    two can never drift apart.
    """
    snr = 10.0 * jnp.log10(jnp.maximum(sig, 1e-30) / jnp.maximum(npow, 1e-30))
    return jnp.nan_to_num(snr, nan=-1e3, posinf=1e3, neginf=-1e3)


def round_telemetry(tel, y_re: Array, noise_re: Array, inv_alpha: Array,
                    energy: Optional[Array], mask: Optional[Array],
                    n_workers: int) -> dict:
    """``obs/`` channel telemetry from values the receive epilogue already
    holds in registers (see the ``repro.obs`` schema docstring).

    All O(d) elementwise-plus-reduce arithmetic over buffers the epilogue
    just produced — no extra HBM passes over the (W, d) worker planes and
    no extra dispatches; the whole dict rides the scan carry.
    """
    sig = jnp.sum(y_re * y_re)
    n_eff = noise_re * inv_alpha
    npw = jnp.sum(n_eff * n_eff)
    # inv_alpha == 0 exactly means nobody transmitted (all-masked round)
    alpha = jnp.where(inv_alpha > 0, 1.0 / jnp.maximum(inv_alpha, 1e-38), 0.0)
    out = {
        "obs/rx_snr_db": snr_db_from_power(sig, npw),
        "obs/min_alpha": alpha,
        "obs/active_workers": (jnp.asarray(float(n_workers), jnp.float32)
                               if mask is None
                               else jnp.sum(mask.astype(jnp.float32))),
    }
    if tel.per_worker and energy is not None:
        # the energy each worker actually radiated: it transmits alpha*s,
        # so E_tx = alpha^2 * |s|^2 summed — a (W,) VECTOR leaf
        e_tx = energy * (alpha * alpha)
        if mask is not None:
            e_tx = jnp.where(mask, e_tx, 0.0)
        out["obs/tx_energy"] = e_tx
    return out


@layer("ota_noise")
def matched_filter_noise_re(key: Array, shape, ccfg: ChannelConfig) -> Array:
    """REAL plane of :func:`~repro.core.channel.matched_filter_noise`,
    without generating the imaginary draw the receiver never reads.

    Bitwise identical to ``matched_filter_noise(key, shape, ccfg).re``:
    ``awgn`` splits the key and feeds the re plane from the FIRST subkey
    only, so skipping the im draw changes no sampled value — it just halves
    the threefry work of the round's only O(D) PRNG draw.
    """
    if not ccfg.noisy:
        return jnp.zeros(shape, jnp.float32)
    kr, _ = jax.random.split(key)
    s = jnp.sqrt(jnp.asarray(ccfg.noise_var_matched / 2.0, jnp.float32))
    return jax.random.normal(kr, shape, jnp.float32) * s


def _chan_step_jnp(h: Complex, chan_step) -> Complex:
    """AR(1) fading update from pre-drawn innovations — expression-for-
    expression :func:`repro.phy.fading.gauss_markov_step` (given its ``w``),
    so fusing the step into the round changes no bit."""
    w, rho_fad, redraw = chan_step
    if float(rho_fad) == 0.0:
        return cplx.cwhere(redraw, w, h)
    s = math.sqrt(max(1.0 - float(rho_fad) ** 2, 0.0))  # innovation_scale
    nxt = Complex(rho_fad * h.re + s * w.re, rho_fad * h.im + s * w.im)
    return cplx.cwhere(redraw, nxt, h)


def ota_round_stats(theta: Array, lam: Complex, h: Complex, rho: float, *,
                    mask: Optional[Array] = None,
                    h_tx: Optional[Complex] = None,
                    chan_step=None,
                    backend: Optional[str] = None,
                    block_cols: Optional[int] = None,
                    ) -> Tuple[Array, Array, Array, Complex]:
    """One pass over the ``(W, ...)`` worker planes: modulate → per-worker
    energy → (mask) → superpose → pilot aggregate.

    Returns ``(y_re, sumh2, energy, h_air)`` where ``y_re``/``sumh2`` have
    the worker dim reduced away, ``energy`` is the per-worker ``(W,)``
    energies the min-α consensus needs, and ``h_air`` is the channel the air
    applied — ``h`` itself, or the AR(1)-stepped channel when
    ``chan_step = (w, rho_fad, redraw)`` fuses the fading update
    (:func:`repro.phy.fading.gauss_markov_step` with pre-drawn innovations
    ``w``) into the same pass.

    This is everything in the round that *touches the worker planes*; the
    remaining receiver arithmetic (min-α, noise, demodulate) is O(d) and
    worker-free.  The jnp path is expression-for-expression the composed
    ``modulate`` → ``power_scale`` → ``receive`` chain (bitwise contract,
    pinned in ``tests/test_fused_round.py``); the pallas path
    (``kernels/ota_round.py``) runs it as ONE kernel launch, with per-block
    energy partials whose reduction order makes energies tolerance-equal
    (not bitwise) to :func:`worker_energy`.
    """
    backend = resolve_backend(backend)
    if backend == "pallas":
        from repro.kernels import ota_round as _k
        W = theta.shape[0]
        shape = theta.shape
        pk = dict(mask=None if mask is None else mask.reshape(W),
                  htx=None if h_tx is None else
                  (h_tx.re.reshape(W, -1), h_tx.im.reshape(W, -1)),
                  chan=None if chan_step is None else
                  (chan_step[0].re.reshape(W, -1),
                   chan_step[0].im.reshape(W, -1),
                   float(chan_step[1]),
                   math.sqrt(max(1.0 - float(chan_step[1]) ** 2, 0.0)),
                   chan_step[2]),
                  block_cols=block_cols, interpret=_interpret())
        out = _k.ota_round_stats(
            _f32(theta).reshape(W, -1), lam.re.reshape(W, -1),
            lam.im.reshape(W, -1), h.re.reshape(W, -1),
            h.im.reshape(W, -1), float(rho), **pk)
        y, p2, energy = out[:3]
        h_air = h if chan_step is None else Complex(
            out[3].reshape(shape), out[4].reshape(shape))
        return y.reshape(shape[1:]), p2.reshape(shape[1:]), energy, h_air
    h_air = h if chan_step is None else _chan_step_jnp(h, chan_step)
    signals = modulate(theta, lam, h_air if h_tx is None else h_tx, rho,
                       backend="jnp")
    energy = worker_energy(signals)
    hm = h_air
    if mask is not None:
        signals = _mask_planes(signals, mask)
        hm = _mask_planes(h_air, mask)
    rx_re = hm.re * signals.re - hm.im * signals.im
    sumh2 = cplx.abs2(hm)
    return (jnp.sum(rx_re, axis=0), jnp.sum(sumh2, axis=0), energy, h_air)


def _ota_round_streamed(theta: Array, lam: Complex, h: Complex, key: Array,
                        rho: float, ccfg: ChannelConfig, chunk: int, *,
                        power_control, mask, h_tx, chan_step, min_reduce_fn,
                        block_cols, backend, telemetry=None):
    """Worker-chunked (cohort-streamed) round: ``lax.scan`` over
    ``ceil(W/chunk)`` cohorts so peak signal-plane memory is O(chunk·D)
    instead of O(W·D) — W in the hundreds-to-thousands with scenario-driven
    participation masks.  The worker axis is zero-padded to a chunk
    multiple: an all-zero worker row contributes exactly zero to the
    superposition/pilot sums and zero energy (α = +inf never binds), so no
    padding mask is needed.  Chunked accumulation changes the summation
    grouping, so the result is tolerance-equal (not bitwise) to the
    monolithic pass — pinned in ``tests/test_fused_round.py``.
    """
    W = theta.shape[0]
    out_shape = theta.shape[1:]
    d = theta.size // W
    n_chunks = -(-W // chunk)
    W_pad = n_chunks * chunk

    def padw(x: Array) -> Array:
        flat = _f32(x).reshape(W, -1)
        return jnp.pad(flat, ((0, W_pad - W), (0, 0))).reshape(
            n_chunks, chunk, d)

    xs = {"theta": padw(theta),
          "lre": padw(lam.re), "lim": padw(lam.im),
          "hre": padw(h.re), "him": padw(h.im)}
    if mask is not None:
        xs["mask"] = jnp.pad(mask, (0, W_pad - W)).reshape(n_chunks, chunk)
    if h_tx is not None:
        xs["txre"], xs["txim"] = padw(h_tx.re), padw(h_tx.im)
    if chan_step is not None:
        w, rho_fad, redraw = chan_step
        xs["wre"], xs["wim"] = padw(w.re), padw(w.im)

    def body(carry, x):
        y, p2 = carry
        cs = None if chan_step is None else (
            Complex(x["wre"], x["wim"]), rho_fad, redraw)
        yi, p2i, ei, h_air_i = ota_round_stats(
            x["theta"], Complex(x["lre"], x["lim"]),
            Complex(x["hre"], x["him"]), rho,
            mask=x.get("mask"),
            h_tx=None if h_tx is None else Complex(x["txre"], x["txim"]),
            chan_step=cs, backend=backend, block_cols=block_cols)
        ys = (ei,) if chan_step is None else (ei, h_air_i)
        return (y + yi, p2 + p2i), ys

    zero = jnp.zeros((d,), jnp.float32)
    (y, p2), ys = jax.lax.scan(body, (zero, zero), xs)
    energy = ys[0].reshape(W_pad)[:W]
    if chan_step is None:
        h_air = h
    else:
        hs = ys[1]
        h_air = Complex(hs.re.reshape(W_pad, d)[:W].reshape(theta.shape),
                        hs.im.reshape(W_pad, d)[:W].reshape(theta.shape))
    if power_control:
        budget = ccfg.transmit_power * d
        inv_alpha = inv_alpha_from_energy(energy, budget,
                                          min_reduce_fn=min_reduce_fn,
                                          mask=mask)
    else:
        inv_alpha = jnp.asarray(1.0, jnp.float32)
    noise_re = matched_filter_noise_re(key, (d,), ccfg)
    Theta = demodulate(y, p2, noise_re, inv_alpha, backend=backend)
    if telemetry is not None:
        tel = round_telemetry(telemetry, y, noise_re, inv_alpha, energy,
                              mask, W)
        return Theta.reshape(out_shape), inv_alpha, h_air, tel
    return Theta.reshape(out_shape), inv_alpha, h_air


@layer("ota_receive")
def ota_round_fused(theta: Array, lam: Complex, h: Complex, key: Array,
                    rho: float, ccfg: ChannelConfig, *,
                    power_control: bool = True,
                    mask: Optional[Array] = None,
                    h_tx: Optional[Complex] = None,
                    chan_step=None,
                    min_reduce_fn: Optional[ReduceFn] = None,
                    worker_chunk: Optional[int] = None,
                    block_cols: Optional[int] = None,
                    backend: Optional[str] = None,
                    telemetry=None,
                    ) -> Tuple[Array, ...]:
    """The whole uplink round in one pass over the worker planes.

    Fused twin of :func:`ota_uplink`: modulate → power-scale → superpose
    (+ participation ``mask``, imperfect-CSI ``h_tx``) → AWGN → matched
    filter → demodulate, reading each ``(W, d)`` worker plane from HBM
    exactly once (:func:`ota_round_stats`); with same-round power control
    the only second pass is the O(d) worker-free demodulate epilogue, and
    with ``power_control=False`` the pallas backend collapses the round
    into a single kernel launch (``kernels/ota_round.ota_round_theta``).
    Results are bitwise identical to the composed path given equal inputs
    (the noise draw is :func:`matched_filter_noise_re` — the same bits
    ``receive`` samples).

    ``chan_step = (w, rho_fad, redraw)`` optionally fuses the AR(1) fading
    step into the same pass; ``worker_chunk`` (default: the
    ``REPRO_OTA_WORKER_CHUNK`` env knob) streams the workers through in
    cohorts of that size (O(chunk·D) peak signal memory, tolerance-equal).

    Returns ``(Theta, inv_alpha, h_air)`` — ``h_air`` is ``h`` or the
    stepped channel when ``chan_step`` is given.  With ``telemetry`` on
    (a live ``repro.obs.TelemetryConfig``) the return gains a fourth
    element, the ``obs/`` metric dict of :func:`round_telemetry`; the
    training math (Θ, inv_alpha, h_air) is unchanged — on the jnp
    backend bitwise so, pinned in ``tests/test_obs.py``.
    """
    from repro import obs as _obs
    tel = _obs.resolve(telemetry)
    backend = resolve_backend(backend)
    W = theta.shape[0]
    d = theta.size // W
    if worker_chunk is None:
        from repro import optflags
        worker_chunk = optflags.ota_worker_chunk()
    chunk = int(worker_chunk)
    if 0 < chunk < W:
        return _ota_round_streamed(
            theta, lam, h, key, rho, ccfg, chunk,
            power_control=power_control, mask=mask, h_tx=h_tx,
            chan_step=chan_step, min_reduce_fn=min_reduce_fn,
            block_cols=block_cols, backend=backend, telemetry=tel)
    out_shape = theta.shape[1:]
    if backend == "pallas" and not power_control and tel is None:
        # α known a priori -> the epilogue fuses into the SAME launch
        from repro.kernels import ota_round as _k
        noise_re = matched_filter_noise_re(key, (d,), ccfg)
        out = _k.ota_round_theta(
            _f32(theta).reshape(W, -1), lam.re.reshape(W, -1),
            lam.im.reshape(W, -1), h.re.reshape(W, -1),
            h.im.reshape(W, -1), noise_re, 1.0, float(rho),
            mask=None if mask is None else mask.reshape(W),
            htx=None if h_tx is None else
            (h_tx.re.reshape(W, -1), h_tx.im.reshape(W, -1)),
            chan=None if chan_step is None else
            (chan_step[0].re.reshape(W, -1), chan_step[0].im.reshape(W, -1),
             float(chan_step[1]),
             math.sqrt(max(1.0 - float(chan_step[1]) ** 2, 0.0)),
             chan_step[2]),
            block_cols=block_cols, interpret=_interpret())
        h_air = h if chan_step is None else Complex(
            out[1].reshape(theta.shape), out[2].reshape(theta.shape))
        return (out[0].reshape(out_shape), jnp.asarray(1.0, jnp.float32),
                h_air)
    y, p2, energy, h_air = ota_round_stats(
        theta, lam, h, rho, mask=mask, h_tx=h_tx, chan_step=chan_step,
        backend=backend, block_cols=block_cols)
    if power_control:
        budget = ccfg.transmit_power * d
        inv_alpha = inv_alpha_from_energy(energy, budget,
                                          min_reduce_fn=min_reduce_fn,
                                          mask=mask)
    else:
        inv_alpha = jnp.asarray(1.0, jnp.float32)
    noise_re = matched_filter_noise_re(key, out_shape, ccfg)
    Theta = demodulate(y, p2, noise_re, inv_alpha, backend=backend)
    if tel is not None:
        telm = round_telemetry(tel, y, noise_re, inv_alpha, energy, mask, W)
        return Theta, inv_alpha, h_air, telm
    return Theta, inv_alpha, h_air


def autotune_ota_round(W: int, d: int, ccfg: Optional[ChannelConfig] = None,
                       *, rho: float = 1.0,
                       block_cols_grid: Optional[Tuple] = None,
                       worker_chunks=(0, 8, 32),
                       iters: int = 10, backend: Optional[str] = None,
                       seed: int = 0) -> dict:
    """Small host-side sweep over the fused round's tiling knobs.

    Times :func:`ota_round_fused` (jit, median of ``iters`` after warmup)
    over a grid of ``(block_cols, worker_chunk)`` on random ``(W, d)``
    planes and returns ``{"best": {...}, "table": [...]}``.  ``block_cols``
    only reaches the pallas kernels, so on the jnp backend the sweep
    degenerates to worker_chunk alone (one block_cols row is kept).  The
    default grid is the kernels' own VMEM-sized tile (``None``) and the
    powers of two from 256 lanes below it, so the sweep never picks a
    narrower tile than the default unless it measured one faster.  The
    winning config maps 1:1 onto the env knobs
    (``REPRO_OTA_BLOCK_COLS`` / ``REPRO_OTA_WORKER_CHUNK``; ``None``
    leaves the first unset) and the ``FLConfig``/CLI fields.
    """
    import time

    if ccfg is None:
        ccfg = ChannelConfig(n_workers=W)
    key = jax.random.PRNGKey(seed)
    kt, kl, kh, kr = jax.random.split(key, 4)
    from repro.core.channel import rayleigh
    theta = jax.random.normal(kt, (W, d), jnp.float32)
    lam = rayleigh(kl, (W, d))
    h = rayleigh(kh, (W, d))

    if block_cols_grid is None:
        from repro.kernels.ota import vmem_block_cols
        widest = min(vmem_block_cols(W, 5), d)
        block_cols_grid = (None,) + tuple(
            256 << k for k in range(16) if 256 << k < widest)
    if resolve_backend(backend) != "pallas":
        block_cols_grid = block_cols_grid[:1]
    table = []
    for bc in block_cols_grid:
        for wc in worker_chunks:
            if wc and wc >= W:
                continue
            fn = jax.jit(_round_timing_fn(rho, ccfg, wc, bc, backend))
            jax.block_until_ready(fn(theta, lam, h, kr))
            ts = []
            for _ in range(iters):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(theta, lam, h, kr))
                ts.append(time.perf_counter() - t0)
            ts.sort()
            table.append({"block_cols": None if bc is None else int(bc),
                          "worker_chunk": int(wc),
                          "us": 1e6 * ts[len(ts) // 2]})
    best = min(table, key=lambda r: r["us"])
    return {"best": best, "table": table}


def _round_timing_fn(rho, ccfg, worker_chunk, block_cols, backend):
    """Closure helper for :func:`autotune_ota_round` (keeps the sweep's
    jitted round a hashable top-level callable per config)."""
    def fn(theta, lam, h, key):
        return ota_round_fused(theta, lam, h, key, rho, ccfg,
                               worker_chunk=worker_chunk,
                               block_cols=block_cols, backend=backend)[0]
    return fn


def autotune_ota_round_cached(W: int, d: int,
                              ccfg: Optional[ChannelConfig] = None, *,
                              cache_path: str, backend: Optional[str] = None,
                              **kw) -> dict:
    """:func:`autotune_ota_round` behind a JSON file cache.

    Results key on ``"{W}x{d}:{backend}"`` — one sweep per problem shape
    per machine, then every later launch (``launch/train.py
    --autotune-cache``) reads the winning tiling instead of re-measuring.
    The write is atomic (tmp + rename) so concurrent launchers can share
    one cache file; a corrupt/unreadable cache is treated as empty, never
    fatal.  The returned dict is the autotune result plus ``"cached":
    True`` on a hit.
    """
    import json
    import os

    bk = resolve_backend(backend)
    cache_key = f"{int(W)}x{int(d)}:{bk}"
    cache = {}
    if os.path.exists(cache_path):
        try:
            with open(cache_path) as f:
                cache = json.load(f)
            if not isinstance(cache, dict):
                cache = {}
        except (OSError, ValueError):
            cache = {}
    if cache_key in cache:
        return dict(cache[cache_key], cached=True)
    res = autotune_ota_round(W, d, ccfg, backend=backend, **kw)
    cache[cache_key] = res
    tmp = f"{cache_path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(cache, f, indent=2, sort_keys=True)
        f.write("\n")
    os.replace(tmp, cache_path)
    return dict(res, cached=False)
