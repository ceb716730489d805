"""Wireless channel substrate: Rayleigh block fading + AWGN + matched filter.

The paper's physical layer, reproduced as an explicit simulated layer:

* **Rayleigh fading** ``h_{n,i} ~ CN(0, 1)`` per (worker n, subcarrier i),
  redrawn every ``coherence_iters`` iterations (paper: 10) — "block fading".
* **AWGN** at the receiver with PSD ``N0``; the matched filter (correlator
  receiver, Appendix B Eq. 23) integrates over ``T`` seconds, reducing the
  effective noise variance from ``N0`` to ``N0 / T``.
* **SNR** defined as the paper's Appendix H: ``SNR = P / (N0 * W_hz)`` — with
  ``N0*W_hz`` fixed, sweeping SNR sweeps transmit power ``P``.

Everything is functional: a :class:`ChannelState` pytree + pure transition
functions, so channel realisations are reproducible and shard_map-safe (the
worker axis of ``h`` is shardable over the mesh ``data`` axis).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import cplx
from repro.core.cplx import Complex
from repro.obs.profiling import layer

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class ChannelConfig:
    """Static description of the simulated wireless link.

    This is the *block-fading* substrate: i.i.d. Rayleigh redraws every
    ``coherence_iters`` rounds.  It is exactly the ``rho = 0`` special case
    of the Gauss–Markov correlated-fading recurrence in ``repro.phy``
    (``h' = rho·h + sqrt(1−rho²)·w`` applied at coherence boundaries) —
    the ``"block-fading"`` scenario preset reproduces this module's
    ``init_channel``/``step_channel`` draws bit-for-bit, and richer
    dynamics (Doppler correlation, geometry, imperfect CSI, deep-fade
    truncation) are scenario presets layered on top, not channel flags
    here.
    """

    n_workers: int
    n_subcarriers: int = 4096
    #: iterations per coherence block (paper Sec. 5: 10)
    coherence_iters: int = 10
    #: average SNR in dB (paper default: 40 dB)
    snr_db: float = 40.0
    #: subcarrier bandwidth in Hz (LTE numerology, Appendix H)
    subcarrier_hz: float = 15e3
    #: noise power spectral density W/Hz (paper Sec. 5 scalability: 1e-9)
    noise_psd: float = 1e-9
    #: matched-filter integration time T in seconds (slot length, 1 ms)
    slot_seconds: float = 1e-3
    #: uplink AWGN on/off (noise-free channels for the convergence theory)
    noisy: bool = True
    #: model downlink as digital (paper Sec. 5 default) or analog
    analog_downlink: bool = False

    @property
    def transmit_power(self) -> float:
        """P implied by the SNR definition SNR = P/(N0*W)."""
        return (10.0 ** (self.snr_db / 10.0)) * self.noise_psd * self.subcarrier_hz

    @property
    def noise_var_matched(self) -> float:
        """Post-matched-filter complex noise variance N0/T (Eq. 23)."""
        return self.noise_psd / self.slot_seconds


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class ChannelBlock:
    """One block-fading realisation.

    Attributes:
      h: fading coefficients, shape (n_workers, n_coeffs) as Complex planes.
      h_prev: the previous block's coefficients (for the time-varying flip rule).
      changed: bool mask — True where ``h != h_prev`` this iteration. Scalar
        per-(worker, coeff) so elementwise update rules can mix.
      age: iterations since this block was drawn.
    """

    h: Complex
    h_prev: Complex
    changed: Array
    age: Array  # int32 scalar

    def tree_flatten(self):
        return ((self.h, self.h_prev, self.changed, self.age), None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def rayleigh(key: Array, shape: Tuple[int, ...], dtype=jnp.float32) -> Complex:
    """CN(0, 1): re, im ~ N(0, 1/2)."""
    kr, ki = jax.random.split(key)
    s = jnp.sqrt(jnp.asarray(0.5, dtype))
    return Complex(
        jax.random.normal(kr, shape, dtype) * s,
        jax.random.normal(ki, shape, dtype) * s,
    )


def awgn(key: Array, shape: Tuple[int, ...], var: float, dtype=jnp.float32) -> Complex:
    """CN(0, var): matched-filter-reduced receiver noise."""
    kr, ki = jax.random.split(key)
    s = jnp.sqrt(jnp.asarray(var / 2.0, dtype))
    return Complex(
        jax.random.normal(kr, shape, dtype) * s,
        jax.random.normal(ki, shape, dtype) * s,
    )


def init_channel(key: Array, cfg: ChannelConfig, n_coeffs: Optional[int] = None) -> ChannelBlock:
    """Draw the first fading block. ``n_coeffs`` defaults to n_subcarriers."""
    n = cfg.n_subcarriers if n_coeffs is None else n_coeffs
    h = rayleigh(key, (cfg.n_workers, n))
    return ChannelBlock(
        h=h,
        h_prev=h,
        changed=jnp.zeros((cfg.n_workers, n), jnp.bool_),
        age=jnp.zeros((), jnp.int32),
    )


def step_channel(key: Array, blk: ChannelBlock, cfg: ChannelConfig) -> ChannelBlock:
    """Advance one iteration: redraw h every ``coherence_iters`` iterations.

    Uses lax.cond-free ``where`` so it stays trivially shardable.
    """
    age = blk.age + 1
    redraw = age >= cfg.coherence_iters
    fresh = rayleigh(key, blk.h.re.shape, blk.h.re.dtype)
    h_new = cplx.cwhere(redraw, fresh, blk.h)
    changed = jnp.broadcast_to(redraw, blk.h.re.shape)
    return ChannelBlock(
        h=h_new,
        h_prev=blk.h,
        changed=changed,
        age=jnp.where(redraw, jnp.zeros((), jnp.int32), age),
    )


@layer("ota_noise")
def matched_filter_noise(key: Array, shape: Tuple[int, ...], cfg: ChannelConfig) -> Complex:
    """Receiver noise after the correlator (Eq. 23): CN(0, N0/T), or zero."""
    if not cfg.noisy:
        return cplx.czero(shape)
    return awgn(key, shape, cfg.noise_var_matched)


def shannon_rate(h: Complex, cfg: ChannelConfig) -> Array:
    """Per-subcarrier achievable rate (bits/slot) for the *digital* baseline.

    Appendix H: R = W log2(1 + P|h|^2/(N0 W)) bits/s; one slot = slot_seconds.
    """
    snr_lin = cfg.transmit_power * cplx.abs2(h) / (cfg.noise_psd * cfg.subcarrier_hz)
    bits_per_sec = cfg.subcarrier_hz * jnp.log2(1.0 + snr_lin)
    return bits_per_sec * cfg.slot_seconds
