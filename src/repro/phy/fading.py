"""Time-correlated small-scale fading: the Gauss–Markov (AR(1)) process.

The repo's historical channel (``core.channel``) redraws an i.i.d. Rayleigh
block every ``coherence_iters`` rounds — a zeroth-order model of mobility.
Real channels decorrelate *continuously* with Doppler: under Jakes'
isotropic-scattering model the complex-gain autocorrelation after a delay
``T`` is ``J0(2·pi·f_d·T)`` (Bessel of the first kind), which the standard
first-order Gauss–Markov approximation turns into the recurrence

    h_{k+1} = rho · h_k + sqrt(1 − rho²) · w_k,      w_k ~ CN(0, 1)

with ``rho = J0(2·pi·f_d·T_update)``.  The recurrence preserves the CN(0,1)
stationary distribution (unit average power) and has per-step correlation
exactly ``rho``; ``rho = 0`` degenerates to an i.i.d. redraw — the existing
block-fading model is literally the ``rho=0`` special case of this step
applied at coherence boundaries (bit-parity pinned in ``tests/test_phy.py``).

All steps are pure ``(key, h) -> h`` functions over packed ``(W, D)``
:class:`~repro.core.cplx.Complex` planes, scan/jit/shard_map-safe, with a
fused Pallas kernel backend (``kernels/phy_channel.fading_step``: one HBM
pass per boundary round) behind the same ``backend=`` dispatch as the
transport.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.channel import gated_redraw, rayleigh
from repro.core.cplx import Complex
from repro.core.transport import _interpret, resolve_backend

Array = jax.Array


def bessel_j0(x: float) -> float:
    """J0(x) for host-side floats (Abramowitz & Stegun 9.4.1 / 9.4.3).

    Polynomial approximations, |error| < 5e-8 — plenty for a correlation
    coefficient; avoids a scipy dependency (the container has none).
    """
    ax = abs(float(x))
    if ax <= 3.0:
        t = (ax / 3.0) ** 2
        return (1.0 + t * (-2.2499997 + t * (1.2656208 + t * (-0.3163866
                + t * (0.0444479 + t * (-0.0039444 + t * 0.0002100))))))
    t = 3.0 / ax
    f0 = (0.79788456 + t * (-0.00000077 + t * (-0.00552740 + t * (-0.00009512
          + t * (0.00137237 + t * (-0.00072805 + t * 0.00014476))))))
    th0 = (ax - 0.78539816 + t * (-0.04166397 + t * (-0.00003954
           + t * (0.00262573 + t * (-0.00054125 + t * (-0.00029333
           + t * 0.00013558))))))
    return f0 * math.cos(th0) / math.sqrt(ax)


def doppler_rho(doppler_hz: float, update_seconds: float) -> float:
    """Jakes-model AR(1) coefficient ``rho = J0(2·pi·f_d·T)``.

    ``T`` is the time between fading updates (slot length × iterations per
    coherence block).  Clamped to [0, 1]: past the first Bessel zero the
    channel is effectively decorrelated and the AR(1) approximation returns
    an i.i.d. redraw rather than an unphysical negative correlation.
    """
    rho = bessel_j0(2.0 * math.pi * float(doppler_hz) * float(update_seconds))
    return min(max(rho, 0.0), 1.0)


def innovation_scale(rho: float) -> float:
    """sqrt(1 − rho²): keeps the recurrence CN(0,1)-stationary."""
    return math.sqrt(max(1.0 - float(rho) ** 2, 0.0))


def gauss_markov_step(key: Array, h: Complex, rho: float,
                      redraw: Array | bool = True, *,
                      backend: Optional[str] = None) -> Complex:
    """One AR(1) fading update, gated by ``redraw`` (coherence boundary).

    ``rho`` is a trace-time float.  The innovations draw and the mix run
    only on the boundary (:func:`~repro.core.channel.gated_redraw`); off it
    ``h`` passes through.  ``rho == 0.0`` returns the draw itself, so the
    legacy block-fading channel is reproduced bitwise, not merely to
    rounding.
    """
    pallas = resolve_backend(backend) == "pallas"

    def mix(key: Array, h: Complex) -> Complex:
        w = rayleigh(key, h.re.shape, h.re.dtype)
        if pallas:
            from repro.kernels import phy_channel as _k
            shape = h.re.shape
            ore, oim = _k.fading_step(
                h.re.reshape(-1), h.im.reshape(-1),
                w.re.reshape(-1), w.im.reshape(-1),
                float(rho), innovation_scale(rho), interpret=_interpret())
            return Complex(ore.reshape(shape), oim.reshape(shape))
        if float(rho) == 0.0:
            return w
        s = innovation_scale(rho)
        return Complex(rho * h.re + s * w.re, rho * h.im + s * w.im)

    return gated_redraw(redraw, mix, key, h)


def correlated_step(key: Array, h: Complex, age: Array, rho: float,
                    coherence_iters: int, *,
                    backend: Optional[str] = None
                    ) -> Tuple[Complex, Array, Array]:
    """Advance one round: AR(1)-mix the fading at coherence boundaries.

    Returns ``(h_new, age_new, redraw)``.  With ``rho=0`` this IS the legacy
    ``core.tree_ota.step_channel_packed``: on a boundary the full ``key``
    feeds one :func:`~repro.core.channel.rayleigh` draw, and off it no draw
    is made at all; both gate through
    :func:`~repro.core.channel.gated_redraw`.
    """
    age = age + 1
    redraw = age >= coherence_iters
    h_new = gauss_markov_step(key, h, rho, redraw, backend=backend)
    age_new = jnp.where(redraw, jnp.zeros((), jnp.int32), age)
    return h_new, age_new, redraw
