"""Jit'd public wrappers for the Pallas kernels.

Off the TPU (the CPU test runs) the kernels execute in ``interpret=True``
mode — the kernel body runs as traced JAX ops, validating the tiling/index
logic.  On a TPU backend the same calls compile to Mosaic.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core.transport import _interpret
from repro.kernels import admm_update as _admm
from repro.kernels import linear_scan as _scan
from repro.kernels import ota as _ota

Array = jax.Array


@functools.partial(jax.jit, static_argnames=("rho",))
def ota_modulate(theta: Array, lam_re: Array, lam_im: Array, h_re: Array,
                 h_im: Array, rho: float) -> Tuple[Array, Array]:
    return _ota.ota_modulate(theta, lam_re, lam_im, h_re, h_im, rho,
                             interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("inv_alpha",))
def ota_demodulate(y_re: Array, noise_re: Array, sumh2: Array,
                   inv_alpha: float) -> Array:
    return _ota.ota_demodulate(y_re, noise_re, sumh2, inv_alpha,
                               interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("rho",))
def admm_dual_update(lam_re: Array, lam_im: Array, h_re: Array, h_im: Array,
                     theta: Array, Theta: Array, rho: float,
                     noise_re: Array) -> Tuple[Array, Array]:
    return _admm.admm_dual_update(lam_re, lam_im, h_re, h_im, theta, Theta,
                                  rho, noise_re, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("rho",))
def admm_flip_lambda(grad: Array, theta: Array, Theta_prev: Array,
                     h_re: Array, h_im: Array, rho: float
                     ) -> Tuple[Array, Array]:
    return _admm.admm_flip_lambda(grad, theta, Theta_prev, h_re, h_im, rho,
                                  interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("block_s", "block_d"))
def linear_scan(a: Array, b: Array, block_s: int = _scan.DEFAULT_BS,
                block_d: int = _scan.DEFAULT_BD) -> Array:
    return _scan.linear_scan(a, b, block_s=block_s, block_d=block_d,
                             interpret=_interpret())


@functools.partial(jax.jit,
                   static_argnames=("causal", "block_q", "block_k",
                                    "interpret"))
def flash_attention(q: Array, k: Array, v: Array, causal: bool = True,
                    block_q: int = 256, block_k: int = 256,
                    interpret: bool | None = None) -> Array:
    """Differentiable (custom_vjp) flash attention; ``interpret=None``
    auto-selects interpret mode off-TPU.  Block sizes apply to the forward
    and both backward kernels."""
    from repro.kernels import flash_attention as _fa
    interp = _interpret() if interpret is None else interpret
    return _fa.flash_attention(q, k, v, causal=causal, block_q=block_q,
                               block_k=block_k, interpret=interp)
