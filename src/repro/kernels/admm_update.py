"""Pallas TPU kernels for the fused ADMM state updates (Eqs. 10–11).

One HBM pass over (λ, h, θ, Θ) instead of the ~8 elementwise HLOs of the
naive lowering; the flip-rule kernel additionally folds the |h|² reciprocal
into the same pass.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ota import (LANE, _block_cols, _block_rows, _col_grid,
                               _grid_spec, _pad_2d, _rows_for)

Array = jax.Array


def _dual_kernel(*refs, rho: float, has_noise: bool):
    lre_ref, lim_ref, hre_ref, him_ref, th_ref, Th_ref = refs[:6]
    ore_ref, oim_ref = refs[-2:]
    r = th_ref[...].astype(jnp.float32) - Th_ref[...]
    re = hre_ref[...] * r
    if has_noise:
        re = re - refs[6][...]
    ore_ref[...] = lre_ref[...] + rho * re
    oim_ref[...] = lim_ref[...] + rho * him_ref[...] * r


def _flip_kernel(g_ref, th_ref, Th_ref, hre_ref, him_ref,
                 ore_ref, oim_ref, *, rho: float):
    hre = hre_ref[...]
    him = him_ref[...]
    h2 = hre * hre + him * him
    t = -(g_ref[...].astype(jnp.float32)
          + rho * h2 * (th_ref[...].astype(jnp.float32)
                        - Th_ref[...].astype(jnp.float32)))
    s = t / jnp.maximum(h2, 1e-12)
    ore_ref[...] = hre * s
    oim_ref[...] = him * s


def admm_dual_update(lam_re: Array, lam_im: Array, h_re: Array, h_im: Array,
                     theta: Array, Theta: Array, rho: float,
                     noise_re: Optional[Array] = None,
                     *, block_cols: Optional[int] = None,
                     interpret: bool = False) -> Tuple[Array, Array]:
    """Fused λ' = λ + ρ·h·(θ−Θ) − ρ·Re{z}.

    λ, h, θ and the optional downlink noise ``noise_re`` are ``(W, d)``
    worker planes (or flat ``(d,)``: one worker); Θ is ``(d,)``.  The grid
    walks column blocks of the planes as they are laid out, and each block
    reads the same Θ columns for every worker, so nothing is reshaped,
    broadcast or cast in HBM (θ is read in its own dtype).  λ' is written
    over λ (``input_output_aliases``): a donated λ is updated in place.
    """
    shape = lam_re.shape
    W = shape[0] if len(shape) == 2 else 1
    n = lam_re.size // W
    has_noise = noise_re is not None
    block_cols = _block_cols(block_cols, W, 8 + has_noise, n)

    def plane(x: Array, rows: int = W) -> Array:
        return x.astype(jnp.float32).reshape(rows, n)

    args = [plane(lam_re), plane(lam_im), plane(h_re), plane(h_im),
            theta.reshape(W, n), plane(Theta, 1)]
    if has_noise:
        args.append(plane(noise_re))
    wspec = pl.BlockSpec((W, block_cols), lambda i: (0, i))
    rspec = pl.BlockSpec((1, block_cols), lambda i: (0, i))
    ore, oim = pl.pallas_call(
        functools.partial(_dual_kernel, rho=float(rho), has_noise=has_noise),
        grid=_col_grid("admm_dual_update", W, n, block_cols, args),
        in_specs=[wspec] * 5 + [rspec] + [wspec] * has_noise,
        out_specs=[wspec, wspec],
        out_shape=[jax.ShapeDtypeStruct((W, n), jnp.float32)] * 2,
        input_output_aliases={0: 0, 1: 1},
        interpret=interpret,
    )(*args)
    return ore.reshape(shape), oim.reshape(shape)


def admm_flip_lambda(grad: Array, theta: Array, Theta_prev: Array,
                     h_re: Array, h_im: Array, rho: float,
                     *, block_rows: Optional[int] = None,
                     interpret: bool = False) -> Tuple[Array, Array]:
    """Fused flip rule: λ = t·h/|h|², t = −(∂f + ρ|h|²(θ−Θ))."""
    block_rows = _block_rows(block_rows, 7)
    n = theta.size
    rows = _rows_for(n)
    args = [_pad_2d(a.astype(jnp.float32), rows)
            for a in (grad, theta, Theta_prev, h_re, h_im)]
    grid, in_specs, out_spec = _grid_spec(5, rows, block_rows)
    ore, oim = pl.pallas_call(
        functools.partial(_flip_kernel, rho=float(rho)),
        grid=grid,
        in_specs=in_specs,
        out_specs=[out_spec, out_spec],
        out_shape=[jax.ShapeDtypeStruct((rows, LANE), jnp.float32)] * 2,
        interpret=interpret,
    )(*args)
    return ore.reshape(-1)[:n], oim.reshape(-1)[:n]
