"""Fused population-scale phy kernel: the WHOLE per-slot physics update —
AR(1) small-scale fading, random-waypoint mobility, on-arrival shadowing
redraw, and log-distance path gain — for an N-worker population in ONE
row-blocked launch over flat ``(N,)`` planes.

Motivation (ROADMAP item 2, the "millions of users" axis): with
N = 10⁵–10⁶ workers the per-function jnp chain in ``Scenario.step``
(``fading.correlated_step`` → ``geometry.waypoint_step`` →
``geometry.worker_gains``) costs one dispatch *and* one HBM round-trip per
plane per function.  This kernel reads each of the 12 input planes exactly
once and writes the 8 output planes in the same pass.

Division of labour (the ``ota_round`` pattern): everything *random* is
pre-drawn OUTSIDE the kernel by ``repro.phy.population.population_step``
with the exact keys the composed chain uses (Rayleigh innovations, fresh
waypoints, fresh shadowing), so the kernel is purely elementwise and the
jnp oracle is bitwise the composed chain by construction.  Kernel-vs-oracle
parity is tolerance-level (≤1e-5), pinned in ``tests/test_population.py``.

Layout matches the rest of the kernel set (``kernels/ota.py``): flat f32
planes reshaped to (rows, 1024) 8×128-aligned VMEM tiles, row-blocked grid
controlled by the same ``REPRO_OTA_BLOCK_ROWS`` knob, runtime scalars in
SMEM.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# one tiling scheme for the whole OTA/phy kernel set — a layout change in
# kernels/ota.py (lane width, padding rule) must reach this kernel too
from repro.kernels.ota import (LANE, _block_rows, _grid_spec, _pad_2d,
                               _rows_for)
from repro.kernels.phy_channel import _scalar_spec

Array = jax.Array


def _population_step_kernel(p_ref,
                            hre_ref, him_ref, wre_ref, wim_ref,
                            px_ref, py_ref, dx_ref, dy_ref,
                            fx_ref, fy_ref, sh_ref, sf_ref,
                            ohre_ref, ohim_ref, opx_ref, opy_ref,
                            odx_ref, ody_ref, osh_ref, og_ref):
    rho, scale, redraw = p_ref[0], p_ref[1], p_ref[2]
    step, d0, dnorm = p_ref[3], p_ref[4], p_ref[5]
    pexp, sh_redraw = p_ref[6], p_ref[7]

    # --- AR(1) fading at coherence boundaries (== phy_channel.fading_step)
    upd = redraw != 0.0
    ohre_ref[...] = jnp.where(upd, rho * hre_ref[...] + scale * wre_ref[...],
                              hre_ref[...])
    ohim_ref[...] = jnp.where(upd, rho * him_ref[...] + scale * wim_ref[...],
                              him_ref[...])

    # --- random-waypoint move (== geometry._advance, x/y planes split)
    ddx = dx_ref[...] - px_ref[...]
    ddy = dy_ref[...] - py_ref[...]
    dist = jnp.sqrt(ddx * ddx + ddy * ddy)
    arrived = dist <= step
    denom = jnp.maximum(dist, 1e-9)
    px = jnp.where(arrived, dx_ref[...], px_ref[...] + step * (ddx / denom))
    py = jnp.where(arrived, dy_ref[...], py_ref[...] + step * (ddy / denom))
    opx_ref[...] = px
    opy_ref[...] = py
    odx_ref[...] = jnp.where(arrived, fx_ref[...], dx_ref[...])
    ody_ref[...] = jnp.where(arrived, fy_ref[...], dy_ref[...])

    # --- shadowing redraw on arrival (== geometry.waypoint_shadow_step)
    sh = jnp.where((sh_redraw != 0.0) & arrived, sf_ref[...], sh_ref[...])
    osh_ref[...] = sh

    # --- path gain at the NEW position (== geometry.worker_gains);
    # exp/log instead of pow for Mosaic-safe float exponents
    d = jnp.maximum(jnp.sqrt(px * px + py * py), d0)
    og_ref[...] = jnp.exp(pexp * jnp.log(dnorm / d)) * sh


def population_step(h_re: Array, h_im: Array, w_re: Array, w_im: Array,
                    pos_x: Array, pos_y: Array, dest_x: Array, dest_y: Array,
                    fresh_x: Array, fresh_y: Array,
                    shadow: Array, shadow_fresh: Array,
                    rho: float, scale: float, redraw: Array | bool,
                    step: float, ref_d: float, norm_d: float, pexp: float,
                    shadow_redraw: float, *,
                    block_rows: Optional[int] = None,
                    interpret: bool = False) -> Tuple[Array, ...]:
    """One fused phy slot over flat ``(N,)`` planes.

    Inputs: fading planes + pre-drawn Rayleigh innovations, position /
    destination / fresh-waypoint x-y planes, shadowing + pre-drawn fresh
    shadowing.  Scalars: AR(1) ``rho``/innovation ``scale``/``redraw``
    gate, waypoint ``step`` = speed·slot, path-loss ``ref_d``/``norm_d``/
    ``pexp``, and the ``shadow_redraw`` enable flag.

    Returns ``(h_re', h_im', pos_x', pos_y', dest_x', dest_y', shadow',
    gain)``, all ``(N,)`` f32.  ``block_rows`` defaults to the
    ``REPRO_OTA_BLOCK_ROWS`` knob (autotunable via
    ``phy.population.autotune_population_step``).
    """
    block_rows = _block_rows(block_rows, 20)
    n = h_re.size
    rows = _rows_for(n)
    planes = [_pad_2d(a.astype(jnp.float32), rows)
              for a in (h_re, h_im, w_re, w_im, pos_x, pos_y, dest_x, dest_y,
                        fresh_x, fresh_y, shadow, shadow_fresh)]
    params = jnp.stack([jnp.asarray(rho, jnp.float32),
                        jnp.asarray(scale, jnp.float32),
                        jnp.asarray(redraw, jnp.float32),
                        jnp.asarray(step, jnp.float32),
                        jnp.asarray(ref_d, jnp.float32),
                        jnp.asarray(norm_d, jnp.float32),
                        jnp.asarray(pexp, jnp.float32),
                        jnp.asarray(shadow_redraw, jnp.float32)])
    grid, _, spec = _grid_spec(0, rows, block_rows)
    outs = pl.pallas_call(
        _population_step_kernel, grid=grid,
        in_specs=[_scalar_spec(8)] + [spec] * 12,
        out_specs=[spec] * 8,
        out_shape=[jax.ShapeDtypeStruct((rows, LANE), jnp.float32)] * 8,
        interpret=interpret)(params, *planes)
    return tuple(o.reshape(-1)[:n] for o in outs)
