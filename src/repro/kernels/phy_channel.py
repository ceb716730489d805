"""Pallas TPU kernels for the ``repro.phy`` wireless scenario engine.

Two per-round primitives run at packed ``(W, D)`` scale every round once a
scenario is active, so both get the same one-HBM-pass treatment as the OTA
transport kernels (``kernels/ota.py``):

* :func:`fading_step` — the Gauss–Markov (AR(1)) small-scale fading
  recurrence ``h' = rho·h + sqrt(1−rho²)·w`` applied at coherence
  boundaries (``redraw`` gate), fused over the four input planes
  (h_re, h_im, w_re, w_im) in a single kernel instead of the ~6 elementwise
  HLOs XLA would schedule (2 muls + 2 adds + 2 selects per plane pair).

* :func:`ota_receive_masked` — the participation-aware receive chain:
  masked workers are zeroed *inside* the kernel (``where``, so NaN/Inf
  garbage in a dropped worker's planes can never leak into the
  superposition), then superpose → matched-filter → demodulate exactly like
  ``kernels/ota.ota_receive``.

Layout matches the rest of the kernel set (``kernels/ota.py``): the fading
step's flat f32 planes reshaped to (rows, 1024) 8×128-aligned VMEM tiles,
the receive's ``(W, d)`` planes walked in VMEM-sized column tiles; runtime
scalars ride in SMEM.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# one tiling scheme for the whole OTA/phy kernel set — a layout change in
# kernels/ota.py (lane width, padding rule) must reach these kernels too
from repro.kernels.ota import (LANE, _block_cols, _block_rows, _col_grid,
                               _grid_spec, _pad_2d, _rows_for)

Array = jax.Array


def _scalar_spec(n: int = 1):
    """(n,) runtime scalar operand, kept in SMEM on TPU."""
    return pl.BlockSpec((n,), lambda i: (0,), memory_space=pltpu.SMEM)


def _fading_step_kernel(p_ref, hre_ref, him_ref, wre_ref, wim_ref,
                        ore_ref, oim_ref):
    rho, scale, redraw = p_ref[0], p_ref[1], p_ref[2]
    upd = redraw != 0.0
    ore_ref[...] = jnp.where(upd, rho * hre_ref[...] + scale * wre_ref[...],
                             hre_ref[...])
    oim_ref[...] = jnp.where(upd, rho * him_ref[...] + scale * wim_ref[...],
                             him_ref[...])


def fading_step(h_re: Array, h_im: Array, w_re: Array, w_im: Array,
                rho: float, scale: float, redraw: Array | bool,
                *, block_rows: Optional[int] = None,
                interpret: bool = False) -> Tuple[Array, Array]:
    """Fused AR(1) fading update over flat planes.

    ``h' = rho·h + scale·w`` where ``redraw`` gates the update (False keeps
    the block — the inter-boundary hold of block fading).  ``rho``/``scale``
    are trace-time floats; ``redraw`` is a traced bool scalar (the coherence
    counter lives in jit-compiled round loops).
    """
    block_rows = _block_rows(block_rows, 6)
    n = h_re.size
    rows = _rows_for(n)
    args = [_pad_2d(a.astype(jnp.float32), rows)
            for a in (h_re, h_im, w_re, w_im)]
    params = jnp.stack([
        jnp.asarray(rho, jnp.float32), jnp.asarray(scale, jnp.float32),
        jnp.asarray(redraw, jnp.float32)])
    grid, _, spec = _grid_spec(0, rows, block_rows)
    ore, oim = pl.pallas_call(
        _fading_step_kernel,
        grid=grid,
        in_specs=[_scalar_spec(3)] + [spec] * 4,
        out_specs=[spec, spec],
        out_shape=[jax.ShapeDtypeStruct((rows, LANE), jnp.float32)] * 2,
        interpret=interpret,
    )(params, *args)
    return ore.reshape(-1)[:n], oim.reshape(-1)[:n]


def _receive_masked_kernel(ia_ref, m_ref, sre_ref, sim_ref, hre_ref, him_ref,
                           nre_ref, out_ref):
    active = m_ref[...] != 0.0
    hre = jnp.where(active, hre_ref[...], 0.0)
    him = jnp.where(active, him_ref[...], 0.0)
    sre = jnp.where(active, sre_ref[...], 0.0)
    sim = jnp.where(active, sim_ref[...], 0.0)
    rx_re = hre * sre - him * sim                     # Re{h ⊙ s}, active only
    y = jnp.sum(rx_re, axis=0, keepdims=True)         # masked superposition
    p2 = jnp.sum(hre * hre + him * him, axis=0, keepdims=True)
    y = y + nre_ref[...] * ia_ref[0]                  # matched-filter noise/α
    out_ref[...] = y / jnp.maximum(p2, 1e-12)         # Θ over active pilots


def ota_receive_masked(s_re: Array, s_im: Array, h_re: Array, h_im: Array,
                       mask: Array, noise_re: Array,
                       inv_alpha: Array | float,
                       *, block_cols: Optional[int] = None,
                       interpret: bool = False) -> Array:
    """Participation-aware fused receive chain.

    Θ = (Re{Σ_{n: mask_n} h_n⊙s_n} + z·α⁻¹) / max(Σ_{n: mask_n} |h_n|², eps).

    ``mask``: (W,) bool/0-1 — a masked worker contributes exactly zero to
    both the superposition and the pilot aggregate (its planes are never
    read into the sums, so non-finite values there are harmless).  s/h:
    (W, d) planes; noise_re: (d,); inv_alpha: traced scalar.  Returns (d,).

    Like ``kernels/ota.ota_receive``, ``d`` may be the shard-local width
    ``d_local`` inside ``shard_map`` on a model-parallel mesh: the grid then
    spans one shard's columns, and the (W,)-replicated mask rides into every
    shard's launch unchanged — scenario participation is worker-level, so
    it is independent of how the packed axis is split.
    """
    W, n = s_re.shape
    block_cols = _block_cols(block_cols, W, 5, n)
    args = [a.astype(jnp.float32) for a in (s_re, s_im, h_re, h_im)]
    m = jnp.broadcast_to(mask.astype(jnp.float32)[:, None], (W, block_cols))
    nz = noise_re.astype(jnp.float32).reshape(1, n)
    ia = jnp.asarray(inv_alpha, jnp.float32).reshape(1)
    wspec = pl.BlockSpec((W, block_cols), lambda i: (0, i))
    mspec = pl.BlockSpec((W, block_cols), lambda i: (0, 0))
    rspec = pl.BlockSpec((1, block_cols), lambda i: (0, i))
    out = pl.pallas_call(
        _receive_masked_kernel,
        grid=_col_grid("ota_receive_masked", W, n, block_cols, args + [nz]),
        in_specs=[_scalar_spec(1), mspec] + [wspec] * 4 + [rspec],
        out_specs=rspec,
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.float32),
        interpret=interpret,
    )(ia, m, *args, nz)
    return out.reshape(-1)
