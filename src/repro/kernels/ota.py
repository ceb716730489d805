"""Pallas TPU kernels for the over-the-air signal path.

At LLM scale the per-round modulate/demodulate pass touches every parameter
byte — at 671B that is the dominant *memory* hot spot of the paper's
protocol (the MXU does nothing here; the VPU and HBM bandwidth are the
resources).  Fusing the complex arithmetic into one pass halves the HBM
traffic versus the 4–5 elementwise HLOs XLA would otherwise schedule
(conj, mul, add, div, select).

Layout: the flat elementwise kernels reshape their f32 planes to
(rows, 1024) = 8×128-aligned VMEM tiles.  The worker-grid kernels (the
receive here, ``ota_round``, ``admm_update.admm_dual_update``,
``phy_channel.ota_receive_masked``) keep their ``(W, d)`` planes as laid
out and walk the ``d`` axis in column tiles as wide as the VMEM budget
allows (:func:`_block_cols`), tens of thousands of lanes at small W: each
grid step has a fixed cost, so a narrow tile spends the launch on steps.
The last tile may overhang ``d``; no plane is padded in HBM.  Complex
values travel as separate re/im planes (no complex dtype on the TPU VPU).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

LANE = 1024               # 8 sublanes x 128 lanes
DEFAULT_BLOCK_ROWS = 256  # 256*1024*4B = 1 MiB per f32 operand tile

#: VMEM the kernels may fill with their pipelined tiles: under the 16 MiB
#: scoped-VMEM default of v5e (the smallest of the TPU generations), with
#: room left for the compiler's own scratch
VMEM_TILE_BUDGET = 12 * 2 ** 20


def _block_rows(block_rows: Optional[int], n_planes: int) -> int:
    """Row tile of a flat elementwise kernel: explicit arg, else
    ``REPRO_OTA_BLOCK_ROWS``, else the most rows (a multiple of 8, at most
    :data:`DEFAULT_BLOCK_ROWS`) whose double-buffered ``(rows, LANE)``
    tiles of the launch's ``n_planes`` operands and results fit
    :data:`VMEM_TILE_BUDGET`."""
    if block_rows is not None:
        return block_rows
    from repro import optflags
    env = optflags.ota_block_rows()
    if env is not None:
        return env
    per_row = 2 * n_planes * LANE * 4
    return max(8, min(DEFAULT_BLOCK_ROWS,
                      VMEM_TILE_BUDGET // per_row // 8 * 8))


def vmem_block_cols(n_workers: int, n_planes: int) -> int:
    """The widest column tile, a multiple of 128 lanes, whose working set
    fits :data:`VMEM_TILE_BUDGET`.

    ``n_planes`` counts the ``(W, block_cols)`` operands and results of the
    launch.  Each is double-buffered by the pipeline, and the body holds
    about as many ``(W, block_cols)`` temporaries again, so the working set
    is ``3 · n_planes · W₈ · block_cols · 4`` bytes, ``W₈`` being ``W``
    rounded up to the 8 sublanes of a vreg.  No lane cap: the budget alone
    binds (16,384–32,768 lanes at W ≤ 8, 768 at W = 256).
    """
    w8 = -(-n_workers // 8) * 8
    per_col = 3 * n_planes * w8 * 4
    return max(128, VMEM_TILE_BUDGET // per_col // 128 * 128)


def _block_cols(block_cols: Optional[int], n_workers: int, n_planes: int,
                n: int) -> int:
    """Column tile of a worker-grid kernel over ``n`` columns: explicit arg,
    else ``REPRO_OTA_BLOCK_COLS``, else :func:`vmem_block_cols`; a tile
    as wide as ``n`` or wider becomes one full-width block."""
    if block_cols is None:
        from repro import optflags
        block_cols = optflags.ota_block_cols()
    if block_cols is None:
        block_cols = vmem_block_cols(n_workers, n_planes)
    return min(block_cols, n)


def _col_grid(kernel: str, n_workers: int, n: int, block_cols: int,
              planes) -> Tuple[int]:
    """Grid of a worker-grid launch: ``cdiv(n, block_cols)`` column steps,
    the last one overhanging ``n`` (Pallas reads it padded and drops the
    overhanging writes).  The launch is noted in the open
    ``repro.obs.profiling.grid_launches`` record, with the columns its
    ``planes`` (the operands walked by the grid) hold beyond ``n``."""
    from repro.obs.profiling import record_grid_launch
    steps = pl.cdiv(n, block_cols)
    record_grid_launch(kernel, workers=n_workers, n=n,
                       block_cols=block_cols, steps=steps,
                       pad_cols=max(p.shape[-1] for p in planes) - n)
    return (steps,)


def _mod_kernel(theta_ref, lre_ref, lim_ref, hre_ref, him_ref,
                sre_ref, sim_ref, *, inv_rho: float):
    t = theta_ref[...].astype(jnp.float32)
    sre_ref[...] = hre_ref[...] * t + lre_ref[...] * inv_rho
    sim_ref[...] = -him_ref[...] * t - lim_ref[...] * inv_rho


def _demod_kernel(yre_ref, nre_ref, p2_ref, out_ref, *, inv_alpha: float):
    y = yre_ref[...] + nre_ref[...] * inv_alpha
    out_ref[...] = y / jnp.maximum(p2_ref[...], 1e-12)


def _demod_dyn_kernel(ia_ref, yre_ref, nre_ref, p2_ref, out_ref):
    y = yre_ref[...] + nre_ref[...] * ia_ref[0]
    out_ref[...] = y / jnp.maximum(p2_ref[...], 1e-12)


def _receive_kernel(ia_ref, sre_ref, sim_ref, hre_ref, him_ref, nre_ref,
                    out_ref):
    hre = hre_ref[...]
    him = him_ref[...]
    rx_re = hre * sre_ref[...] - him * sim_ref[...]   # Re{h ⊙ s}
    y = jnp.sum(rx_re, axis=0, keepdims=True)         # superposition (the air)
    p2 = jnp.sum(hre * hre + him * him, axis=0, keepdims=True)
    y = y + nre_ref[...] * ia_ref[0]                  # matched-filter noise/α
    out_ref[...] = y / jnp.maximum(p2, 1e-12)         # Θ (Eq. 24)


def _accumulate_kernel(yacc_ref, p2acc_ref, sre_ref, sim_ref, hre_ref,
                       him_ref, yout_ref, p2out_ref):
    hre = hre_ref[...]
    him = him_ref[...]
    yout_ref[...] = yacc_ref[...] + hre * sre_ref[...] - him * sim_ref[...]
    p2out_ref[...] = p2acc_ref[...] + hre * hre + him * him


def _grid_spec(n_inputs: int, rows: int, block_rows: int):
    """Row-blocked grid over ``(rows, LANE)`` planes.  The last block may
    overhang ``rows``: Pallas reads it padded and drops the overhanging
    writes, so no operand is copied to a block multiple."""
    br = min(block_rows, rows)
    grid = (-(-rows // br),)
    spec = pl.BlockSpec((br, LANE), lambda i: (i, 0))
    return grid, [spec] * n_inputs, spec


def _pad_2d(x: Array, rows: int) -> Array:
    """Flat ``x`` as ``(rows, LANE)``, zero-padded; no copy when it fills
    them already."""
    x = x.reshape(-1)
    pad = rows * LANE - x.size
    return (jnp.pad(x, (0, pad)) if pad else x).reshape(rows, LANE)


def _rows_for(n: int) -> int:
    return -(-n // LANE)


def ota_modulate(theta: Array, lam_re: Array, lam_im: Array, h_re: Array,
                 h_im: Array, rho: float, *,
                 block_rows: Optional[int] = None,
                 interpret: bool = False) -> Tuple[Array, Array]:
    """Fused s = conj(h)·θ + conj(λ)/ρ over a flat parameter vector."""
    block_rows = _block_rows(block_rows, 7)
    n = theta.size
    rows = _rows_for(n)
    args = [_pad_2d(a.astype(jnp.float32), rows)
            for a in (theta, lam_re, lam_im, h_re, h_im)]
    grid, in_specs, out_spec = _grid_spec(5, rows, block_rows)
    sre, sim = pl.pallas_call(
        functools.partial(_mod_kernel, inv_rho=1.0 / rho),
        grid=grid,
        in_specs=in_specs,
        out_specs=[out_spec, out_spec],
        out_shape=[jax.ShapeDtypeStruct((rows, LANE), jnp.float32)] * 2,
        interpret=interpret,
    )(*args)
    return sre.reshape(-1)[:n], sim.reshape(-1)[:n]


def ota_demodulate(y_re: Array, noise_re: Array, sumh2: Array,
                   inv_alpha: float, *, block_rows: Optional[int] = None,
                   interpret: bool = False) -> Array:
    """Fused Θ = (y_re + z_re/α) / max(Σ|h|², eps)."""
    block_rows = _block_rows(block_rows, 4)
    n = y_re.size
    rows = _rows_for(n)
    args = [_pad_2d(a.astype(jnp.float32), rows)
            for a in (y_re, noise_re, sumh2)]
    grid, in_specs, out_spec = _grid_spec(3, rows, block_rows)
    out = pl.pallas_call(
        functools.partial(_demod_kernel, inv_alpha=float(inv_alpha)),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((rows, LANE), jnp.float32),
        interpret=interpret,
    )(*args)
    return out.reshape(-1)[:n]


def _scalar_spec():
    """(1,) runtime scalar operand, kept in SMEM on TPU."""
    return pl.BlockSpec((1,), lambda i: (0,), memory_space=pltpu.SMEM)


def ota_demodulate_dyn(y_re: Array, noise_re: Array, sumh2: Array,
                       inv_alpha: Array | float,
                       *, block_cols: Optional[int] = None,
                       interpret: bool = False) -> Array:
    """Fused Θ = (y_re + z_re·inv_alpha) / max(Σ|h|², eps) with a *traced*
    inv_alpha scalar (the power-control α is data-dependent per round).

    The ``(d,)`` planes run as one ``(1, d)`` row on a column grid, the
    layout the round's stats kernel emits ``y_re``/``Σ|h|²`` in, so no
    operand is re-tiled in HBM on the way in."""
    n = y_re.size
    block_cols = _block_cols(block_cols, 1, 4, n)
    args = [a.astype(jnp.float32).reshape(1, n)
            for a in (y_re, noise_re, sumh2)]
    ia = jnp.asarray(inv_alpha, jnp.float32).reshape(1)
    spec = pl.BlockSpec((1, block_cols), lambda i: (0, i))
    out = pl.pallas_call(
        _demod_dyn_kernel,
        grid=_col_grid("ota_demodulate_dyn", 1, n, block_cols, args),
        in_specs=[_scalar_spec()] + [spec] * 3,
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.float32),
        interpret=interpret,
    )(ia, *args)
    return out.reshape(y_re.shape)


def ota_accumulate(y_re: Array, sumh2: Array, s_re: Array, s_im: Array,
                   h_re: Array, h_im: Array,
                   *, block_rows: Optional[int] = None,
                   interpret: bool = False) -> Tuple[Array, Array]:
    """Fused worker-at-a-time receiver update over a flat vector:

        y_re  += Re{h ⊙ s} = h_re·s_re − h_im·s_im
        Σ|h|² += h_re² + h_im²

    One HBM pass over six input planes and two outputs — the per-scan-step
    superposition of the time-multiplexed (sketched) uplink, whose final
    demodulate then runs once per round (``ota_demodulate_dyn``).
    """
    block_rows = _block_rows(block_rows, 8)
    n = y_re.size
    rows = _rows_for(n)
    args = [_pad_2d(a.astype(jnp.float32), rows)
            for a in (y_re, sumh2, s_re, s_im, h_re, h_im)]
    grid, in_specs, out_spec = _grid_spec(6, rows, block_rows)
    y, p2 = pl.pallas_call(
        _accumulate_kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[out_spec, out_spec],
        out_shape=[jax.ShapeDtypeStruct((rows, LANE), jnp.float32)] * 2,
        interpret=interpret,
    )(*args)
    return y.reshape(-1)[:n], p2.reshape(-1)[:n]


def ota_receive(s_re: Array, s_im: Array, h_re: Array, h_im: Array,
                noise_re: Array, inv_alpha: Array | float,
                *, block_cols: Optional[int] = None,
                interpret: bool = False) -> Array:
    """Fully fused receive chain: Θ = (Re{Σ_n h_n⊙s_n} + z·α⁻¹)/max(Σ|h|²,eps).

    One pass over the (W, d) signal/fading planes — the superposition (worker
    reduction), matched-filter noise scaling, and demodulation never
    materialise y/Σ|h|² in HBM.  s/h: (W, d) planes; noise_re: (d,);
    inv_alpha: traced scalar.  Returns (d,) f32.

    ``d`` is whatever the caller's packing produced: the full packed D on a
    replicated/single-device layout, or the SHARD-LOCAL width ``d_local``
    inside ``shard_map`` on a model-parallel mesh — there the grid spans one
    shard's columns and each device launches its own fused chain (the
    shard-local round passes ``reduce_fn=None`` whenever the worker axis is
    local, so the whole receive stays one kernel per shard).
    """
    W, n = s_re.shape
    block_cols = _block_cols(block_cols, W, 4, n)
    args = [a.astype(jnp.float32) for a in (s_re, s_im, h_re, h_im)]
    nz = noise_re.astype(jnp.float32).reshape(1, n)
    ia = jnp.asarray(inv_alpha, jnp.float32).reshape(1)
    wspec = pl.BlockSpec((W, block_cols), lambda i: (0, i))
    rspec = pl.BlockSpec((1, block_cols), lambda i: (0, i))
    out = pl.pallas_call(
        _receive_kernel,
        grid=_col_grid("ota_receive", W, n, block_cols, args + [nz]),
        in_specs=[_scalar_spec()] + [wspec] * 4 + [rspec],
        out_specs=rspec,
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.float32),
        interpret=interpret,
    )(ia, *args, nz)
    return out.reshape(-1)
