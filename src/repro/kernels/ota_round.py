"""Fused one-pass OTA *round* kernels: the whole uplink in one HBM sweep.

The composed transport path (``kernels/ota.py``) launches one kernel per
primitive — modulate, (mask+)receive, demodulate — and each launch re-streams
the ``(W, d_pad)`` worker planes through HBM.  At packed LLM scale those
planes ARE the round's byte budget, so the round should read each worker
plane exactly once.  The kernels here do that:

* :func:`ota_round_stats` — modulate → per-worker energy → (participation
  mask) → superpose → pilot aggregate, in ONE pass over the worker planes.
  Emits ``(y_re, sumh2, energy)``: everything the receiver needs that
  depends on the ``(W, d)`` data.  The min-α power consensus is a *global*
  data dependence (α = min over ALL workers of sqrt(P/E_n)), so with
  same-round power control the demodulate epilogue cannot run in the same
  launch — it runs as the existing O(d) ``ota_demodulate_dyn`` kernel over
  the reduced planes, which never touches the worker axis.  The AR(1)
  fading step (``kernels/phy_channel.fading_step``) can optionally be fused
  into the same launch (``chan`` inputs), so channel evolution + the whole
  TX side share the single pass.

* :func:`ota_round_theta` — when ``inv_alpha`` is known *before* the pass
  (``power_control=False``, or a cached/previous-round α), the epilogue
  collapses into the same launch: modulate → mask → superpose → AWGN →
  matched filter → demodulate, worker planes to Θ in ONE kernel.

Per-worker energies accumulate in a resident ``(W, 1)`` output block that
every grid step revisits, so the stats kernel's grid axis is sequential
(``"arbitrary"``).  The column-block partial sums change the summation
*order* versus ``transport.worker_energy`` (a single (W, d) row sum), so
energies/α agree to float tolerance, not bitwise; the noise-free Θ stays
bitwise regardless (zero noise × any α).

Layout matches the kernel set: the ``(W, d)`` f32 planes as laid out, on a
column grid of ``block_cols`` lanes, the widest tile whose working set fits
VMEM (``kernels/ota._block_cols``: 26,112 lanes for the stats kernel at
W = 2, 768 at W = 256).  The last tile may overhang ``d``: Pallas reads it
padded and drops the overhanging writes, and the energy sum, the one
reduction across columns, masks those columns out.  No plane is padded in
HBM.  Runtime scalars ride in SMEM.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ota import _block_cols, _col_grid

Array = jax.Array


def _scalar_spec(n: int = 1):
    """(n,) runtime scalar operand, kept in SMEM on TPU."""
    return pl.BlockSpec((n,), lambda i: (0,), memory_space=pltpu.SMEM)


def _round_kernel(*refs, inv_rho: float, has_mask: bool, has_htx: bool,
                  has_chan: bool, emit_theta: bool, n: int, block_cols: int):
    """Shared body of the stats/theta round kernels.

    Ref order (inputs): [ia (SMEM) if emit_theta] [chan params (SMEM) if
    has_chan] [mask if has_mask] theta lre lim hre him [txre txim if
    has_htx] [wre wim if has_chan] [nre if emit_theta]; then outputs:
    emit_theta -> theta_out [+ hnew_re hnew_im]; else -> y p2 energy
    [+ hnew_re hnew_im].
    """
    it = iter(refs)
    ia_ref = next(it) if emit_theta else None
    p_ref = next(it) if has_chan else None
    m_ref = next(it) if has_mask else None
    th_ref, lre_ref, lim_ref, hre_ref, him_ref = (next(it) for _ in range(5))
    tx_refs = (next(it), next(it)) if has_htx else None
    w_refs = (next(it), next(it)) if has_chan else None
    nre_ref = next(it) if emit_theta else None
    if emit_theta:
        out_ref = next(it)
    else:
        y_ref, p2_ref, e_ref = next(it), next(it), next(it)
    hn_refs = (next(it), next(it)) if has_chan else None

    hre = hre_ref[...]
    him = him_ref[...]
    if has_chan:
        rho_f, scale, redraw = p_ref[0], p_ref[1], p_ref[2]
        upd = redraw != 0.0
        hre = jnp.where(upd, rho_f * hre + scale * w_refs[0][...], hre)
        him = jnp.where(upd, rho_f * him + scale * w_refs[1][...], him)
        hn_refs[0][...] = hre           # stepped channel, pre-mask
        hn_refs[1][...] = him

    # modulate with the worker-side CSI (h_hat planes, or the channel itself)
    txre = tx_refs[0][...] if has_htx else hre
    txim = tx_refs[1][...] if has_htx else him
    t = th_ref[...].astype(jnp.float32)
    sre = txre * t + lre_ref[...] * inv_rho
    sim = -txim * t - lim_ref[...] * inv_rho

    if not emit_theta:
        # per-worker energy of the UNMASKED signal (power control measures
        # what the worker WOULD send; participation applies in min-α),
        # accumulated over the column blocks in the resident (W, 1) block
        @pl.when(pl.program_id(0) == 0)
        def _init():
            e_ref[...] = jnp.zeros_like(e_ref)

        e2 = sre * sre + sim * sim
        if n % block_cols:
            # the last block overhangs the n columns; what Pallas read
            # past them must not reach the sum
            col = jax.lax.broadcasted_iota(jnp.int32, e2.shape, 1)
            e2 = jnp.where(col < n - pl.program_id(0) * block_cols, e2, 0.0)
        e_ref[...] += jnp.sum(e2, axis=1, keepdims=True)

    if has_mask:
        active = m_ref[...] != 0.0
        hre = jnp.where(active, hre, 0.0)
        him = jnp.where(active, him, 0.0)
        sre = jnp.where(active, sre, 0.0)
        sim = jnp.where(active, sim, 0.0)

    y = jnp.sum(hre * sre - him * sim, axis=0, keepdims=True)   # Re{Σ h⊙s}
    p2 = jnp.sum(hre * hre + him * him, axis=0, keepdims=True)  # Σ|h|²
    if emit_theta:
        y = y + nre_ref[...] * ia_ref[0]                        # z/α
        out_ref[...] = y / jnp.maximum(p2, 1e-12)               # Θ (Eq. 24)
    else:
        y_ref[...] = y
        p2_ref[...] = p2


def _round_call(theta, lam_re, lam_im, h_re, h_im, rho, *, mask, htx, chan,
                noise_ia, block_cols, interpret):
    """Assemble specs/operands for the shared round kernel and launch it."""
    W, n = theta.shape
    emit_theta = noise_ia is not None
    has_mask, has_htx, has_chan = (mask is not None, htx is not None,
                                   chan is not None)
    # (W, block_cols) planes of the launch: mask, the five round planes,
    # CSI and innovations in, the stepped channel out
    block_cols = _block_cols(block_cols, W,
                             has_mask + 5 + 2 * has_htx + 4 * has_chan, n)

    def f32(x: Array) -> Array:
        return x.astype(jnp.float32)

    wspec = pl.BlockSpec((W, block_cols), lambda i: (0, i))
    mspec = pl.BlockSpec((W, block_cols), lambda i: (0, 0))
    rspec = pl.BlockSpec((1, block_cols), lambda i: (0, i))
    espec = pl.BlockSpec((W, 1), lambda i: (0, 0))
    wplane = jax.ShapeDtypeStruct((W, n), jnp.float32)
    rplane = jax.ShapeDtypeStruct((1, n), jnp.float32)

    ops, in_specs = [], []
    if emit_theta:
        noise_re, inv_alpha = noise_ia
        ops.append(jnp.asarray(inv_alpha, jnp.float32).reshape(1))
        in_specs.append(_scalar_spec(1))
    if has_chan:
        w_re, w_im, rho_f, scale, redraw = chan
        ops.append(jnp.stack([jnp.asarray(rho_f, jnp.float32),
                              jnp.asarray(scale, jnp.float32),
                              jnp.asarray(redraw, jnp.float32)]))
        in_specs.append(_scalar_spec(3))
    if has_mask:
        ops.append(jnp.broadcast_to(mask.astype(jnp.float32)[:, None],
                                    (W, block_cols)))
        in_specs.append(mspec)
    planes = [f32(a) for a in (theta, lam_re, lam_im, h_re, h_im)]
    if has_htx:
        planes += [f32(htx[0]), f32(htx[1])]
    if has_chan:
        planes += [f32(w_re), f32(w_im)]
    ops += planes
    in_specs += [wspec] * len(planes)
    if emit_theta:
        ops.append(f32(noise_re).reshape(1, n))
        in_specs.append(rspec)

    if emit_theta:
        out_specs, out_shape = [rspec], [rplane]
    else:
        out_specs = [rspec, rspec, espec]
        out_shape = [rplane, rplane,
                     jax.ShapeDtypeStruct((W, 1), jnp.float32)]
    if has_chan:
        out_specs += [wspec, wspec]
        out_shape += [wplane, wplane]

    kernel = functools.partial(
        _round_kernel, inv_rho=1.0 / rho, has_mask=has_mask,
        has_htx=has_htx, has_chan=has_chan, emit_theta=emit_theta, n=n,
        block_cols=block_cols)
    outs = pl.pallas_call(
        kernel,
        grid=_col_grid("ota_round_theta" if emit_theta else "ota_round_stats",
                       W, n, block_cols, planes),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        # the stats kernel revisits its energy block on every step
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel" if emit_theta else "arbitrary",)),
        interpret=interpret,
    )(*ops)

    it = iter(outs)
    if emit_theta:
        res = (next(it).reshape(-1),)
    else:
        y, p2, e = next(it), next(it), next(it)
        res = (y.reshape(-1), p2.reshape(-1), e.reshape(W))
    if has_chan:
        res += (next(it), next(it))
    return res


def ota_round_stats(theta: Array, lam_re: Array, lam_im: Array,
                    h_re: Array, h_im: Array, rho: float, *,
                    mask: Optional[Array] = None,
                    htx: Optional[Tuple[Array, Array]] = None,
                    chan: Optional[Tuple] = None,
                    block_cols: Optional[int] = None,
                    interpret: bool = False):
    """One-pass TX side of the round over ``(W, d)`` planes.

    Returns ``(y_re (d,), sumh2 (d,), energy (W,))``, plus
    ``(h_new_re, h_new_im)`` planes when ``chan`` fuses the AR(1) fading
    step ``chan = (w_re, w_im, rho_fad, scale, redraw)`` into the launch.
    ``htx = (re, im)`` is the imperfect-CSI precoding channel (the air
    still applies ``h``).
    """
    return _round_call(theta, lam_re, lam_im, h_re, h_im, rho, mask=mask,
                       htx=htx, chan=chan, noise_ia=None,
                       block_cols=block_cols, interpret=interpret)


def ota_round_theta(theta: Array, lam_re: Array, lam_im: Array,
                    h_re: Array, h_im: Array, noise_re: Array,
                    inv_alpha: Array | float, rho: float, *,
                    mask: Optional[Array] = None,
                    htx: Optional[Tuple[Array, Array]] = None,
                    chan: Optional[Tuple] = None,
                    block_cols: Optional[int] = None,
                    interpret: bool = False):
    """The ENTIRE round in one launch, for a-priori-known ``inv_alpha``
    (``power_control=False``): worker planes in, Θ ``(d,)`` out.  Same
    optional ``mask``/``htx``/``chan`` fusion as :func:`ota_round_stats`.

    Returns ``(Theta,)`` or ``(Theta, h_new_re, h_new_im)``.
    """
    return _round_call(theta, lam_re, lam_im, h_re, h_im, rho, mask=mask,
                       htx=htx, chan=chan, noise_ia=(noise_re, inv_alpha),
                       block_cols=block_cols, interpret=interpret)
