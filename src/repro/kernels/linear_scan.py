"""Pallas TPU kernel: blocked gated linear recurrence  h_t = a_t⊙h_{t−1} + b_t.

The compute hot spot of the SSM/hybrid families (mamba1 selective scan with
the state dim folded into channels; RG-LRU directly).  The naive lowering
materialises the full (B, S, D) scan intermediates in HBM; this kernel walks
the sequence in VMEM-resident tiles, carrying the (1, bd) recurrence state in
scratch across sequential grid steps — HBM traffic is exactly one read of
(a, b) and one write of h.

Grid: (B, D/bd, S/bs) — the sequence dimension is innermost (and
``"arbitrary"``), so for a fixed (batch, channel-tile) the S-tiles execute
in order and the carry is live in VMEM the whole time.  Within a tile the
recurrence walks the rows in order, one ``(1, bd)`` lane row per step:

    h_t = a_t * h_{t-1} + b_t,   h_{-1} = carry

Mosaic lowers neither ``associative_scan`` nor ``cumprod`` along sublanes,
and row-wise steps are exactly the reference recurrence.

Differentiable via :func:`jax.custom_vjp`: the cotangent recurrence
``g_t = dh_t + a_{t+1} g_{t+1}`` is itself a linear scan run in reverse, so
the backward pass is ONE more launch of the same kernel on flipped/shifted
inputs plus two elementwise products (``da_t = g_t ⊙ h_{t−1}``,
``db = g``) — the forward output ``h`` is the only residual.  Forward-mode
(``jax.jvp``) raises JAX's clean custom_vjp TypeError.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

DEFAULT_BS = 256   # sequence tile
DEFAULT_BD = 128   # channel tile (lane width)


def _scan_kernel(a_ref, b_ref, o_ref, carry_ref, *, block_s: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    def step(t, h):                    # h: (1, bd)
        row = pl.ds(t, 1)
        h = a_ref[0, row, :] * h + b_ref[0, row, :]
        o_ref[0, row, :] = h
        return h

    carry_ref[...] = jax.lax.fori_loop(0, block_s, step, carry_ref[...])


def _scan_launch(a: Array, b: Array, *, block_s: int, block_d: int,
                 interpret: bool) -> Array:
    """Raw kernel launch (no AD rule).  Pads S and D up to tile multiples
    (a=1/b=0 padding is the identity element of the recurrence, so padded
    steps are no-ops)."""
    B, S, D = a.shape
    Sp = -(-S // block_s) * block_s
    Dp = -(-D // block_d) * block_d
    ap = jnp.pad(a.astype(jnp.float32), ((0, 0), (0, Sp - S), (0, Dp - D)),
                 constant_values=1.0)
    bp = jnp.pad(b.astype(jnp.float32), ((0, 0), (0, Sp - S), (0, Dp - D)))

    grid = (B, Dp // block_d, Sp // block_s)
    spec = pl.BlockSpec((1, block_s, block_d), lambda bi, di, si: (bi, si, di))
    out = pl.pallas_call(
        functools.partial(_scan_kernel, block_s=block_s),
        grid=grid,
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((B, Sp, Dp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, block_d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(ap, bp)
    return out[:, :S, :D]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _scan_vjp(a: Array, b: Array, block_s: int, block_d: int,
              interpret: bool) -> Array:
    return _scan_launch(a, b, block_s=block_s, block_d=block_d,
                        interpret=interpret)


def _scan_fwd_rule(a, b, block_s, block_d, interpret):
    h = _scan_launch(a, b, block_s=block_s, block_d=block_d,
                     interpret=interpret)
    return h, (a, b, h)   # b only for its dtype (db = g cast back)


def _scan_bwd_rule(block_s, block_d, interpret, res, dh):
    a, b, h = res
    af = a.astype(jnp.float32)
    # g_t = dh_t + a_{t+1} g_{t+1}: the same recurrence over the reversed
    # sequence with the gates shifted one step — a'_t = a_{S-t} (a'_0 only
    # ever multiplies the zero initial carry, so the roll wrap is harmless).
    a_rev = jnp.roll(jnp.flip(af, axis=1), 1, axis=1)
    g = jnp.flip(_scan_launch(a_rev, jnp.flip(dh.astype(jnp.float32), axis=1),
                              block_s=block_s, block_d=block_d,
                              interpret=interpret), axis=1)
    h_prev = jnp.pad(h[:, :-1], ((0, 0), (1, 0), (0, 0)))  # h_{-1} = 0
    return (g * h_prev).astype(a.dtype), g.astype(b.dtype)


_scan_vjp.defvjp(_scan_fwd_rule, _scan_bwd_rule)


def linear_scan(a: Array, b: Array, *, block_s: int = DEFAULT_BS,
                block_d: int = DEFAULT_BD, interpret: bool = False) -> Array:
    """h_t = a_t ⊙ h_{t−1} + b_t over (B, S, D); h_0 = b_0.  Differentiable
    (custom VJP: one reversed launch of the same kernel, see module doc)."""
    return _scan_vjp(a, b, int(block_s), int(block_d), bool(interpret))
