"""Pallas TPU kernel: flash attention (online-softmax, KV-blocked) with a
custom VJP — differentiable end-to-end, so ``REPRO_USE_PALLAS=1`` training
runs the TPU-native attention in the grad path.

The §Perf analysis (EXPERIMENTS.md) shows ~64% of the train_4k memory term
is the attention-score elementwise chain — (S,S) tensors crossing HBM once
per softmax stage per pass.  Keeping the score block resident in VMEM while
streaming KV tiles removes that traffic entirely; this kernel is the
TPU-native fix (the pure-XLA q-chunking variant was measured and refuted:
it reduces peak, not traffic).

Forward:  q (B,H,S,hd), k/v (B,H,T,hd).  Grid (B, H, S/bq, T/bk), KV tiles
innermost; the (m, l, acc) online-softmax state lives in VMEM scratch across
KV steps.  Causal masking by absolute indices; fully-masked KV tiles skip
the matmuls via ``pl.when``.  Besides the output ``o`` the kernel emits the
per-row log-sum-exp residual ``lse = m + log(l)`` — ONE extra f32
``(B, H, S, 1)`` plane, the only thing the backward pass needs beyond the
primal inputs (the (S,S) probability tensor is never materialised in either
pass).  Row statistics (lse, δ, and the m/l scratch) are ``(rows, 1)``
columns: a ``(bq, 1)`` block is tile-aligned for Mosaic (a trailing dim
equal to the array's) and broadcasts against the ``(bq, bk)`` scores
without a relayout.

Backward (registered via :func:`jax.custom_vjp`): two kernels that
recompute the probability block ``p = exp(s − lse)`` from the residuals:

* ``dq``   — grid (B, H, S/bq, T/bk), KV innermost: streams KV tiles per Q
  block, accumulating ``dq += (p ∘ (do·vᵀ − δ)) · k · scale`` in VMEM.
* ``dk/dv`` — grid (B, H, T/bk, S/bq), Q innermost: streams Q tiles per KV
  block, accumulating ``dv += pᵀ·do`` and ``dk += dsᵀ·q · scale``.

Both skip fully-masked causal tiles with the same ``pl.when`` predicate as
the forward.  ``δ = Σ_d do ∘ o`` (another (B,H,S,1) f32 plane) is computed
once outside the kernels.  Forward-mode AD (``jax.jvp``) is explicitly
unsupported — JAX raises a clean ``TypeError`` for custom_vjp functions
instead of the historical ``_pallas_call_jvp_rule`` AssertionError.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

DEFAULT_BQ = 256
DEFAULT_BK = 256
NEG_INF = -1e30


def _causal_mask(qi, ki, bq: int, bk: int, t_limit: Optional[int]):
    """cols ≤ rows, and (when KV is tile-padded, ``t_limit = T``) cols < T —
    rows past T would otherwise causally admit the zero-padded keys."""
    rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    cols = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    m = cols <= rows
    if t_limit is not None:
        m = jnp.logical_and(m, cols < t_limit)
    return m


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref, *,
                scale: float, causal: bool, bq: int, bk: int, n_k: int,
                t_limit: Optional[int]):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _step():
        q = q_ref[0, 0].astype(jnp.float32)           # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)           # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            s = jnp.where(_causal_mask(qi, ki, bq, bk, t_limit), s, NEG_INF)
        m_prev = m_ref[...]                            # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    if causal:
        # skip KV tiles strictly above the diagonal (fully masked)
        pl.when((ki * bk) <= (qi * bq + bq - 1))(_step)
    else:
        _step()

    @pl.when(ki == n_k - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = m_ref[...] + jnp.log(l)


def _pad_qkv(q: Array, k: Array, v: Array, causal: bool, bq: int, bk: int):
    S, T = q.shape[2], k.shape[2]
    Sp = -(-S // bq) * bq
    Tp = -(-T // bk) * bk
    if not causal and Tp != T:
        raise NotImplementedError("non-causal padding requires T % block_k == 0")
    # padded keys must never win the max: leave them 0 — causal masking
    # hides them (cols > rows, plus the cols < T bound the kernels apply
    # whenever Tp != T, which covers rows past T when T < S).
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, Sp - S), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, Tp - T), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, Tp - T), (0, 0)))
    return qp, kp, vp, Sp, Tp


def _flash_forward(q: Array, k: Array, v: Array, *, causal: bool,
                   scale: float, block_q: int, block_k: int,
                   interpret: bool) -> Tuple[Array, Array]:
    """Forward kernel launch.  Returns (o, lse), both sliced to S; lse is
    ``(B, H, S, 1)``."""
    B, H, S, hd = q.shape
    T = k.shape[2]
    bq = min(block_q, S)
    bk = min(block_k, T)
    qp, kp, vp, Sp, Tp = _pad_qkv(q, k, v, causal, bq, bk)
    n_k = Tp // bk

    grid = (B, H, Sp // bq, n_k)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=float(scale), causal=causal,
                          bq=bq, bk=bk, n_k=n_k,
                          t_limit=T if Tp != T else None),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, qi, ki: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, qi, ki: (b, h, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, qi, ki: (b, h, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Sp, hd), q.dtype),
            jax.ShapeDtypeStruct((B, H, Sp, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        interpret=interpret,
    )(qp, kp, vp)
    return out[:, :, :S], lse[:, :, :S]


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_acc, *, scale: float, causal: bool, bq: int, bk: int,
               n_k: int, t_limit: Optional[int]):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def _step():
        q = q_ref[0, 0].astype(jnp.float32)            # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)            # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)          # (bq, hd)
        lse = lse_ref[0, 0]                            # (bq, 1)
        delta = delta_ref[0, 0]                        # (bq, 1)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            s = jnp.where(_causal_mask(qi, ki, bq, bk, t_limit), s, NEG_INF)
        p = jnp.exp(s - lse)                  # masked entries -> 0
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dq_acc[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    if causal:
        pl.when((ki * bk) <= (qi * bq + bq - 1))(_step)
    else:
        _step()

    @pl.when(ki == n_k - 1)
    def _finish():
        dq_ref[0, 0] = dq_acc[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref,
                dv_ref, dk_acc, dv_acc, *, scale: float, causal: bool,
                bq: int, bk: int, n_q: int, t_limit: Optional[int]):
    ki = pl.program_id(2)
    qi = pl.program_id(3)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def _step():
        q = q_ref[0, 0].astype(jnp.float32)            # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)            # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)          # (bq, hd)
        lse = lse_ref[0, 0]                            # (bq, 1)
        delta = delta_ref[0, 0]                        # (bq, 1)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            s = jnp.where(_causal_mask(qi, ki, bq, bk, t_limit), s, NEG_INF)
        p = jnp.exp(s - lse)                  # (bq, bk)
        dv_acc[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # pᵀ·do  (bk, hd)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # dsᵀ·q (bk, hd)

    if causal:
        # a KV tile sees gradient only from Q rows at or below its diagonal
        pl.when((qi * bq + bq - 1) >= (ki * bk))(_step)
    else:
        _step()

    @pl.when(qi == n_q - 1)
    def _finish():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_backward(q: Array, k: Array, v: Array, o: Array, lse: Array,
                    do: Array, *, causal: bool, scale: float, block_q: int,
                    block_k: int, interpret: bool
                    ) -> Tuple[Array, Array, Array]:
    B, H, S, hd = q.shape
    T = k.shape[2]
    bq = min(block_q, S)
    bk = min(block_k, T)
    qp, kp, vp, Sp, Tp = _pad_qkv(q, k, v, causal, bq, bk)
    dop = jnp.pad(do, ((0, 0), (0, 0), (0, Sp - S), (0, 0)))
    # δ = Σ_d do ∘ o per row (f32): with do/δ zero on padded rows, those
    # rows contribute exactly 0 to every cotangent, so lse can pad with 0.
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1,
                    keepdims=True)
    lsep = jnp.pad(lse, ((0, 0), (0, 0), (0, Sp - S), (0, 0)))
    deltap = jnp.pad(delta, ((0, 0), (0, 0), (0, Sp - S), (0, 0)))
    n_q = Sp // bq
    n_k = Tp // bk

    q_spec = pl.BlockSpec((1, 1, bq, hd), lambda b, h, qi, ki: (b, h, qi, 0))
    k_spec = pl.BlockSpec((1, 1, bk, hd), lambda b, h, qi, ki: (b, h, ki, 0))
    r_spec = pl.BlockSpec((1, 1, bq, 1), lambda b, h, qi, ki: (b, h, qi, 0))

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=float(scale), causal=causal,
                          bq=bq, bk=bk, n_k=n_k,
                          t_limit=T if Tp != T else None),
        grid=(B, H, n_q, n_k),
        in_specs=[q_spec, k_spec, k_spec, q_spec, r_spec, r_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, Sp, hd), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, hd), jnp.float32)],
        interpret=interpret,
    )(qp, kp, vp, dop, lsep, deltap)

    # KV-major grid: program_id(2) walks KV tiles, Q tiles stream innermost
    qT_spec = pl.BlockSpec((1, 1, bq, hd), lambda b, h, ki, qi: (b, h, qi, 0))
    kT_spec = pl.BlockSpec((1, 1, bk, hd), lambda b, h, ki, qi: (b, h, ki, 0))
    rT_spec = pl.BlockSpec((1, 1, bq, 1), lambda b, h, ki, qi: (b, h, qi, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=float(scale), causal=causal,
                          bq=bq, bk=bk, n_q=n_q,
                          t_limit=T if Tp != T else None),
        grid=(B, H, n_k, n_q),
        in_specs=[qT_spec, kT_spec, kT_spec, qT_spec, rT_spec, rT_spec],
        out_specs=[kT_spec, kT_spec],
        out_shape=[jax.ShapeDtypeStruct((B, H, Tp, hd), k.dtype),
                   jax.ShapeDtypeStruct((B, H, Tp, hd), v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, hd), jnp.float32),
                        pltpu.VMEM((bk, hd), jnp.float32)],
        interpret=interpret,
    )(qp, kp, vp, dop, lsep, deltap)

    return dq[:, :, :S], dk[:, :, :T], dv[:, :, :T]


# ---------------------------------------------------------------------------
# custom_vjp wiring
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q: Array, k: Array, v: Array, causal: bool, scale: float,
           block_q: int, block_k: int, interpret: bool) -> Array:
    o, _ = _flash_forward(q, k, v, causal=causal, scale=scale,
                          block_q=block_q, block_k=block_k,
                          interpret=interpret)
    return o


def _flash_fwd_rule(q, k, v, causal, scale, block_q, block_k, interpret):
    o, lse = _flash_forward(q, k, v, causal=causal, scale=scale,
                            block_q=block_q, block_k=block_k,
                            interpret=interpret)
    return o, (q, k, v, o, lse)


def _flash_bwd_rule(causal, scale, block_q, block_k, interpret, res, do):
    q, k, v, o, lse = res
    return _flash_backward(q, k, v, o, lse, do, causal=causal, scale=scale,
                           block_q=block_q, block_k=block_k,
                           interpret=interpret)


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention(q: Array, k: Array, v: Array, *, causal: bool = True,
                    scale: Optional[float] = None, block_q: int = DEFAULT_BQ,
                    block_k: int = DEFAULT_BK,
                    interpret: bool = False) -> Array:
    """q: (B,H,S,hd); k/v: (B,H,T,hd) -> (B,H,S,hd).  S, T padded to tiles.

    Differentiable: ``jax.grad``/``jax.vjp`` route through the Pallas
    backward kernels above (cotangents returned in the primal dtypes, f32
    accumulation).  Residual cost beyond the primals: one f32
    ``(B, H, S, 1)`` log-sum-exp plane saved by the forward.
    """
    hd = q.shape[-1]
    scale = hd ** -0.5 if scale is None else float(scale)
    return _flash(q, k, v, bool(causal), float(scale), int(block_q),
                  int(block_k), bool(interpret))
