"""Round health guards: detect a bad OTA round in the hot path, recover.

After the fused receive produced ``(y, Σ|h|², energy)`` the receiver runs
an O(d), worker-free health check on the would-be global model:

* **finiteness** — every Θ entry finite (NaN/Inf planes from a corrupt
  worker, an overflowed spike, or a degenerate channel poison the whole
  consensus otherwise);
* **receive-SNR floor** — the *measured* signal-to-noise ratio of the slot,
  ``Σy² / Σ(z_eff)²`` where ``z_eff = z/α (+ interference burst)``, must
  clear ``snr_floor_db``.  The check is division-free
  (``Σy² ≥ 10^(floor/10) · Σz²``) so the noise-free 0/0 case can never
  manufacture a NaN, and a NaN anywhere fails closed (NaN comparisons are
  False).

Recovery is a ``lax.cond``/``while_loop``-gated cascade so the healthy fast
path pays only the O(d) check (benchmarked ≤ 1.05× the unguarded fused
round, ``BENCH_faults.json``):

* ``evict`` — offenders (rows with non-finite signal energy or channel
  planes) are cut from the participation mask and the slot re-received
  without them, SAME key: eviction is the PS digitally excising a
  transmitter from the superposition, not a new slot, so an evicted round
  is bitwise the round that never admitted the offender.
* ``retransmit`` — the slot re-runs with a fresh noise draw
  (``fold_in(key, RETRY_SALT + attempt)``) and an exponentially
  backed-off power budget (``power.retry_power_budget`` →
  ``power.alpha_from_energy``), up to ``max_retries``.  The workers resend
  the same planes, so only the O(d) epilogue re-runs — no second pass over
  the (W, D) signals.  Interference bursts are transient and do not recur
  on retries (that is what makes retransmission effective against them).
* ``skip`` — the terminal fallback (and the whole policy when
  ``policy="skip"``): the guard reports ``healthy=False`` and the round
  driver reuses the previous Θ and freezes every dual, riding the PR 4
  all-masked machinery.

``policy`` picks the cascade: ``"skip"``, ``"retransmit"``, ``"evict"``
(evict → skip), or ``"evict-retransmit"`` (evict → retransmit → skip).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import power, transport
from repro.obs.profiling import layer

Array = Any

#: fold_in salts for the guard's extra draws (disjoint from plan.FAULT_SALT)
RETRY_SALT = 0x0E77
BURST_SALT = 0x0B57

_POLICIES = ("skip", "retransmit", "evict", "evict-retransmit")


@dataclasses.dataclass(frozen=True)
class GuardConfig:
    """Static guard description (hashable -> safe to close over in jit)."""

    policy: str = "skip"                 # one of _POLICIES
    snr_floor_db: Optional[float] = None  # None: finiteness check only
    max_retries: int = 2                 # retransmission budget
    power_backoff: float = 2.0           # per-retry power ramp γ

    def __post_init__(self):
        if self.policy not in _POLICIES:
            raise ValueError(f"unknown guard policy {self.policy!r}; "
                             f"expected one of {_POLICIES}")
        if self.max_retries < 0:
            raise ValueError("max_retries must be >= 0")

    @property
    def evicts(self) -> bool:
        return self.policy in ("evict", "evict-retransmit")

    @property
    def retries(self) -> int:
        if self.policy in ("retransmit", "evict-retransmit"):
            return self.max_retries
        return 0


class GuardedRound(NamedTuple):
    """Result of a guarded receive."""

    Theta: Array        # global model (valid iff healthy)
    inv_alpha: Array    # the accepted slot's 1/α
    healthy: Array      # () bool — False: caller applies the skip policy
    evicted: Array      # (W,) bool — offenders cut this round
    metrics: dict       # guard/retries, guard/snr_db, ... (+ obs/ if on)


class _Carry(NamedTuple):
    attempt: Array
    ok: Array
    Theta: Array
    inv_alpha: Array
    y: Array
    p2: Array
    energy: Array
    mask: Array
    sig: Array
    npow: Array


def _identity(x):
    return x


def guarded_receive(key: Array, gcfg: GuardConfig, *,
                    stats_fn: Callable,
                    inv_alpha_fn: Callable,
                    noise_fn: Callable,
                    demod_fn: Callable,
                    mask: Optional[Array],
                    n_workers: int,
                    burst_std: Optional[Array] = None,
                    gsum: Callable = _identity,
                    offender_fn: Optional[Callable] = None,
                    telemetry=None,
                    ) -> GuardedRound:
    """Generic guarded-receive engine, parameterised so the flat/packed
    round (:func:`guarded_ota_round`) and the shard-local round (inside
    ``shard_map``, with psum/pmin reducers) share one cascade.

    * ``stats_fn(mask) -> (y, p2, energy)`` — re-runs the worker-plane pass
      (only called lazily, inside the evict ``lax.cond`` branch; attempt 0
      receives the caller's original mask, possibly None).
    * ``inv_alpha_fn(energy, mask, attempt) -> inv_alpha`` — min-α with the
      attempt's backed-off budget.
    * ``noise_fn(key) -> z`` — matched-filter noise for the local columns.
    * ``demod_fn(y, p2, n_eff) -> Theta``.
    * ``gsum(x) -> x`` — global scalar-sum reducer (identity unsharded,
      psum over the model axis under shard_map; every health decision is a
      ``gsum``-reduced scalar so all shards branch in lockstep).
    * ``offender_fn(mask) -> (W,) bool`` — extra per-row offender evidence
      (non-finite channel planes) on top of the non-finite-energy test.
    """
    base_mask = (jnp.ones((n_workers,), bool) if mask is None else mask)

    def epilogue(y, p2, energy, m, k, attempt, burst):
        ia = inv_alpha_fn(energy, m, attempt)
        n = noise_fn(k)
        if burst is not None:
            # interference enters at the PS antenna, so the receiver's 1/α
            # division scales it exactly like the matched-filter noise
            kb = jax.random.fold_in(k, BURST_SALT)
            n = n + burst * jax.random.normal(kb, n.shape, jnp.float32)
        n_eff = n * ia
        Theta = demod_fn(y, p2, n_eff)
        bad = gsum(jnp.sum((~jnp.isfinite(Theta)).astype(jnp.float32)))
        ok = bad == 0.0
        sig = gsum(jnp.sum(y * y))
        npow = gsum(jnp.sum(n_eff * n_eff))
        if gcfg.snr_floor_db is not None:
            thr = 10.0 ** (gcfg.snr_floor_db / 10.0)
            # division-free: NaN-safe (0/0 impossible, NaN fails closed)
            ok &= sig >= thr * npow
        return Theta, ia, ok, sig, npow

    y0, p20, e0 = stats_fn(mask)
    Th0, ia0, ok0, sig0, np0 = epilogue(y0, p20, e0, base_mask, key,
                                        jnp.int32(0), burst_std)
    no_evict = jnp.zeros((n_workers,), bool)
    carry = _Carry(jnp.int32(1), ok0, Th0, ia0, y0, p20, e0, base_mask,
                   sig0, np0)

    if gcfg.evicts:
        def cut(c):
            off = ~jnp.isfinite(c.energy)
            if offender_fn is not None:
                off |= offender_fn(c.mask)
            off &= c.mask
            m2 = c.mask & ~off
            y2, p22, e2 = stats_fn(m2)
            # SAME key: the PS excises the offender from the received
            # superposition; noise/burst bits of the slot are unchanged
            Th, ia, ok, sig, npow = epilogue(y2, p22, e2, m2, key,
                                             jnp.int32(0), burst_std)
            return c._replace(ok=ok, Theta=Th, inv_alpha=ia, y=y2, p2=p22,
                              energy=e2, mask=m2, sig=sig, npow=npow), off

        def keep(c):
            return c, no_evict

        carry, evicted = jax.lax.cond(ok0, keep, cut, carry)
    else:
        evicted = no_evict

    if gcfg.retries > 0:
        def unhealthy(c):
            return (~c.ok) & (c.attempt <= gcfg.retries)

        def retry(c):
            k = jax.random.fold_in(key, RETRY_SALT + c.attempt)
            Th, ia, ok, sig, npow = epilogue(c.y, c.p2, c.energy, c.mask, k,
                                             c.attempt, None)
            return c._replace(attempt=c.attempt + 1, ok=ok, Theta=Th,
                              inv_alpha=ia, sig=sig, npow=npow)

        carry = jax.lax.while_loop(unhealthy, retry, carry)

    snr_db = transport.snr_db_from_power(carry.sig, carry.npow)
    metrics = {
        "guard/retries": (carry.attempt - 1).astype(jnp.float32),
        "guard/snr_db": snr_db,
        "guard/ok_first": ok0.astype(jnp.float32),
        "guard/healthy": carry.ok.astype(jnp.float32),
        "guard/evicted": jnp.sum(evicted.astype(jnp.float32)),
    }
    tel = telemetry
    if tel is not None:
        # the accepted attempt's channel telemetry — everything is already
        # in the cascade carry, so this adds no dispatches.  The guard's
        # sig/npow include the burst term, so obs/rx_snr_db here is exactly
        # guard/snr_db (one SNR definition, two namespaces).
        alpha = jnp.where(carry.inv_alpha > 0,
                          1.0 / jnp.maximum(carry.inv_alpha, 1e-38), 0.0)
        metrics["obs/rx_snr_db"] = snr_db
        metrics["obs/min_alpha"] = alpha
        metrics["obs/active_workers"] = jnp.sum(
            carry.mask.astype(jnp.float32))
        if tel.per_worker:
            metrics["obs/tx_energy"] = jnp.where(
                carry.mask, carry.energy * (alpha * alpha), 0.0)
    return GuardedRound(carry.Theta, carry.inv_alpha, carry.ok, evicted,
                        metrics)


def _rows_nonfinite(*planes) -> Array:
    """(W,) True where any plane's row holds a non-finite entry."""
    bad = None
    for p in planes:
        axes = tuple(range(1, p.ndim))
        b = ~jnp.all(jnp.isfinite(p), axis=axes)
        bad = b if bad is None else bad | b
    return bad


@layer("ota_receive")
def guarded_ota_round(theta: Array, lam, h, key: Array, rho: float,
                      ccfg, gcfg: GuardConfig, *,
                      power_control: bool = True,
                      mask: Optional[Array] = None,
                      h_tx=None,
                      min_reduce_fn=None,
                      block_cols: Optional[int] = None,
                      backend: Optional[str] = None,
                      burst_std: Optional[Array] = None,
                      telemetry=None,
                      ) -> GuardedRound:
    """Guarded twin of :func:`transport.ota_round_fused` for the flat
    ``(W, d)`` and packed ``(W, D)`` paths.  On a healthy round (no burst,
    finite planes, SNR above floor) the result is BITWISE the unguarded
    monolithic fused round — the guard only adds the O(d) health check.

    The worker-chunk streaming knob is intentionally not consumed here:
    retransmission reuses the one-shot ``(y, p2, energy)`` stats, which the
    cohort scan does not expose mid-stream.  Guarded + streamed cohorts is
    a ROADMAP item-2 composition.
    """
    W = theta.shape[0]
    d = theta.size // W
    budget = ccfg.transmit_power * d

    def stats_fn(m):
        y, p2, e, _ = transport.ota_round_stats(
            theta, lam, h, rho, mask=m, h_tx=h_tx, backend=backend,
            block_cols=block_cols)
        return y, p2, e

    def inv_alpha_fn(energy, m, attempt):
        if not power_control:
            return jnp.asarray(1.0, jnp.float32)
        b = power.retry_power_budget(budget, attempt, gcfg.power_backoff)
        return transport.inv_alpha_from_energy(
            energy, b, min_reduce_fn=min_reduce_fn, mask=m)

    def noise_fn(k):
        return transport.matched_filter_noise_re(k, theta.shape[1:], ccfg)

    def demod_fn(y, p2, n_eff):
        return transport.demodulate(y, p2, n_eff, 1.0, backend=backend)

    def offender_fn(_m):
        planes = [h.re, h.im]
        if h_tx is not None:
            planes += [h_tx.re, h_tx.im]
        return _rows_nonfinite(*planes)

    from repro import obs as _obs
    return guarded_receive(key, gcfg, stats_fn=stats_fn,
                           inv_alpha_fn=inv_alpha_fn, noise_fn=noise_fn,
                           demod_fn=demod_fn, mask=mask, n_workers=W,
                           burst_std=burst_std, offender_fn=offender_fn,
                           telemetry=_obs.resolve(telemetry))
