"""repro.obs — zero-overhead-when-off observability for OTA-FL rounds.

Three tiers, matching the three places a run can be observed:

1. **In-graph telemetry** (``TelemetryConfig``): channel/round statistics
   computed *inside* the jitted round from values the fused receive
   already has in registers (no extra dispatches), threaded through
   ``transport.ota_round_fused`` → ``admm.afadmm_round`` /
   ``tree_ota.ota_tree_round_*`` → ``AFadmm`` → the trainers.  With
   telemetry off (the default everywhere) every path is bitwise the
   pre-obs code; with telemetry on the *training math* is unchanged —
   only extra metric leaves ride the scan carry.
2. **Structured run logs** (``repro.obs.sink.MetricsSink``): one JSONL
   event per round plus a run manifest under ``--run-dir``.
3. **Profiling hooks** (``repro.obs.profiling``): ``jax.profiler`` trace
   annotations, wall-clock spans with a compile/execute split, named
   layer scopes (``layer``, vocabulary ``LAYERS`` below) that label the
   compiled step's ops by the layer they belong to, and an HLO compile
   report built on ``launch.hlo_analysis``.

Canonical metric-key schema
---------------------------

Every per-round metrics dict is a flat ``str -> scalar-or-(W,)-vector``
mapping.  Keys are namespaced by producer; ``merge_disjoint`` is the ONE
place collisions are rejected, so a producer can never silently clobber
another's keys:

``(no prefix)`` — ADMM/trainer math (always present):
    ``loss``             mean (sketched) / last (replicated) worker loss
    ``primal_residual``  mean ||theta_w - Theta||
    ``dual_residual``    rho * ||Theta - Theta_prev||
    ``inv_alpha``        receive-side 1/sqrt(alpha_min) equaliser gain
    ``channel_uses``     cumulative real-dimension channel uses
    ``participation``    fraction of workers transmitting this round
    ``theta_drift``      RMS gap between local models and consensus
    ``grad_norm``        (analog-GD paths) global gradient norm

``fault/`` — fault-injection events (``repro.faults.plan``; present when
a ``FaultPlan`` is active):
    ``fault/alive``      workers not permanently crashed
    ``fault/stragglers`` workers uploading a stale snapshot this round
    ``fault/corrupt``    workers with corrupted (NaN/Inf/spike) uploads
    ``fault/burst``      1.0 when a PS interference burst hit this round

``guard/`` — round health-guard verdicts (``repro.faults.guards``;
present when a ``GuardConfig`` is active):
    ``guard/ok_first``   attempt-0 receive passed the health check
    ``guard/retries``    retransmission attempts consumed
    ``guard/snr_db``     effective receive SNR of the accepted attempt
    ``guard/healthy``    final verdict (round committed vs skipped)
    ``guard/evicted``    workers evicted by the offender policy

``obs/`` — channel telemetry (present when ``TelemetryConfig`` is on):
    ``obs/rx_snr_db``    effective receive SNR:  10 log10(sum y^2 /
                         sum (noise * inv_alpha)^2), the guard's exact
                         division-free formula
    ``obs/min_alpha``    min-alpha transmit power scale actually applied
                         (0.0 when nobody transmitted)
    ``obs/tx_energy``    per-worker transmit energy alpha * sum|h s|^2,
                         a (W,) VECTOR leaf (sinks store it as a list)
    ``obs/active_workers``  number of workers transmitting this round
    ``obs/theta_update_norm``  l2 norm of the committed Theta update
    ``obs/chan_redraw``  1.0 on a round whose fading step crossed a
                         coherence boundary (redrew), else 0.0 (the
                         LLM trainers, ``train.llm_trainer``)
    ``obs/cohort_size``  workers sampled this round (population/cohort
                         sampling active — ``core.cohort``)
    ``obs/population_sampled_frac``  cohort / population

Keys starting with ``_`` (e.g. ``_fault_aux``) are private plumbing that
callers pop before metrics reach a sink.

Layer scopes (``LAYERS``)
------------------------

``repro.obs.profiling.layer(name)`` opens ``jax.named_scope(name)``; the
name lands in every HLO instruction's ``metadata={op_name=...}`` as one
path segment (wrapped by transforms, e.g. ``transpose(jvp(penalty))``),
at no run-time cost.  A device trace names ops by instruction, so the
metadata maps each op's device time to a layer.  Five top-level scopes,
which never nest in one another:

    ``chan_step``    the fading/phy step (``tree_ota.step_channel_packed``,
                     ``step_channel_tree``, ``phy.scenario.Scenario.step``)
    ``local_steps``  the workers' local-step ``lax.scan`` (trainers)
    ``ota_pack``     packing θ/Θ/λ/h into and out of the (W, D) planes
                     (``core.packing`` packers)
    ``ota_receive``  modulate → power-scale → superpose → noise →
                     demodulate (``transport.ota_round_fused``,
                     ``ota_uplink``, ``receive``, the shard-local and
                     guarded receives)
    ``ota_dual``     the dual update (``transport.dual_update``)

and two nested ones:

    ``penalty``      the prox penalty gradient, inside ``local_steps``
                     (``tree_ota.tree_penalty_grad``)
    ``ota_noise``    the matched-filter noise draw, inside ``ota_receive``

A scope opened inside another top-level scope (a packer the receive
calls, a receive inside a receive) or a nested scope outside its parent
opens nothing: the enclosing layer owns that work.

JSONL event schema (one object per line, ``metrics.jsonl``):
    ``{"event": "round",  "round": r, "metrics": {key: float|[float]}}``
    ``{"event": "block",  "round": r, "seconds": s, "rounds": n}``
    ``{"event": "resume", "round": r}``
    ``{"event": "done",   "rounds": n, "seconds": s}``
non-finite values are serialised as ``null``.  The manifest
(``manifest.json``) records the resolved FLConfig, ADMM/channel knobs,
mesh shape, backend, git SHA, and host.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

__all__ = ["TelemetryConfig", "resolve", "is_on", "merge_disjoint",
           "LAYERS"]

#: the layer scopes of ``repro.obs.profiling.layer``: name -> the top-level
#: scope it nests in (None for the five top-level scopes)
LAYERS: Dict[str, Optional[str]] = {
    "chan_step": None,
    "local_steps": None,
    "ota_pack": None,
    "ota_receive": None,
    "ota_dual": None,
    "penalty": "local_steps",
    "ota_noise": "ota_receive",
}


@dataclasses.dataclass(frozen=True)
class TelemetryConfig:
    """In-graph telemetry knobs.

    ``enabled``    master switch; ``False`` is bitwise the pre-obs path.
    ``per_worker`` emit the (W,) ``obs/tx_energy`` vector leaf (off →
                   only scalar telemetry keys).
    """

    enabled: bool = True
    per_worker: bool = True


def resolve(tel: Any) -> Optional[TelemetryConfig]:
    """Normalise a telemetry knob (None/bool/TelemetryConfig) to either a
    live ``TelemetryConfig`` or ``None`` (off)."""
    if tel is None or tel is False:
        return None
    if tel is True:
        return TelemetryConfig()
    if isinstance(tel, TelemetryConfig):
        return tel if tel.enabled else None
    raise TypeError(f"telemetry must be None, bool or TelemetryConfig, "
                    f"got {type(tel).__name__}")


def is_on(tel: Any) -> bool:
    return resolve(tel) is not None


def merge_disjoint(dst: Dict[str, Any], *srcs: Dict[str, Any],
                   who: str = "metrics") -> Dict[str, Any]:
    """Merge metric dicts, rejecting key collisions.

    THE single disjointness assertion of the metric-key schema: every
    producer merge (ADMM + guard + fault + obs) goes through here, so a
    new key can never silently clobber an existing one.  Keys are static
    python strings, so this check costs nothing inside jit.
    """
    out = dict(dst)
    for src in srcs:
        clash = out.keys() & src.keys()
        if clash:
            raise ValueError(
                f"{who}: metric key collision {sorted(clash)} — namespace "
                f"the producer's keys (see repro.obs docstring)")
        out.update(src)
    return out
