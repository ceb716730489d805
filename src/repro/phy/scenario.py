"""Wireless scenario engine: composable channel dynamics + participation.

A :class:`Scenario` is a pure ``(init, step)`` pair producing a
:class:`PhyState` over the packed ``(W, D)`` index space — a
``TreeChannel``-compatible pytree (same ``.h`` / ``.age`` fields) extended
with everything the paper argues about but the legacy substrate could not
express:

* **time-correlated fading** — Gauss–Markov/Jakes-Doppler recurrence
  (``phy.fading``); the legacy block-fading model is the ``rho = 0``
  special case and is reproduced *bitwise* (pinned test).
* **geometry** — log-distance path loss + log-normal shadowing from
  per-worker positions, random-waypoint mobility (``phy.geometry``).
* **imperfect CSI** — workers precode with ``h_hat = h + CN(0, σ_e²)``
  while the air applies ``h`` (``phy.csi``).
* **deep-fade truncation** — the paper-style participation rule: a worker
  whose RMS channel amplitude ``sqrt(mean_i |h_{n,i}|²)`` falls below
  ``h_min`` skips the round (transmits nothing, dual frozen).  Under the
  frequency-flat presets the RMS is exactly the scalar ``|h_n|``, i.e. the
  classic truncated-channel-inversion threshold of refs [9-11].  The
  decision is made on what the worker *knows*: its CSI ``h_hat`` when CSI
  is imperfect, the true ``h`` otherwise.

Presets (``make_scenario(name, ccfg)``):

======================  =====================================================
``static-iid``          one Rayleigh draw, frozen forever (convergence theory)
``block-fading``        today's default — bit-identical to ``core.channel``
``markov-doppler``      AR(1) fading, ``rho = J0(2π f_d T_slot)``, per round
``urban-mobility``      markov fading × path loss × shadowing × waypoint walk
``deep-fade-truncation``frequency-flat markov fading + ``|h| < h_min`` dropout
======================  =====================================================
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import cplx
from repro.core.channel import ChannelConfig, rayleigh
from repro.core.cplx import Complex
from repro.phy import csi as _csi
from repro.phy import fading as _fading
from repro.phy import geometry as _geo
from repro.phy import population as _pop
from repro.phy.geometry import GeometryConfig
from repro.obs.profiling import layer

Array = jax.Array

#: "never" for the static preset (int32-safe round counter headroom)
STATIC_COHERENCE = 1 << 30


@dataclasses.dataclass(frozen=True)
class PhyConfig:
    """Static description of one scenario's physics."""

    #: AR(1) fading correlation at coherence boundaries (0 = block fading)
    rho: float = 0.0
    #: rounds per fading update (legacy coherence block; 1 = every round)
    coherence_iters: int = 10
    #: wall-clock slots the physics advances per round (1 = physically
    #: honest but slow: mobility crawls one slot/round, so gain dynamics
    #: are invisible in short runs).  RECORD of what :func:`make_scenario`
    #: already resolved — the k-fold time step is baked into ``rho`` (the
    #: k-slot Doppler update period) and ``geometry.slot_seconds`` (k slots
    #: of waypoint distance) at build time; ``step`` never reads this
    #: field, so setting it on a hand-built PhyConfig alone does nothing.
    slots_per_round: int = 1
    #: worker CSI error std σ_e (0 = perfect CSI)
    csi_err: float = 0.0
    #: participation threshold on the per-worker RMS |h| (0 = everyone
    #: transmits every round)
    h_min: float = 0.0
    #: frequency-flat small-scale fading: one scalar fade per worker,
    #: broadcast over the packed dimension (narrowband links — the regime
    #: where per-worker deep fades actually occur)
    freq_flat: bool = False
    #: large-scale gains + mobility (None = unit gains, no positions)
    geometry: Optional[GeometryConfig] = None
    #: Pallas/jnp backend for the fused fading-step kernel (None = env var)
    backend: Optional[str] = None


class PhyState(NamedTuple):
    """Per-round channel state over the packed ``(W, D)`` index space.

    ``TreeChannel``-compatible (``.h``, ``.age``); optional fields are
    ``None`` (statically, per scenario) when the corresponding physics is
    disabled, so simple scenarios carry no dead buffers through scans.
    """

    h: Complex                       # effective air channel (W, D)
    h_small: Optional[Complex]       # unit-power AR(1) state (None: h is it)
    h_hat: Optional[Complex]         # worker-side CSI (None: perfect)
    gain: Optional[Array]            # (W,) linear power gains
    shadow: Optional[Array]          # (W,) static shadowing factors
    pos: Optional[Array]             # (W, 2) worker positions
    dest: Optional[Array]            # (W, 2) random-waypoint targets
    mask: Optional[Array]            # (W,) bool participation this round
    age: Array                       # int32 rounds since last fading redraw


def h_tx(state: PhyState) -> Complex:
    """The channel the *workers* act on: their CSI if imperfect, else h."""
    return state.h if state.h_hat is None else state.h_hat


def participation_mask(h: Complex, h_min: float) -> Array:
    """Paper-style truncation: sqrt(mean_i |h_{n,i}|²) >= h_min -> (W,) bool.

    For frequency-flat fading the RMS equals the scalar ``|h_n|``, so this
    is exactly the ``|h| < h_min ⇒ skip`` rule.
    """
    rms = jnp.sqrt(jnp.mean(cplx.abs2(h), axis=-1))
    return rms >= h_min


def _broadcast_flat(h_small: Complex, d: int) -> Complex:
    """(W, 1) scalar fades -> (W, d) planes (transport kernels flatten the
    planes, so they need real equal-shape arrays, not lazy broadcasts)."""
    W = h_small.re.shape[0]
    return Complex(jnp.broadcast_to(h_small.re, (W, d)),
                   jnp.broadcast_to(h_small.im, (W, d)))


@dataclasses.dataclass(frozen=True)
class Scenario:
    """A named, immutable scenario: pure ``init``/``step`` over PhyState."""

    name: str
    cfg: PhyConfig

    # -- static structure queries (decide pytree layout & key budget) ------
    @property
    def truncating(self) -> bool:
        return self.cfg.h_min > 0.0

    @property
    def imperfect_csi(self) -> bool:
        return self.cfg.csi_err > 0.0

    @property
    def has_geometry(self) -> bool:
        return self.cfg.geometry is not None

    @property
    def mobile(self) -> bool:
        g = self.cfg.geometry
        return g is not None and g.speed_mps > 0.0

    @property
    def _plain_fading(self) -> bool:
        """True when the only randomness is the fading draw — then the
        incoming key feeds it whole, bit-matching ``core.channel``."""
        return not (self.has_geometry or self.imperfect_csi)

    def _keys(self, key: Array) -> Tuple[Array, Array, Array]:
        if self._plain_fading:
            return key, key, key  # geometry/csi keys unused
        kf, kg, kc = jax.random.split(key, 3)
        return kf, kg, kc

    def changed(self, state: PhyState) -> Array:
        """Scalar bool: did the channel *discontinuously* redraw this round?

        This drives the flat path's flip rule (``flip_on_change``), whose
        premise is a fresh i.i.d. block at a coherence boundary — workers
        keep θ and phase-flip λ to re-align with the NEW channel.  Only the
        ``rho = 0`` redraw is such a discontinuity: AR(1) mixing
        (``rho > 0``) and mobility drift the channel *continuously*, and
        the dual update tracks them on its own — flagging them would fire
        the flip every round and freeze θ permanently."""
        if self.cfg.rho > 0.0:
            return jnp.zeros((), bool)
        return state.age == 0

    # -- dynamics ----------------------------------------------------------
    def init(self, key: Array, n_workers: int, d: int) -> PhyState:
        cfg = self.cfg
        kf, kg, kc = self._keys(key)
        shape = (n_workers, 1) if cfg.freq_flat else (n_workers, d)
        h_small = rayleigh(kf, shape)

        gain = shadow = pos = dest = None
        if self.has_geometry:
            kp, ks = jax.random.split(kg)
            pos, dest = _geo.init_positions(kp, n_workers, cfg.geometry)
            shadow = _geo.shadowing(ks, n_workers, cfg.geometry)
            gain = _geo.worker_gains(pos, shadow, cfg.geometry)

        return self._assemble(kc, h_small, gain, shadow, pos, dest,
                              jnp.zeros((), jnp.int32), d)

    @layer("chan_step")
    def step(self, key: Array, state: PhyState) -> PhyState:
        cfg = self.cfg
        if (cfg.coherence_iters >= STATIC_COHERENCE and self._plain_fading
                and not self.mobile):
            # static-iid: the channel never moves — skip the (W, D) draw
            # the coherence gate would discard anyway
            return state._replace(age=state.age + 1)
        kf, kg, kc = self._keys(key)
        h_small = state.h if state.h_small is None else state.h_small

        gain, shadow, pos, dest = (state.gain, state.shadow, state.pos,
                                   state.dest)
        if self.mobile:
            # the whole population's physics in one call: fading + waypoint
            # mobility + on-arrival shadowing redraw + path gain.  On the
            # pallas backend with a frequency-flat channel this is ONE
            # kernel launch over the flat (N,) planes (phy.population);
            # the jnp path composes the exact chain that used to live here.
            h_small, age, pos, dest, shadow, gain = _pop.population_step(
                kf, kg, h_small, state.age, pos, dest, shadow, cfg.geometry,
                rho=cfg.rho, coherence_iters=cfg.coherence_iters,
                backend=cfg.backend)
        else:
            h_small, age, _redraw = _fading.correlated_step(
                kf, h_small, state.age, cfg.rho, cfg.coherence_iters,
                backend=cfg.backend)

        d = state.h.re.shape[-1]
        return self._assemble(kc, h_small, gain, shadow, pos, dest, age, d)

    def _assemble(self, kc: Array, h_small: Complex, gain, shadow, pos,
                  dest, age: Array, d: int) -> PhyState:
        """Derive (h, h_hat, mask) from the independent state components."""
        cfg = self.cfg
        if cfg.freq_flat:
            # narrowband: the link has ONE coefficient per worker, so the
            # CSI error is ONE draw per worker (on the (W, 1) scalar, before
            # broadcast) — a per-element draw would both vanish from the
            # RMS truncation statistic at large D and have workers precode
            # each element against a different estimate
            h_narrow = (cplx.scale(h_small, jnp.sqrt(gain)[:, None])
                        if gain is not None else h_small)
            hat_narrow = (_csi.estimate(kc, h_narrow, cfg.csi_err)
                          if self.imperfect_csi else None)
            h = _broadcast_flat(h_narrow, d)
            h_hat = (None if hat_narrow is None
                     else _broadcast_flat(hat_narrow, d))
            # the (W, 1) plane carries the mask's full information — don't
            # RMS-reduce D identical broadcast columns on the hot path
            known = h_narrow if hat_narrow is None else hat_narrow
        else:
            h = (cplx.scale(h_small, jnp.sqrt(gain)[:, None])
                 if gain is not None else h_small)
            h_hat = _csi.estimate(kc, h, cfg.csi_err) \
                if self.imperfect_csi else None
            known = h if h_hat is None else h_hat
        # the truncation decision is the WORKER's: it only knows its CSI,
        # so under imperfect CSI the rule runs on h_hat, not the true h
        mask = participation_mask(known, cfg.h_min) \
            if self.truncating else None
        keep_small = cfg.freq_flat or gain is not None
        return PhyState(h=h, h_small=h_small if keep_small else None,
                        h_hat=h_hat, gain=gain, shadow=shadow, pos=pos,
                        dest=dest, mask=mask, age=age)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

#: preset -> PhyConfig overrides; ``doppler_hz`` resolves to ``rho`` via
#: the Jakes model at build time (rho = J0(2π f_d · slot · coherence)).
PRESETS: Dict[str, Dict[str, Any]] = {
    "static-iid": dict(rho=0.0, coherence_iters=STATIC_COHERENCE),
    "block-fading": dict(rho=0.0),
    "markov-doppler": dict(doppler_hz=50.0, coherence_iters=1),
    "urban-mobility": dict(
        doppler_hz=100.0, coherence_iters=1,
        geometry=GeometryConfig(speed_mps=15.0, shadowing_sigma_db=6.0,
                                pathloss_exp=3.2)),
    "deep-fade-truncation": dict(doppler_hz=50.0, coherence_iters=1,
                                 freq_flat=True, h_min=0.5),
}


def list_scenarios() -> Tuple[str, ...]:
    return tuple(PRESETS)


def make_scenario(name: str, ccfg: Optional[ChannelConfig] = None, *,
                  doppler_hz: Optional[float] = None,
                  csi_err: Optional[float] = None,
                  h_min: Optional[float] = None,
                  coherence_iters: Optional[int] = None,
                  rho: Optional[float] = None,
                  geometry: Optional[GeometryConfig] = None,
                  freq_flat: Optional[bool] = None,
                  slots_per_round: Optional[int] = None,
                  backend: Optional[str] = None) -> Scenario:
    """Build a preset scenario, with per-experiment overrides.

    ``ccfg`` supplies the slot length (Doppler → rho conversion) and the
    default coherence block; explicit keyword overrides win over the preset,
    which wins over the ``ChannelConfig`` defaults.

    There is ONE slot clock: the geometry's ``slot_seconds`` is overridden
    with the same slot the Doppler conversion uses, so fading decorrelation
    and waypoint mobility always advance in lock-step (a ``ChannelConfig``
    slot override would otherwise silently desynchronise them).
    ``slots_per_round`` scales that shared clock: one round advances
    ``k`` slots of physics (waypoint distance AND Doppler update period),
    so gains evolve visibly in short runs.
    """
    if name not in PRESETS:
        raise ValueError(
            f"unknown scenario {name!r}; want one of {list_scenarios()}")
    p = dict(PRESETS[name])
    spr = int(slots_per_round if slots_per_round is not None
              else p.get("slots_per_round", 1))
    if spr < 1:
        raise ValueError(f"slots_per_round must be >= 1, got {spr}")
    slot = (ccfg.slot_seconds if ccfg is not None else 1e-3) * spr
    coh = coherence_iters if coherence_iters is not None else p.get(
        "coherence_iters", ccfg.coherence_iters if ccfg is not None else 10)

    f_d = doppler_hz if doppler_hz is not None else p.get("doppler_hz")
    if rho is not None:
        rho_val = float(rho)
    elif f_d is not None:
        rho_val = _fading.doppler_rho(f_d, slot * coh)
    else:
        rho_val = float(p.get("rho", 0.0))

    geom = geometry if geometry is not None else p.get("geometry")
    if geom is not None and geom.slot_seconds != slot:
        geom = dataclasses.replace(geom, slot_seconds=slot)

    cfg = PhyConfig(
        rho=rho_val,
        coherence_iters=int(coh),
        csi_err=float(csi_err if csi_err is not None else p.get("csi_err", 0.0)),
        h_min=float(h_min if h_min is not None else p.get("h_min", 0.0)),
        freq_flat=bool(freq_flat if freq_flat is not None
                       else p.get("freq_flat", False)),
        geometry=geom,
        slots_per_round=spr,
        backend=backend,
    )
    return Scenario(name=name, cfg=cfg)
