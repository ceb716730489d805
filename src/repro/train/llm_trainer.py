"""Federated LLM training: A-FADMM integrated as the aggregation layer.

Two execution modes (DESIGN.md §4):

* ``replicated`` — paper-faithful.  Every FL worker owns a full (θ_n, λ_n)
  copy; per-worker tensors carry a leading worker dim sharded over the mesh
  ``data`` axis.  Local prox steps run vmapped over workers; one analog OTA
  round (superposition = all-reduce over the worker axis) produces the new
  global model; duals update locally.  Per the paper's Appendix H the
  stochastic variant skips the time-varying flip rule (primal-only updates).
  Duals/fading live persistently packed: one (W, D) Complex buffer each on
  data-parallel meshes, the SHARD-LOCAL (W, d_pad) layout on model-parallel
  meshes (``tree_ota.ota_tree_round_shard_local`` runs the round per model
  shard inside shard_map — no leafwise fallback, scenarios included).

* ``sketched`` — A-FADMM-CS for archs whose per-worker copies exceed HBM
  (qwen1.5-110b, deepseek-v3-671b; the paper's §6 "Large Models" extension).
  One (fsdp×model)-sharded global model; workers are time-multiplexed via a
  ``lax.scan`` (faithful to FL semantics: each worker's local delta is
  computed from its own shard of data).  The delta is hash-count-sketched by
  ONE global codec over the SHARD-LOCAL packed index space
  (``core/packing.ShardPackSpec``): inside ``shard_map`` each (fsdp, model)
  shard packs its resident slice, encodes a partial sketch against the
  canonical global indices (``shard_perm_local``), and one ``psum`` over the
  shard grid yields the global ``(d_s,)`` sketch — no flatten/all-gather of
  the model, no per-leaf codec loop.  The stacked ``(W, d_s)`` sketches then
  ride the SAME packed transport as the replicated mode
  (``tree_ota.ota_tree_round_packed_state``): one fused receive, one dual
  update, phy scenarios (the ``(W,)`` participation mask threads into the
  sketched round), and fault guards — all inherited, not reimplemented.
  Decode is collective-free: each shard gathers its resident coordinates
  from the replicated ``(d_s,)`` consensus and applies the delta to its
  resident base-param slice.

Both modes expose the same ``(init_fn, train_step)`` pair; ``train_step`` is
a pure function of ``(state, batch, key)`` suitable for jit / pjit lowering
on the production mesh.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import cplx, transport
from repro.core.admm import AdmmConfig
from repro.core.channel import ChannelConfig
from repro.core.cplx import Complex
from repro.core.packing import (b_segment_perm, build_packspec,
                                build_shard_packspec, c_segment_perm,
                                pack_shard_local, rep_segment_perm,
                                shard_perm_local, shard_rep_chunk,
                                shard_valid_mask, unpack_cplx,
                                unpack_shard_local)
from repro.core.sketch import decode_shard_local, encode_shard_local
from repro.core.tree_ota import (TreeChannel, TreeFLState, _zmap,
                                 init_channel_packed, init_channel_tree,
                                 ota_tree_round, ota_tree_round_packed_state,
                                 ota_tree_round_shard_local,
                                 step_channel_packed, step_channel_tree,
                                 tree_penalty_grad, unpack_cplx_shard_local)
from repro.models.registry import Model
from repro.models.sharding import shard
from repro.obs import merge_disjoint, resolve as resolve_telemetry
from repro.obs.profiling import layer
from repro.optim.optimizers import adam, sgd

Array = jax.Array
PyTree = Any


@dataclasses.dataclass(frozen=True)
class FLConfig:
    mode: str = "replicated"        # replicated | sketched
    n_workers: int = 4
    local_steps: int = 1
    local_lr: float = 1e-3
    local_optimizer: str = "sgd"    # sgd | adam (adam = 2 extra per-worker copies)
    #: sketched mode: d_s = ceil(packed_size / ratio)
    sketch_ratio: int = 256
    #: step size applied to the decoded global sketch delta
    sketch_lr: float = 1.0
    #: OTA transport backend for every signal primitive: "jnp" | "pallas" |
    #: None (defer to the REPRO_USE_PALLAS env var) — per-experiment, not
    #: env-only.  Pallas is safe in differentiated code: the flash-attention
    #: kernel carries a custom VJP (Pallas backward kernels), so there is no
    #: "pallas transport but jnp grad path" split to manage anymore.
    transport_backend: Optional[str] = None
    #: replicated mode: keep λ/h persistently packed and issue one fused
    #: uplink per round (None/True — the default everywhere; under a
    #: model-parallel mesh the buffers are SHARD-LOCAL packed (W, d_pad)
    #: and the round runs per shard inside shard_map, see
    #: tree_ota.ota_tree_round_shard_local), or keep the per-leaf tree
    #: state + reference loop (False — the semantics oracle).
    packed_uplink: Optional[bool] = None
    #: ``repro.phy`` wireless scenario preset: None keeps the legacy i.i.d.
    #: block-fading channel bit-for-bit; a name from
    #: ``phy.list_scenarios()`` runs the scenario engine over the packed
    #: index space — (W, D) in replicated mode (shard-locally packed under
    #: model-parallel meshes, where the (W,)-shaped masks/gains replicate
    #: across the model axis and force the packed state layout), and the
    #: sketch-space (W, d_s) planes in sketched mode (the participation
    #: mask threads into the sketched round).
    scenario: Optional[str] = None
    #: scenario overrides (None = the preset's value)
    doppler_hz: Optional[float] = None
    csi_err: Optional[float] = None
    h_min: Optional[float] = None
    #: wall-clock slots the scenario advances per round (None = preset's 1);
    #: mobility/Doppler decorrelation speed up accordingly so gain dynamics
    #: are visible in short runs
    slots_per_round: Optional[int] = None
    #: one-pass fused receive (``transport.ota_round_fused``): None/True uses
    #: the fused round on the packed paths (modulate → power-scale →
    #: superpose → AWGN → demodulate over each worker plane ONCE); False
    #: keeps the composed per-primitive chain (the semantics oracle).
    ota_fused: Optional[bool] = None
    #: worker-cohort streaming: 0/None processes all W planes in one pass;
    #: k>0 scans ceil(W/k) cohorts so peak signal memory is O(k·D) — W in
    #: the hundreds-to-thousands.  None defers to REPRO_OTA_WORKER_CHUNK.
    ota_worker_chunk: Optional[int] = None
    #: fused-kernel column tile; None defers to REPRO_OTA_BLOCK_COLS
    ota_block_cols: Optional[int] = None
    #: ``repro.faults.FaultPlan`` — fault injection (worker crash /
    #: straggler staleness / corrupted uplink / burst interference),
    #: replicated mode with the packed state layout.  None keeps the
    #: fault-free trainer bit-for-bit (the fault key is a ``fold_in``
    #: side-branch of the round key, never a ``split``).
    faults: Optional[Any] = None
    #: ``repro.faults.GuardConfig`` — round health guard (Θ finiteness +
    #: receive-SNR floor, skip/retransmit/evict cascade) compiled into the
    #: fused receive.  A healthy guarded round is bitwise the unguarded one.
    guard: Optional[Any] = None
    #: ``repro.obs.TelemetryConfig`` (or True) — in-graph round telemetry:
    #: ``obs/``-prefixed metrics (receive SNR, min-α, per-worker tx energy,
    #: active workers, Θ-update norm) collected inside the round and riding
    #: the existing metrics dict / scan carry.  None/False keeps the trainer
    #: bitwise identical to the telemetry-free build (no extra ops traced).
    telemetry: Optional[Any] = None
    #: population/cohort split (ROADMAP item 2, ``core.cohort``): when
    #: ``population`` is set it supersedes ``n_workers`` as the number of
    #: workers that EXIST — θ/λ/opt/phy/fault state all carry the (N, ...)
    #: leading dim — while only ``cohort`` workers are sampled each round:
    #: their rows are gathered, the local steps + the whole packed uplink
    #: run at cohort width (peak signal memory O(cohort·D) regardless of
    #: N), and θ/λ/opt rows scatter back with non-sampled workers frozen
    #: (exactly the masked-worker semantics).  Batch leaves are
    #: COHORT-width: row i feeds the round's i-th sampled worker.
    #: ``cohort == population`` traces no sampling at all and is bitwise a
    #: ``n_workers=population`` run.  Replicated mode, single-buffer
    #: packed layout only (no shard-local / sketched support yet).
    population: Optional[int] = None
    #: workers sampled per round (requires ``population``)
    cohort: Optional[int] = None
    #: ``core.cohort.POLICIES``: uniform | top-gain | prop-h2
    cohort_policy: str = "uniform"


def _local_opt(flcfg: FLConfig):
    if flcfg.local_optimizer == "adam":
        return adam(flcfg.local_lr)
    return sgd(flcfg.local_lr)


# ---------------------------------------------------------------------------
# replicated mode
# ---------------------------------------------------------------------------

def make_replicated(model: Model, flcfg: FLConfig, acfg: AdmmConfig,
                    ccfg: ChannelConfig, mesh=None):
    """``mesh`` (or the mesh active at build time) decides the dual/fading
    layout: single-device and pure-data meshes keep ONE globally packed
    (W, D) buffer; model-parallel meshes keep the SHARD-LOCAL packed
    (W, d_pad) layout (``ShardPackSpec``) and run the round per shard
    inside ``shard_map`` — scenarios included (the historical
    scenario + model-parallel rejection is gone)."""
    cohort_cfg = None
    if flcfg.population is not None:
        from repro.core import cohort as _cohort
        if flcfg.cohort is None:
            raise ValueError(
                "FLConfig.population sets the worker-population size but "
                "says nothing about the per-round uplink width — set "
                "FLConfig.cohort too (cohort == population disables "
                "sampling bitwise)")
        cohort_cfg = _cohort.CohortConfig(
            population=flcfg.population, cohort=flcfg.cohort,
            policy=flcfg.cohort_policy)
    W = flcfg.population if flcfg.population is not None \
        else flcfg.n_workers
    opt = _local_opt(flcfg)
    tel = resolve_telemetry(flcfg.telemetry)

    if mesh is None:
        from repro.models.sharding import current_mesh
        mesh = current_mesh()
    model_n = dict(mesh.shape).get("model", 1) if mesh is not None else 1
    fsdp_n = dict(mesh.shape).get("fsdp", 1) if mesh is not None else 1

    scn = None
    if flcfg.scenario is not None:
        from repro.phy import make_scenario
        from repro.phy.scenario import h_tx as _phys_h_tx
        if flcfg.packed_uplink is False:
            raise ValueError(
                "FLConfig.scenario runs over the packed (W, D) index space "
                "and requires the packed state layout (packed_uplink != "
                "False)")
        scn = make_scenario(flcfg.scenario, ccfg,
                            doppler_hz=flcfg.doppler_hz,
                            csi_err=flcfg.csi_err, h_min=flcfg.h_min,
                            slots_per_round=flcfg.slots_per_round,
                            backend=flcfg.transport_backend)

    fplan, gcfg = flcfg.faults, flcfg.guard
    if fplan is not None or gcfg is not None:
        if flcfg.packed_uplink is False:
            raise ValueError(
                "FLConfig.faults/guard apply to the packed uplink and "
                "require the packed state layout (packed_uplink != False)")
        from repro import faults as _faults
    if tel is not None and flcfg.packed_uplink is False:
        raise ValueError(
            "FLConfig.telemetry is collected inside the packed receive and "
            "requires the packed state layout (packed_uplink != False)")

    def _packed_state() -> bool:
        """Resolved once at build time; ``train_step`` then reads the layout
        from the state structure itself (so init and step can't disagree).
        θ always stays a tree — the local steps run the model."""
        if scn is not None:
            return True   # the scenario engine IS (W, D)-packed
        if flcfg.packed_uplink is not None:
            return flcfg.packed_uplink
        return True

    #: model-parallel / fsdp mesh + packed state -> shard-local packed
    #: buffers over the 2D (fsdp, model) shard grid
    shard_local = _packed_state() and (model_n > 1 or fsdp_n > 1)

    sampling = cohort_cfg is not None and _cohort.cohort_active(cohort_cfg)
    if sampling:
        if not _packed_state():
            raise ValueError(
                "FLConfig.population/cohort sampling gathers rows of the "
                "packed (N, D) dual/fading buffers and requires the packed "
                "state layout (packed_uplink != False)")
        if shard_local:
            raise ValueError(
                "FLConfig.population/cohort sampling is not supported on "
                "the shard-local packed layout yet — run cohort sampling "
                "on a single-device or pure-data mesh")

    def _shard_spec(theta):
        from repro.launch.shardings import shard_dims_2d
        mdims, fdims = shard_dims_2d(theta, model.cfg, mesh,
                                     multi_pod="pod" in mesh.axis_names)
        return build_shard_packspec(theta, mdims, model_n, batch_dims=1,
                                    fsdp_dims=fdims, n_fsdp=fsdp_n)

    def init_fn(key: Array) -> TreeFLState:
        kp, kc = jax.random.split(key)
        pkeys = jax.random.split(kp, W)
        theta = jax.vmap(model.init)(pkeys)                 # leaves (W, ...)
        theta = jax.tree.map(lambda l: shard(
            l, *(["worker"] + [None] * (l.ndim - 1))), theta)
        Theta = jax.tree.map(
            lambda l: jnp.mean(l.astype(jnp.float32), 0).astype(l.dtype),
            theta)
        flt = None
        if _packed_state():
            # λ/h live packed between rounds: no per-round pack_cplx concat.
            # Shard-local: the packed axis is d_pad wide (per-shard slices
            # concatenated) and sharded over the model axis.
            d = _shard_spec(theta).d_pad if shard_local \
                else build_packspec(theta, batch_dims=1).d
            lam = cplx.czero((W, d), jnp.float32)
            chan = scn.init(kc, W, d) if scn is not None \
                else init_channel_packed(kc, W, d)
            if fplan is not None:
                # straggler snapshots live in the same packed layout as λ
                flt = _faults.init(fplan, W, d)
        else:
            lam = jax.tree.map(
                lambda l: cplx.czero(l.shape, jnp.float32), theta)
            chan = init_channel_tree(kc, theta)
        return TreeFLState(theta=theta, lam=lam, Theta=Theta, chan=chan,
                           opt=opt.init(theta), step=jnp.zeros((), jnp.int32),
                           flt=flt)

    def loss_w(p: PyTree, b: PyTree) -> Array:
        l, _ = model.loss(p, b)
        return l

    def train_step(state: TreeFLState, batch: PyTree, key: Array
                   ) -> Tuple[TreeFLState, dict]:
        """batch leaves: (W, B_local, ...) — worker-major, sharded w->data."""
        packed = isinstance(state.lam, Complex)   # state layout decides
        if packed and not shard_local:
            # the layout was latched at build time; tracing the GLOBAL
            # (W, D) packed round under a model-parallel mesh would quietly
            # recreate the GSPMD reshard storm shard-local packing exists
            # to prevent — fail loudly instead of compiling it
            from repro.models.sharding import current_mesh
            active = current_mesh()
            if active is not None and (
                    dict(active.shape).get("model", 1) > 1
                    or dict(active.shape).get("fsdp", 1) > 1):
                raise ValueError(
                    "train_step traced under a model-parallel mesh but the "
                    "trainer was built without one: pass mesh= to "
                    "make_fl_train (or build inside the mesh context) so "
                    "the state comes up in the shard-local packed layout")
        kc, kn = jax.random.split(key)
        mask = h_tx_p = Theta_prev = None
        spec = sspec = None
        idx = None
        if packed:
            # slice-views of the packed buffers for the leafwise penalty —
            # constant across the local steps, so unpack once per round.
            # Shard-local layout: the unpack runs inside shard_map (each
            # device rebuilds only its resident leaf shards).
            if shard_local:
                sspec = _shard_spec(state.theta)
                unpack_tree = lambda buf: unpack_cplx_shard_local(
                    sspec, buf, mesh)
            else:
                spec = build_packspec(state.theta, batch_dims=1)
                unpack_tree = lambda buf: unpack_cplx(spec, buf)
        if scn is not None:
            chan = scn.step(kc, state.chan)       # PhyState, (N, D)-packed
            h_pack = _phys_h_tx(chan)
            if scn.truncating:
                mask, Theta_prev = chan.mask, state.Theta
            if scn.imperfect_csi:
                h_tx_p = chan.h_hat
        elif packed:
            chan, _changed = step_channel_packed(kc, state.chan, ccfg)
            h_pack = chan.h
        else:
            chan, _changed = step_channel_tree(kc, state.chan, ccfg)
            lam_tree, h_tree = state.lam, chan.h
        theta_run, opt_run = state.theta, state.opt
        if packed:
            lam_pack = state.lam
            if sampling:
                # COHORT_SALT side branch of the ROUND key — the base
                # kc/kn schedule (and every unsampled bit) is untouched,
                # and resume re-derives the cohort from the round index
                # uniform never reads the weight — skip the (N, D) |h|²
                # pass so sampled-round compute stays O(cohort·D) + O(N)
                wgt = _cohort.channel_weight(chan.h) \
                    if cohort_cfg.policy != "uniform" else None
                idx = _cohort.sample_cohort(key, cohort_cfg, weight=wgt)
                # local steps see only the sampled rows: θ/opt/λ/CSI all
                # gather to cohort width before any compute (batch leaves
                # arrive cohort-width already)
                lam_pack = _cohort.take_rows(lam_pack, idx)
                h_pack = _cohort.take_rows(h_pack, idx)
                theta_run = jax.tree.map(lambda l: l[idx], state.theta)
                opt_run = jax.tree.map(
                    lambda l: l if jnp.ndim(l) == 0 else l[idx], state.opt)
            # workers see their CSI everywhere they act: penalty + duals
            lam_tree = unpack_tree(lam_pack)
            h_tree = unpack_tree(h_pack)

        faults_arg = None
        fmetrics = {}
        flt_mid = state.flt
        if fplan is not None:
            # fold_in side-branch of the ROUND key: the fault-free kc/kn
            # schedule (and so every fault-free bit) is untouched
            kf = jax.random.fold_in(key, _faults.FAULT_SALT)
            rf, flt_mid, fmetrics = _faults.draw(fplan, kf, state.flt)
            mask = rf.alive if mask is None else mask & rf.alive
            faults_arg = (fplan, rf, state.flt.stale)
        if fplan is not None or gcfg is not None:
            Theta_prev = state.Theta   # skip fallback / all-crashed keep

        def local_body(carry, _):
            theta, opt_state = carry
            losses, grads = jax.vmap(jax.value_and_grad(loss_w))(theta, batch)
            pen = tree_penalty_grad(theta, lam_tree, h_tree, state.Theta,
                                    acfg.rho)
            g = jax.tree.map(lambda a, b_: a + b_.astype(a.dtype), grads, pen)
            theta, opt_state = opt.update(g, opt_state, theta)
            return (theta, opt_state), jnp.mean(losses)

        with layer("local_steps"):
            (theta, opt_state), losses = jax.lax.scan(
                local_body, (theta_run, opt_run), None,
                length=flcfg.local_steps)

        if shard_local:  # incl. scenarios: (W,) masks replicate over model
            Theta_f32, lam_new, m = ota_tree_round_shard_local(
                theta, state.lam, chan.h, kn, acfg, ccfg, sspec, mesh,
                backend=flcfg.transport_backend, mask=mask, h_tx_p=h_tx_p,
                Theta_prev=Theta_prev, fused=flcfg.ota_fused,
                block_cols=flcfg.ota_block_cols,
                guard=gcfg, faults=faults_arg, telemetry=tel)
        elif packed:  # incl. every scenario: mask/h_tx/guard default to None
            # sampling: θ arrives cohort-width; λ/h/mask/faults stay
            # population-width and the round gathers/scatters their rows
            # around the cohort-width receive (lam_new comes back (N, D)
            # with non-sampled duals frozen)
            Theta_f32, lam_new, m = ota_tree_round_packed_state(
                theta, state.lam, chan.h, kn, acfg, ccfg, spec,
                backend=flcfg.transport_backend, mask=mask, h_tx_p=h_tx_p,
                Theta_prev=Theta_prev, fused=flcfg.ota_fused,
                worker_chunk=flcfg.ota_worker_chunk,
                block_cols=flcfg.ota_block_cols,
                guard=gcfg, faults=faults_arg, telemetry=tel,
                cohort_idx=idx)
        else:
            Theta_f32, lam_new, m = ota_tree_round(
                theta, state.lam, chan.h, kn, acfg, ccfg,
                backend=flcfg.transport_backend, packed=False)
        flt_new = state.flt
        if fplan is not None:
            aux = m.pop("_fault_aux", {})
            flt_new = _faults.commit(flt_mid, aux.get("stale"),
                                     aux.get("evicted"))
        if idx is not None:
            # non-sampled workers keep this round's pre-round θ/opt rows
            # (frozen, like masked workers) — only cohort rows scatter back
            theta = jax.tree.map(lambda full, rows: full.at[idx].set(rows),
                                 state.theta, theta)
            opt_state = jax.tree.map(
                lambda full, rows: rows if jnp.ndim(full) == 0
                else full.at[idx].set(rows), state.opt, opt_state)
        Theta_new = _zmap(lambda T, t: T.astype(t.dtype), Theta_f32, state.Theta)
        if tel is not None and "obs/theta_update_norm" not in m:
            # fault-free rounds never see Theta_prev inside the round, so
            # the round couldn't emit the norm itself — compute it here
            sq = sum(jnp.sum((jnp.asarray(n, jnp.float32)
                              - jnp.asarray(o, jnp.float32)) ** 2)
                     for n, o in zip(jax.tree.leaves(Theta_new),
                                     jax.tree.leaves(state.Theta)))
            m["obs/theta_update_norm"] = jnp.sqrt(sq)
        new_state = TreeFLState(theta=theta, lam=lam_new, Theta=Theta_new,
                                chan=chan, opt=opt_state,
                                step=state.step + 1, flt=flt_new)
        metrics = merge_disjoint(
            {"loss": losses[-1],
             "theta_drift": _tree_rms_gap(theta, Theta_new)},
            m, fmetrics, who="make_replicated.train_step")
        return new_state, metrics

    return init_fn, train_step


def _tree_rms_gap(theta_w: PyTree, Theta: PyTree) -> Array:
    def leaf(t, T):
        d = t.astype(jnp.float32) - T[None].astype(jnp.float32)
        return jnp.sum(d * d), d.size

    parts = jax.tree_util.tree_leaves(
        jax.tree.map(leaf, theta_w, Theta), is_leaf=lambda x: isinstance(x, tuple))
    num = sum(p[0] for p in parts)
    den = float(sum(p[1] for p in parts))
    return jnp.sqrt(num / den)


# ---------------------------------------------------------------------------
# sketched mode (A-FADMM-CS)
# ---------------------------------------------------------------------------

class SketchFLState(NamedTuple):
    Theta: PyTree       # shared global params ((fsdp, model)-sharded)
    lam: Complex        # packed sketch-space duals, (W, d_s) f32
    chan: Any           # TreeChannel / PhyState — h: Complex (W, d_s)
    step: Array
    flt: Any = None     # FaultState (sketch-space layout) or None


#: hash seed of the global packed count-sketch codec
SKETCH_SEED = 17


def _sketch_dim(packed_size: int, ratio: int) -> int:
    if ratio < 1:
        raise ValueError(
            f"FLConfig.sketch_ratio must be a positive compression ratio "
            f"(d_s = ceil(d / ratio)), got {ratio}")
    return max(8, -(-packed_size // ratio))


def make_sketched(model: Model, flcfg: FLConfig, acfg: AdmmConfig,
                  ccfg: ChannelConfig, mesh=None):
    """A-FADMM-CS on the shard-local packed transport.

    The codec is a stage on the shard-local packed index space: under
    ``mesh`` each (fsdp, model) shard of the base params encodes/decodes
    its RESIDENT ``d_local`` slice against the global hashed codec inside
    ``shard_map`` (partial sketches psum over the shard grid; decode is a
    collective-free gather).  The stacked ``(W, d_s)`` sketches then run
    the consensus through :func:`tree_ota.ota_tree_round_packed_state` —
    the same fused one-pass receive, scenario masks, and fault guards as
    the replicated mode.  On a mesh without a dedicated ``fsdp`` axis the
    legacy FSDP-over-data placement of the base params defines the grid
    (the codec's "fsdp" shards ride the data axes — the worker dim lives
    only on the small (W, d_s) planes, never on the params).
    """
    if flcfg.population is not None:
        raise ValueError(
            "FLConfig.population/cohort sampling is a replicated-mode "
            "feature (per-worker θ rows to gather); sketched mode "
            "time-multiplexes workers over one shared model and has no "
            "population state to subsample")
    W = flcfg.n_workers
    ratio = flcfg.sketch_ratio
    backend = flcfg.transport_backend
    tel = resolve_telemetry(flcfg.telemetry)

    if mesh is None:
        from repro.models.sharding import current_mesh
        mesh = current_mesh()
    multi_pod = mesh is not None and "pod" in mesh.axis_names

    scn = None
    if flcfg.scenario is not None:
        from repro.phy import make_scenario
        from repro.phy.scenario import h_tx as _phys_h_tx
        scn = make_scenario(flcfg.scenario, ccfg,
                            doppler_hz=flcfg.doppler_hz,
                            csi_err=flcfg.csi_err, h_min=flcfg.h_min,
                            slots_per_round=flcfg.slots_per_round,
                            backend=backend)

    fplan, gcfg = flcfg.faults, flcfg.guard
    if fplan is not None or gcfg is not None:
        from repro import faults as _faults

    # --- the codec shard grid: how the BASE params are actually sharded ---
    model_axis = "model"
    if mesh is not None:
        from repro.launch.mesh import axis_size as _axis_size
        from repro.launch.shardings import fsdp_axes as _fsdp_axes
        model_n = dict(mesh.shape).get(model_axis, 1)
        faxes = _fsdp_axes(mesh, worker_dim=False, multi_pod=multi_pod)
        fsdp_n = _axis_size(mesh, faxes) if faxes else 1
    else:
        model_n, fsdp_n, faxes = 1, 1, None
    grid = model_n > 1 or fsdp_n > 1
    grid_axes = tuple(a for a in ((model_axis,) + tuple(faxes or ()))
                      if mesh is not None and a in mesh.axis_names) \
        if grid else ()

    def _codec_spec(Theta):
        if grid:
            from repro.launch.shardings import shard_dims_2d
            mdims, fdims = shard_dims_2d(Theta, model.cfg, mesh,
                                         multi_pod=multi_pod,
                                         worker_dim=False)
            return build_shard_packspec(Theta, mdims, model_n,
                                        fsdp_dims=fdims, n_fsdp=fsdp_n)
        n = build_packspec(Theta).n_leaves
        return build_shard_packspec(Theta, (None,) * n, 1)

    def _grid_idx():
        jm = jax.lax.axis_index(model_axis) if model_n > 1 else \
            jnp.zeros((), jnp.int32)
        jf = jnp.zeros((), jnp.int32)
        if faxes and fsdp_n > 1:
            for a in faxes:           # row-major over the fsdp axes tuple
                jf = jf * mesh.shape[a] + jax.lax.axis_index(a)
        return jm, jf

    def _param_specs(sspec):
        from jax.sharding import PartitionSpec as P
        f_entry = (faxes if len(faxes) > 1 else faxes[0]) if faxes else None
        specs = []
        for i, (md, fd) in enumerate(zip(sspec.shard_dims,
                                         sspec.fsdp_dims)):
            ax = [None] * len(sspec.spec.shapes[i])
            if md is not None:
                ax[md] = model_axis
            if fd is not None:
                ax[fd] = f_entry
            specs.append(P(*ax))
        return jax.tree_util.tree_unflatten(sspec.spec.treedef, specs)

    def _seg_valid(n_real: int, n_pad: int) -> Array:
        return jnp.arange(n_pad) < n_real

    def encode_delta(sspec, delta: PyTree, d_s: int) -> Array:
        """Delta tree -> ONE global (d_s,) count sketch, shard-locally."""
        def enc(tree, j):
            buf = pack_shard_local(sspec, tree, j)
            return encode_shard_local(buf, shard_perm_local(sspec, j),
                                      shard_valid_mask(sspec, j),
                                      d_s, SKETCH_SEED)

        if not grid:
            return enc(delta, 0)
        from jax.sharding import PartitionSpec as P

        def body(tree):
            jm, jf = _grid_idx()
            s = enc(tree, jf * sspec.n_model + jm)
            # each canonical element is owned by exactly ONE shard, so the
            # partial sketches sum into the global codec (== encode of the
            # globally packed delta, pinned in tests/test_sketch_codec.py)
            return jax.lax.psum(s, grid_axes)

        return jax.shard_map(body, mesh=mesh,
                             in_specs=(_param_specs(sspec),),
                             out_specs=P(), check_vma=False)(delta)

    def decode_delta(sspec, s: Array) -> PyTree:
        """(d_s,) global sketch -> delta tree in the params' own sharding.

        Collective-free: every shard gathers only its resident coordinates
        (class-A blocks via its local perm, the B/C/replicated segments via
        their static segment perms)."""
        def dec(s, jm, jf):
            j = jf * sspec.n_model + jm
            buf = decode_shard_local(s, shard_perm_local(sspec, j),
                                     shard_valid_mask(sspec, j),
                                     SKETCH_SEED)
            b_seg = c_seg = rep_seg = None
            if sspec.b_leaves:
                b_seg = decode_shard_local(
                    s, b_segment_perm(sspec, jm),
                    _seg_valid(sspec.b_size, sspec.b_pad), SKETCH_SEED)
            if sspec.c_leaves:
                c_seg = decode_shard_local(
                    s, c_segment_perm(sspec, jf),
                    _seg_valid(sspec.c_size, sspec.c_pad), SKETCH_SEED)
            if sspec.rep_leaves:
                rep_seg = decode_shard_local(
                    s, rep_segment_perm(sspec),
                    _seg_valid(sspec.rep_size, sspec.rep_pad), SKETCH_SEED)
            return unpack_shard_local(sspec, buf, rep_seg, b_seg=b_seg,
                                      c_seg=c_seg)

        if not grid:
            return dec(s, jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32))
        from jax.sharding import PartitionSpec as P

        def body(s):
            jm, jf = _grid_idx()
            return dec(s, jm, jf)

        return jax.shard_map(body, mesh=mesh, in_specs=(P(),),
                             out_specs=_param_specs(sspec),
                             check_vma=False)(s)

    def init_fn(key: Array) -> SketchFLState:
        kp, kc = jax.random.split(key)
        Theta = model.init(kp)
        d_s = _sketch_dim(build_packspec(Theta).d, ratio)
        lam = cplx.czero((W, d_s), jnp.float32)
        chan = scn.init(kc, W, d_s) if scn is not None \
            else init_channel_packed(kc, W, d_s)
        flt = _faults.init(fplan, W, d_s) if fplan is not None else None
        return SketchFLState(Theta=Theta, lam=lam, chan=chan,
                             step=jnp.zeros((), jnp.int32), flt=flt)

    def loss_fn(p: PyTree, b: PyTree) -> Array:
        l, _ = model.loss(p, b)
        return l

    def constrain_grads(g: PyTree) -> PyTree:
        """§Perf "rs_grads": pin per-worker grads to the parameter sharding
        so GSPMD reduces them with reduce-scatter (result = one shard) rather
        than all-reducing replicated full gradients."""
        from repro.models.sharding import current_mesh
        from repro.optflags import enabled
        mesh = current_mesh()
        if mesh is None or not enabled("rs_grads"):
            return g
        from repro.launch.shardings import named, tree_pspecs
        specs = tree_pspecs(g, cfg=model.cfg, mesh=mesh, worker_dim=False,
                            fsdp=True, multi_pod="pod" in mesh.axis_names)
        return jax.lax.with_sharding_constraint(g, named(mesh, specs))

    def worker_delta(Theta: PyTree, batch_w: PyTree) -> Tuple[PyTree, Array]:
        """H local steps from the shared global model -> (delta, last_loss)."""
        def body(carry, _):
            theta = carry
            l, g = jax.value_and_grad(loss_fn)(theta, batch_w)
            g = constrain_grads(g)
            theta = jax.tree.map(
                lambda p, gg: p - flcfg.local_lr * gg.astype(p.dtype), theta, g)
            return theta, l

        with layer("local_steps"):
            theta, losses = jax.lax.scan(body, Theta, None,
                                         length=flcfg.local_steps)
        delta = jax.tree.map(
            lambda a, b_: (a - b_).astype(jnp.float32), theta, Theta)
        return delta, losses[-1]

    def train_step(state: SketchFLState, batch: PyTree, key: Array
                   ) -> Tuple[SketchFLState, dict]:
        """batch leaves: (W, B_w, ...) — workers time-multiplexed via scan.

        The per-worker scan only *encodes*: each step computes that
        worker's local delta and its shard-local sketch, stacking the
        ``(W, d_s)`` planes.  The whole analog round — modulate, min-α
        power consensus, ONE fused receive, dual update, participation
        masks, fault guards — is the SAME
        :func:`tree_ota.ota_tree_round_packed_state` the replicated mode
        runs, applied to the sketch stack as a single packed leaf.
        """
        kc, kn = jax.random.split(key)
        d_s = state.lam.re.shape[-1]
        sspec = _codec_spec(state.Theta)        # static per trace

        mask = h_tx_p = None
        if scn is not None:
            chan = scn.step(kc, state.chan)     # PhyState over (W, d_s)
            if scn.truncating:
                mask = chan.mask
            if scn.imperfect_csi:
                h_tx_p = chan.h_hat
        else:
            chan, _ = step_channel_packed(kc, state.chan, ccfg)

        faults_arg = None
        fmetrics = {}
        flt_mid = state.flt
        Theta_prev = None
        if fplan is not None:
            # fold_in side-branch of the ROUND key (fault-free bits intact)
            kf = jax.random.fold_in(key, _faults.FAULT_SALT)
            rf, flt_mid, fmetrics = _faults.draw(fplan, kf, state.flt)
            mask = rf.alive if mask is None else mask & rf.alive
            faults_arg = (fplan, rf, state.flt.stale)
        if mask is not None or gcfg is not None or fplan is not None:
            # a skipped/all-masked round must leave the base params alone:
            # the sketch-space fallback consensus is the ZERO sketch, whose
            # decoded delta is identically zero
            Theta_prev = jnp.zeros((d_s,), jnp.float32)

        def per_worker(_, batch_w):
            delta, l = worker_delta(state.Theta, batch_w)
            return None, (encode_delta(sspec, delta, d_s), l)

        _, (s_w, losses) = jax.lax.scan(per_worker, None, batch)

        # the consensus round in sketch space: s_w IS the packed buffer
        # (identity pack), so the fused one-pass receive, scenario masks and
        # guards apply verbatim — budget = transmit_power * d_s as before
        s_spec = build_packspec(s_w, batch_dims=1)
        Theta_s, lam_new, m = ota_tree_round_packed_state(
            s_w, state.lam, chan.h, kn, acfg, ccfg, s_spec,
            backend=backend, mask=mask, h_tx_p=h_tx_p,
            Theta_prev=Theta_prev, fused=flcfg.ota_fused,
            worker_chunk=flcfg.ota_worker_chunk,
            block_cols=flcfg.ota_block_cols,
            guard=gcfg, faults=faults_arg, telemetry=tel)

        g_delta = decode_delta(sspec, Theta_s)
        Theta_new = jax.tree.map(
            lambda p, dg: p + flcfg.sketch_lr * dg.astype(p.dtype),
            state.Theta, g_delta)

        flt_new = state.flt
        if fplan is not None:
            aux = m.pop("_fault_aux", {})
            flt_new = _faults.commit(flt_mid, aux.get("stale"),
                                     aux.get("evicted"))
        new_state = SketchFLState(Theta=Theta_new, lam=lam_new, chan=chan,
                                  step=state.step + 1, flt=flt_new)
        metrics = merge_disjoint({"loss": jnp.mean(losses)}, m, fmetrics,
                                 who="make_sketched.train_step")
        if tel is not None:
            # report the MODEL-space update norm (sketch_lr · ‖decoded
            # delta‖), superseding any sketch-space norm the round emitted
            sq = sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
                     for l in jax.tree.leaves(g_delta))
            metrics["obs/theta_update_norm"] = flcfg.sketch_lr * jnp.sqrt(sq)
        return new_state, metrics

    return init_fn, train_step


def make_fl_train(model: Model, flcfg: FLConfig, acfg: AdmmConfig,
                  ccfg: ChannelConfig, mesh=None):
    """``mesh`` picks the replicated-mode state layout (shard-local packed
    under a model-parallel mesh); None falls back to the mesh active at
    build time, then to the single-buffer packed layout."""
    if flcfg.scenario is None:
        orphans = {k: getattr(flcfg, k)
                   for k in ("doppler_hz", "csi_err", "h_min",
                             "slots_per_round")
                   if getattr(flcfg, k) is not None}
        if orphans:
            raise ValueError(
                f"FLConfig{tuple(orphans)} are scenario overrides and do "
                "nothing without FLConfig.scenario — set e.g. "
                "scenario='markov-doppler' (refusing to silently ignore "
                "them)")
    if flcfg.population is None and flcfg.cohort is not None:
        raise ValueError(
            "FLConfig.cohort samples from FLConfig.population and does "
            "nothing without it — set population=N too (refusing to "
            "silently ignore it)")
    if flcfg.mode == "replicated":
        return make_replicated(model, flcfg, acfg, ccfg, mesh=mesh)
    if flcfg.mode == "sketched":
        return make_sketched(model, flcfg, acfg, ccfg, mesh=mesh)
    raise ValueError(f"unknown FL mode {flcfg.mode!r}")
