"""Pytree-level A-FADMM: the production integration of the paper's protocol.

``core.admm`` works on flat ``(W, d)`` vectors (the paper's own scale);
LLM-scale parameters are pytrees whose leaves carry a leading worker dim
``W`` sharded over the mesh ``data`` axis.  The OTA math is elementwise, so
the pytree round *packs* the leaves into one contiguous ``(W, D)`` f32
buffer (:mod:`repro.core.packing`) and runs the flat transport path on it —
exactly one fused receive kernel chain, one matched-filter noise draw, and
one min-α consensus per round, however many leaves the model has.  The
historical per-leaf loop survives as :func:`ota_tree_round_leafwise` (the
reference the packed path is pinned against).

Fading is drawn per (worker, element) exactly as before; OTA arithmetic
runs in f32 regardless of param dtype (the analog signal path), duals are
f32.
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import cplx, transport
from repro.core.admm import AdmmConfig
from repro.core.channel import ChannelConfig, rayleigh
from repro.core.cplx import Complex
from repro.core.packing import (ShardPackSpec, build_packspec, pack,
                                pack_cplx, pack_shard_local, scatter_b_chunk,
                                scatter_c_chunk, scatter_rep_chunk,
                                shard_b_chunk, shard_c_chunk,
                                shard_rep_chunk, shard_valid_mask, unpack,
                                unpack_cplx, unpack_shard_local)
from repro.obs import merge_disjoint, resolve as resolve_telemetry
from repro.obs.profiling import layer

Array = jax.Array
PyTree = Any


class TreeChannel(NamedTuple):
    h: PyTree       # Complex leaves (W,) + leaf_shape, f32 — or ONE packed
                    # Complex (W, D) buffer (persistently-packed trainers)
    age: Array      # int32 scalar


class TreeFLState(NamedTuple):
    theta: PyTree   # param pytree, leaves (W, ...) — always a tree
    lam: PyTree     # Complex leaves (W, ...) f32, or ONE packed Complex (W, D)
    Theta: PyTree   # global model, leaves (...)
    chan: TreeChannel
    opt: Any        # per-worker local optimizer state (leaves (W, ...))
    step: Array
    #: ``repro.faults`` fault-process state (worker liveness, straggler
    #: snapshot in the packed/shard-packed layout); None when fault
    #: injection is off.
    flt: Any = None


def _is_cplx(x) -> bool:
    return isinstance(x, Complex)


def _zmap(fn: Callable, *trees: PyTree) -> PyTree:
    """tree.map that treats :class:`Complex` as a leaf in EVERY argument.

    Mixed trees (plain-array leaves vs Complex leaves) share theta's
    structure, so we zip their flattened leaves positionally.
    """
    flats = [jax.tree_util.tree_flatten(t, is_leaf=_is_cplx)[0] for t in trees]
    treedef = jax.tree_util.tree_structure(trees[0], is_leaf=_is_cplx)
    out = [fn(*args) for args in zip(*flats)]
    return jax.tree_util.tree_unflatten(treedef, out)


def _leaf_keys(key: Array, tree: PyTree) -> list:
    n = len(jax.tree_util.tree_flatten(tree, is_leaf=_is_cplx)[0])
    return list(jax.random.split(key, n))


def init_channel_tree(key: Array, theta_w: PyTree) -> TreeChannel:
    keys = iter(_leaf_keys(key, theta_w))
    h = jax.tree.map(lambda l: rayleigh(next(keys), l.shape), theta_w)
    return TreeChannel(h=h, age=jnp.zeros((), jnp.int32))


@layer("chan_step")
def step_channel_tree(key: Array, chan: TreeChannel,
                      ccfg: ChannelConfig) -> Tuple[TreeChannel, Array]:
    """Redraw every leaf's fading block at coherence boundaries."""
    age = chan.age + 1
    redraw = age >= ccfg.coherence_iters
    keys = iter(_leaf_keys(key, chan.h))

    def upd(h_leaf: Complex) -> Complex:
        fresh = rayleigh(next(keys), h_leaf.re.shape)
        return cplx.cwhere(redraw, fresh, h_leaf)

    h = _zmap(upd, chan.h)
    new_age = jnp.where(redraw, jnp.zeros((), jnp.int32), age)
    return TreeChannel(h=h, age=new_age), redraw


@layer("penalty")
def tree_penalty_grad(theta: PyTree, lam: PyTree, h: PyTree, Theta: PyTree,
                      rho: float) -> PyTree:
    """Leafwise Re{λ*h} + ρ|h|²(θ − Θ), broadcasting Θ over the worker dim."""
    return _zmap(lambda t, l, hh, T: transport.penalty_grad(t, l, hh, T, rho),
                 theta, lam, h, Theta)


def _modulate_tree(theta: PyTree, lam: PyTree, h: PyTree, rho: float,
                   backend: Optional[str] = None) -> PyTree:
    return _zmap(lambda t, l, hh: transport.modulate(t, l, hh, rho,
                                                     backend=backend),
                 theta, lam, h)


def _tree_energy_per_worker(signals: PyTree) -> Array:
    """Σ over all leaves/elements of |s|² per worker -> (W,)."""
    leaves = jax.tree_util.tree_leaves(signals, is_leaf=_is_cplx)
    return sum(transport.worker_energy(s) for s in leaves)


def _tree_size(tree: PyTree) -> int:
    leaves = jax.tree_util.tree_leaves(tree, is_leaf=_is_cplx)
    total = 0
    for l in leaves:
        shape = l.re.shape if isinstance(l, Complex) else l.shape
        n = 1
        for s in shape[1:]:  # skip worker dim
            n *= s
        total += n
    return total


# ---------------------------------------------------------------------------
# persistently-packed dual/fading state (λ, h as (W, D) Complex buffers)
# ---------------------------------------------------------------------------
#
# The packed round below (:func:`ota_tree_round`) still re-packs λ and h from
# their trees every round — two `pack_cplx` calls whose XLA `concatenate`
# lowers single-threaded on CPU (~3–9 ms at D≈400k, ROADMAP PR 2 notes).
# λ and h never need to BE trees: only θ does (the local prox steps run the
# model).  Trainers therefore keep λ/h packed *persistently* in their state
# and use the helpers here; the per-round layout cost drops to one θ pack
# plus cheap slice-views (`unpack_cplx`) of λ/h for the penalty gradient.

def init_channel_packed(key: Array, n_workers: int, d: int) -> TreeChannel:
    """One Rayleigh fading block drawn directly over the packed ``(W, D)``
    index space (a single PRNG draw — the packed twin of
    :func:`init_channel_tree`'s per-leaf draws; same distribution)."""
    return TreeChannel(h=rayleigh(key, (n_workers, d)),
                       age=jnp.zeros((), jnp.int32))


@layer("chan_step")
def step_channel_packed(key: Array, chan: TreeChannel,
                        ccfg: ChannelConfig) -> Tuple[TreeChannel, Array]:
    """Coherence-boundary redraw of a packed fading buffer (one draw)."""
    age = chan.age + 1
    redraw = age >= ccfg.coherence_iters
    fresh = rayleigh(key, chan.h.re.shape)
    h = cplx.cwhere(redraw, fresh, chan.h)
    new_age = jnp.where(redraw, jnp.zeros((), jnp.int32), age)
    return TreeChannel(h=h, age=new_age), redraw


def ota_tree_round_packed_state(theta: PyTree, lam_p: Complex, h_p: Complex,
                                key: Array, acfg: AdmmConfig,
                                ccfg: ChannelConfig, spec,
                                backend: Optional[str] = None,
                                reduce_fn: Optional[Callable[[Array], Array]] = None,
                                min_reduce_fn: Optional[Callable[[Array], Array]] = None,
                                mask: Optional[Array] = None,
                                h_tx_p: Optional[Complex] = None,
                                Theta_prev: Optional[PyTree] = None,
                                fused: Optional[bool] = None,
                                worker_chunk: Optional[int] = None,
                                block_cols: Optional[int] = None,
                                guard=None,
                                faults=None,
                                telemetry=None,
                                cohort_idx: Optional[Array] = None,
                                ) -> Tuple[PyTree, Complex, dict]:
    """One OTA round where the duals/fading are ALREADY packed ``(W, D)``.

    Only θ is packed here (it must stay a tree for the local steps); the
    uplink math is bit-identical to the packed :func:`ota_tree_round` given
    equal values — ``pack_cplx`` of a λ/h tree commutes with keeping the
    buffers packed.  Returns ``(Theta_tree_f32, lam_new_packed, metrics)``.

    Scenario extensions (``repro.phy``): ``mask`` ((W,) participation)
    zeroes truncated workers out of the superposition/min-α and freezes
    their duals; ``h_tx_p`` is the packed worker-side CSI (imperfect CSI);
    ``Theta_prev`` (tree) guards the all-masked degenerate round — with
    nobody transmitting the global model is simply kept.

    ``fused`` (default True) runs the uplink as
    :func:`~repro.core.transport.ota_round_fused` — one pass over the
    worker planes, bitwise identical to the composed
    :func:`~repro.core.transport.ota_uplink` (``fused=False``, kept as the
    benchmark baseline and for callers that need a custom ``reduce_fn``,
    which forces the composed path).  ``worker_chunk``/``block_cols``
    thread the streaming/tiling knobs through (None = the
    ``REPRO_OTA_WORKER_CHUNK`` / ``REPRO_OTA_BLOCK_COLS`` env knobs).

    Fault tolerance (``repro.faults``): ``faults=(plan, rf, stale)``
    substitutes the UPLINKED planes per the round's
    :class:`~repro.faults.plan.RoundFaults` draw (straggler staleness,
    corruption, burst interference) — worker-local state (θ, duals) stays
    truthful, only the air sees the faulted planes.  ``guard`` (a
    :class:`~repro.faults.guards.GuardConfig`) replaces the fused receive
    with the guarded cascade: on a healthy round it is BITWISE the
    unguarded monolithic fused round (``worker_chunk`` is ignored; requires
    ``Theta_prev`` for the skip fallback and the fused path).  An unhealthy
    round that exhausts recovery keeps the previous Θ and freezes every
    dual (the PR 4 all-masked machinery); evicted offenders get their dual
    zeroed.  Aux state the caller must thread back (refreshed stale buffer,
    evicted rows) rides in ``metrics["_fault_aux"]``.

    Cohort sampling (``repro.core.cohort``): with ``cohort_idx`` ((W,)
    int32 indices into the N-worker population) the caller's θ tree is
    ALREADY cohort-width, while λ/h (and ``mask``/``h_tx_p``/fault rows)
    arrive population-width — their cohort rows are gathered here, the
    whole round runs at cohort width, and the dual update / fault aux
    scatter back, with every non-sampled worker's dual frozen by
    construction.  ``cohort_idx=None`` traces the exact pre-cohort round.
    """
    tel = resolve_telemetry(telemetry)
    theta_p = pack(spec, theta)                    # the one layout op per round
    lam_pop = h_pop = stale_pop = None
    n_population = lam_p.re.shape[0]
    if cohort_idx is not None:
        from repro.core import cohort as _cohort
        lam_pop, h_pop = lam_p, h_p
        lam_p = _cohort.take_rows(lam_p, cohort_idx)
        h_p = _cohort.take_rows(h_p, cohort_idx)
        h_tx_p = _cohort.take_rows(h_tx_p, cohort_idx)
        mask = _cohort.take_rows(mask, cohort_idx)
        if faults is not None:
            fplan, rf, stale = faults
            stale_pop = stale
            rf = rf._replace(
                alive=_cohort.take_rows(rf.alive, cohort_idx),
                straggler=_cohort.take_rows(rf.straggler, cohort_idx),
                corrupt=_cohort.take_rows(rf.corrupt, cohort_idx),
                snapshot_due=_cohort.take_rows(rf.snapshot_due, cohort_idx))
            faults = (fplan, rf,
                      _cohort.take_rows(stale, cohort_idx))
    aux = {}
    burst_std = None
    theta_tx_p = theta_p
    if faults is not None:
        from repro.faults import plan as _fplan
        fplan, rf, stale = faults
        theta_tx_p, stale_next = _fplan.apply_uplink(fplan, rf, theta_p,
                                                     stale)
        burst_std = rf.burst_std
        if stale_next is not None:
            aux["stale"] = stale_next
    use_fused = (fused is not False) and reduce_fn is None
    healthy = None
    evicted = None
    guard_metrics = {}
    if guard is not None or burst_std is not None:
        from repro.faults import guards as _fguards
        if not use_fused:
            raise ValueError("round guards/bursts require the fused path "
                             "(fused=True, reduce_fn=None)")
        if guard is not None and Theta_prev is None:
            raise ValueError("guard needs Theta_prev for the skip fallback")
        gcfg = guard if guard is not None else _fguards.GuardConfig()
        gr = _fguards.guarded_ota_round(
            theta_tx_p, lam_p, h_p, key, acfg.rho, ccfg, gcfg,
            power_control=acfg.power_control, mask=mask, h_tx=h_tx_p,
            min_reduce_fn=min_reduce_fn, block_cols=block_cols,
            backend=backend, burst_std=burst_std, telemetry=tel)
        Theta_p, inv_alpha = gr.Theta, gr.inv_alpha
        if guard is not None:   # burst-only: no policy, accept the round
            healthy, evicted = gr.healthy, gr.evicted
            guard_metrics = gr.metrics
            aux["evicted"] = evicted
        else:
            # burst-only: no guard verdicts, but the accepted slot's obs/
            # channel telemetry still applies
            guard_metrics = {k: v for k, v in gr.metrics.items()
                             if k.startswith("obs/")}
    elif use_fused:
        if tel is not None:
            Theta_p, inv_alpha, _, guard_metrics = transport.ota_round_fused(
                theta_tx_p, lam_p, h_p, key, acfg.rho, ccfg,
                power_control=acfg.power_control, mask=mask, h_tx=h_tx_p,
                min_reduce_fn=min_reduce_fn, worker_chunk=worker_chunk,
                block_cols=block_cols, backend=backend, telemetry=tel)
        else:
            Theta_p, inv_alpha, _ = transport.ota_round_fused(
                theta_tx_p, lam_p, h_p, key, acfg.rho, ccfg,
                power_control=acfg.power_control, mask=mask, h_tx=h_tx_p,
                min_reduce_fn=min_reduce_fn, worker_chunk=worker_chunk,
                block_cols=block_cols, backend=backend)
    else:
        Theta_p, inv_alpha = transport.ota_uplink(
            theta_tx_p, lam_p, h_p, key, acfg.rho, ccfg,
            power_control=acfg.power_control, reduce_fn=reduce_fn,
            min_reduce_fn=min_reduce_fn, mask=mask, h_tx=h_tx_p,
            backend=backend)
    h_wkr = h_p if h_tx_p is None else h_tx_p
    # duals update from the worker's TRUE planes: a straggler/corrupter's
    # bookkeeping is healthy even when its transmission was not
    lam_new_p = transport.dual_update(lam_p, h_wkr, theta_p, Theta_p,
                                      acfg.rho, backend=backend)
    metrics = merge_disjoint({"inv_alpha": jnp.asarray(inv_alpha)},
                             guard_metrics, who="ota_tree_round_packed_state")
    freeze = mask
    if evicted is not None:
        freeze = ~evicted if freeze is None else freeze & ~evicted
    if freeze is not None:
        lam_new_p = cplx.cwhere(freeze[:, None], lam_new_p, lam_p)
    if healthy is not None:
        lam_new_p = cplx.cwhere(healthy, lam_new_p, lam_p)
    if evicted is not None:
        lam_new_p = cplx.cwhere(evicted[:, None],
                                cplx.czero(lam_new_p.re.shape,
                                           lam_new_p.re.dtype), lam_new_p)
    if mask is not None:
        metrics["participation"] = jnp.mean(mask.astype(jnp.float32))
    Theta_new = unpack(spec, Theta_p, cast=False)  # analog path stays f32
    keep = None
    if mask is not None or evicted is not None:
        active = jnp.ones((theta_p.shape[0],), bool) if mask is None else mask
        if evicted is not None:
            active = active & ~evicted
        keep = jnp.any(active)
    if healthy is not None:
        keep = healthy if keep is None else keep & healthy
    if keep is not None and Theta_prev is not None:
        Theta_new = jax.tree.map(
            lambda new, old: jnp.where(keep, new, old.astype(new.dtype)),
            Theta_new, Theta_prev)
    if tel is not None and Theta_prev is not None:
        # l2 norm of the COMMITTED consensus update (post keep/skip gating)
        sq = sum(jnp.sum((jnp.asarray(n, jnp.float32)
                          - jnp.asarray(o, jnp.float32)) ** 2)
                 for n, o in zip(jax.tree.leaves(Theta_new),
                                 jax.tree.leaves(Theta_prev)))
        metrics["obs/theta_update_norm"] = jnp.sqrt(sq)
    if cohort_idx is not None:
        from repro.core import cohort as _cohort
        # scatter the cohort's results back over the population buffers:
        # non-sampled duals keep their previous rows (frozen), fault aux
        # (stale snapshots, evictions) lands on the sampled rows only
        lam_new_p = _cohort.put_rows(lam_pop, cohort_idx, lam_new_p)
        if "stale" in aux and stale_pop is not None:
            aux["stale"] = stale_pop.at[cohort_idx].set(aux["stale"])
        if "evicted" in aux:
            aux["evicted"] = jnp.zeros((n_population,), bool).at[
                cohort_idx].set(aux["evicted"])
        if tel is not None:
            metrics = merge_disjoint(
                metrics,
                {"obs/cohort_size": jnp.asarray(
                    float(cohort_idx.shape[0]), jnp.float32),
                 "obs/population_sampled_frac": jnp.asarray(
                     float(cohort_idx.shape[0]) / float(n_population),
                     jnp.float32)},
                who="ota_tree_round_packed_state.cohort")
    if aux:
        metrics["_fault_aux"] = aux
    return Theta_new, lam_new_p, metrics


def ota_tree_round(theta: PyTree, lam: PyTree, h: PyTree, key: Array,
                   acfg: AdmmConfig, ccfg: ChannelConfig,
                   backend: Optional[str] = None,
                   reduce_fn: Optional[Callable[[Array], Array]] = None,
                   min_reduce_fn: Optional[Callable[[Array], Array]] = None,
                   packed: Optional[bool] = None,
                   mask: Optional[Array] = None,
                   h_tx: Optional[PyTree] = None,
                   Theta_prev: Optional[PyTree] = None,
                   fused: Optional[bool] = None,
                   worker_chunk: Optional[int] = None,
                   telemetry=None,
                   ) -> Tuple[PyTree, PyTree, dict]:
    """Uplink + global + dual for one round (post-local-steps), packed.

    The pytree is flattened through a :class:`~repro.core.packing.PackSpec`
    into one contiguous ``(W, D)`` f32 buffer so the round issues exactly
    ONE ``transport.ota_uplink`` (one fused receive kernel chain, one noise
    draw over the packed vector, one min-α consensus) and one dual update —
    regardless of leaf count.  This is the paper-faithful reading of Alg. 1:
    the whole update is a single d-dimensional analog channel use.

    Bit-exactness contract: on a noise-free channel this equals
    :func:`ota_tree_round_leafwise` bitwise (the jnp reference reduces the
    same values in the same worker order).  Under AWGN the *distribution* is
    unchanged but the draw differs: one PRNG sample of shape ``(D,)``
    replaces the historical per-leaf splits — pinned in
    ``tests/test_transport.py``.

    Returns (Theta_new, lam_new, metrics).  theta leaves: (W, ...).

    ``packed`` defaults to the packed path; ``False`` forces the per-leaf
    reference loop.  (The historical ``packed=None`` -> leafwise
    auto-fallback under model-parallel meshes is gone: model-parallel
    callers hold their state in the shard-local layout and run
    :func:`ota_tree_round_shard_local`, which never pays the global
    concatenate this tree-in/tree-out convenience API lowers to.)
    """
    if packed is False:
        return ota_tree_round_leafwise(theta, lam, h, key, acfg, ccfg,
                                       backend=backend, reduce_fn=reduce_fn,
                                       min_reduce_fn=min_reduce_fn,
                                       mask=mask, h_tx=h_tx,
                                       Theta_prev=Theta_prev)
    spec = build_packspec(theta, batch_dims=1)
    Theta_new, lam_new_p, metrics = ota_tree_round_packed_state(
        theta, pack_cplx(spec, lam), pack_cplx(spec, h), key, acfg, ccfg,
        spec, backend=backend, reduce_fn=reduce_fn,
        min_reduce_fn=min_reduce_fn, mask=mask,
        h_tx_p=None if h_tx is None else pack_cplx(spec, h_tx),
        Theta_prev=Theta_prev, fused=fused, worker_chunk=worker_chunk,
        telemetry=telemetry)
    return Theta_new, unpack_cplx(spec, lam_new_p), metrics


def ota_tree_round_leafwise(theta: PyTree, lam: PyTree, h: PyTree, key: Array,
                            acfg: AdmmConfig, ccfg: ChannelConfig,
                            backend: Optional[str] = None,
                            reduce_fn: Optional[Callable[[Array], Array]] = None,
                            min_reduce_fn: Optional[Callable[[Array], Array]] = None,
                            mask: Optional[Array] = None,
                            h_tx: Optional[PyTree] = None,
                            Theta_prev: Optional[PyTree] = None,
                            ) -> Tuple[PyTree, PyTree, dict]:
    """Reference per-leaf round: one receive chain and one noise key per
    leaf (the historical semantics).  Kept as the parity contract for the
    packed path — and for callers that need per-leaf noise reproducibility
    (the per-leaf PRNG schedule is pinned in ``tests/test_transport.py``:
    leaf ``i`` draws its matched-filter noise from
    ``jax.random.split(key, n_leaves)[i]``).

    ``mask``/``h_tx``/``Theta_prev``: same participation/CSI semantics as
    :func:`ota_tree_round_packed_state`, applied per leaf.
    """
    rho = acfg.rho
    h_wkr = h if h_tx is None else h_tx
    with layer("ota_receive"):
        signals = _modulate_tree(theta, lam, h_wkr, rho, backend)

        if acfg.power_control:
            budget = ccfg.transmit_power * _tree_size(signals)
            inv_alpha = transport.inv_alpha_from_energy(
                _tree_energy_per_worker(signals), budget,
                min_reduce_fn=min_reduce_fn, mask=mask)
        else:
            inv_alpha = jnp.asarray(1.0, jnp.float32)

        s_leaves, treedef = jax.tree_util.tree_flatten(signals,
                                                       is_leaf=_is_cplx)
        h_leaves = jax.tree_util.tree_flatten(h, is_leaf=_is_cplx)[0]
        keys = _leaf_keys(key, signals)
        Theta_new = jax.tree_util.tree_unflatten(treedef, [
            transport.receive(s, hh, k, ccfg, inv_alpha,
                              reduce_fn=reduce_fn, mask=mask,
                              backend=backend)
            for s, hh, k in zip(s_leaves, h_leaves, keys)])

    lam_new = _zmap(
        lambda l, hh, t, T: transport.dual_update(l, hh, t, T, rho,
                                                  backend=backend),
        lam, h_wkr, theta, Theta_new)
    metrics = {"inv_alpha": jnp.asarray(inv_alpha)}
    if mask is not None:
        lam_new = _zmap(
            lambda new, old: cplx.cwhere(
                mask.reshape((mask.shape[0],) + (1,) * (new.re.ndim - 1)),
                new, old),
            lam_new, lam)
        metrics["participation"] = jnp.mean(mask.astype(jnp.float32))
        if Theta_prev is not None:
            keep = jnp.any(mask)
            Theta_new = _zmap(
                lambda new, old: jnp.where(keep, new, old.astype(new.dtype)),
                Theta_new, Theta_prev)
    return Theta_new, lam_new, metrics


# ---------------------------------------------------------------------------
# shard-local packed round (model-parallel meshes, inside shard_map)
# ---------------------------------------------------------------------------
#
# Under a model-parallel mesh the packed (W, D) layout above is hostile:
# every model-sharded θ leaf would have to be all-gathered into the
# replicated packed buffer each round and the received Θ scattered back —
# GSPMD reshards all five signal planes per round (measured on the 16x16
# dryrun: compile 55s -> 106s, collective-permutes 452 -> 2107, ~10x HBM).
# The shard-local path packs only the leaf shards RESIDENT on each device
# (:class:`~repro.core.packing.ShardPackSpec`) and runs the fused receive +
# min-α consensus + dual update per shard inside ``shard_map``, with the
# worker superposition a ``psum`` over the data axes and the power consensus
# a ``psum`` (per-worker energy over model shards) + ``pmin`` (over
# workers).  λ/h live persistently in the global shard-packed (W, d_pad)
# layout — sharded P(data, model) — so no signal plane ever crosses the
# model axis.

def _mesh_data_axes(mesh, model_axis: str,
                    fsdp_axis: str = "fsdp") -> Tuple[str, ...]:
    return tuple(a for a in mesh.axis_names
                 if a not in (model_axis, fsdp_axis))


def _shard_grid_axes(mesh, model_axis: str,
                     fsdp_axis: str = "fsdp") -> Tuple[str, ...]:
    """Mesh axes of the (fsdp, model) shard grid, fsdp-major — the axes the
    packed ``d_pad`` dimension shards over (flat shard
    ``j = jf * n_model + jm``)."""
    return tuple(a for a in (fsdp_axis, model_axis) if a in mesh.axis_names)


def _axes_entry(axes: Tuple[str, ...]):
    return axes if len(axes) > 1 else axes[0]


def _shard_theta_specs(sspec: ShardPackSpec, wentry, model_axis: str,
                       worker_dim: bool, fsdp_axis: str = "fsdp"):
    """Per-leaf PartitionSpecs of the (worker-major) tree the shard-local
    round consumes/produces: worker dim over the data axes, the recorded
    model/fsdp shard dims over their mesh axes, everything else
    replicated."""
    from jax.sharding import PartitionSpec as P
    specs = []
    lead = 1 if worker_dim else 0
    for i, (mdim, fdim) in enumerate(zip(sspec.shard_dims,
                                         sspec.fsdp_dims)):
        ax = [None] * (lead + len(sspec.spec.shapes[i]))
        if worker_dim:
            ax[0] = wentry
        if mdim is not None:
            ax[lead + mdim] = model_axis
        if fdim is not None:
            ax[lead + fdim] = fsdp_axis
        specs.append(P(*ax))
    return jax.tree_util.tree_unflatten(sspec.spec.treedef, specs)


def _segs_psum(sspec: ShardPackSpec, plane: Array, jm, jf, model_axis: str,
               fsdp_axis: str = "fsdp"):
    """Rebuild the full B/C/D segments from the per-shard chunks — one
    small ``psum`` each over exactly the axes the segment is split across
    (B over fsdp, C over model, D over both; norm/bias/scalar bytes only).
    Returns ``(b_seg, c_seg, rep_seg)`` (None where the class is empty)."""
    b_seg = c_seg = rep_seg = None
    if sspec.b_leaves:
        b_seg = scatter_b_chunk(sspec, shard_b_chunk(sspec, plane), jf)
        if sspec.n_fsdp > 1:
            b_seg = jax.lax.psum(b_seg, fsdp_axis)
    if sspec.c_leaves:
        c_seg = scatter_c_chunk(sspec, shard_c_chunk(sspec, plane), jm)
        if sspec.n_model > 1:
            c_seg = jax.lax.psum(c_seg, model_axis)
    if sspec.rep_leaves:
        j = jf * sspec.n_model + jm
        rep_seg = scatter_rep_chunk(sspec, shard_rep_chunk(sspec, plane), j)
        axes = tuple(a for a, n in ((fsdp_axis, sspec.n_fsdp),
                                    (model_axis, sspec.n_model)) if n > 1)
        if axes:
            rep_seg = jax.lax.psum(rep_seg, axes if len(axes) > 1
                                   else axes[0])
    return b_seg, c_seg, rep_seg


@layer("ota_pack")
def unpack_cplx_shard_local(sspec: ShardPackSpec, buf: Complex, mesh,
                            model_axis: str = "model",
                            fsdp_axis: str = "fsdp") -> PyTree:
    """Global shard-packed ``(W, d_pad)`` Complex planes -> tree of Complex
    ``(W, ...)`` leaves, each carrying its natural model/fsdp sharding.

    Runs inside ``shard_map`` so every sharded leaf is rebuilt from the
    slice already resident on its device (pure layout ops); only the small
    B/C/replicated segments cross shard axes (one psum each).  This is how
    the trainer reads λ/h slice-views for the penalty gradient without ever
    materialising a replicated (W, D) buffer.
    """
    from jax.sharding import PartitionSpec as P

    daxes = _mesh_data_axes(mesh, model_axis, fsdp_axis)
    saxes = _shard_grid_axes(mesh, model_axis, fsdp_axis)
    wentry = _axes_entry(daxes)

    def body(b: Complex) -> PyTree:
        jm = jax.lax.axis_index(model_axis)
        jf = jax.lax.axis_index(fsdp_axis) if fsdp_axis in saxes \
            else jnp.int32(0)

        def one(plane):
            b_seg, c_seg, rep_seg = _segs_psum(sspec, plane, jm, jf,
                                               model_axis, fsdp_axis)
            return unpack_shard_local(sspec, plane, rep_seg,
                                      b_seg=b_seg, c_seg=c_seg)

        re_l = jax.tree_util.tree_flatten(one(b.re))[0]
        im_l = jax.tree_util.tree_flatten(one(b.im))[0]
        return jax.tree_util.tree_unflatten(
            sspec.spec.treedef,
            [Complex(r, i) for r, i in zip(re_l, im_l)])

    out_specs = _shard_theta_specs(sspec, wentry, model_axis,
                                   worker_dim=True, fsdp_axis=fsdp_axis)
    return jax.shard_map(body, mesh=mesh,
                         in_specs=(P(wentry, _axes_entry(saxes)),),
                         out_specs=out_specs, check_vma=False)(buf)


def ota_tree_round_shard_local(theta: PyTree, lam_p: Complex, h_p: Complex,
                               key: Array, acfg: AdmmConfig,
                               ccfg: ChannelConfig, sspec: ShardPackSpec,
                               mesh, *, backend: Optional[str] = None,
                               mask: Optional[Array] = None,
                               h_tx_p: Optional[Complex] = None,
                               Theta_prev: Optional[PyTree] = None,
                               model_axis: str = "model",
                               fsdp_axis: str = "fsdp",
                               fused: Optional[bool] = None,
                               block_cols: Optional[int] = None,
                               guard=None,
                               faults=None,
                               telemetry=None,
                               ) -> Tuple[PyTree, Complex, dict]:
    """One OTA round with SHARD-LOCAL packing under a model-parallel mesh.

    θ is a (W, ...) tree carrying its natural model shardings; λ/fading are
    the persistent global shard-packed ``(W, d_pad)`` Complex buffers
    (sharded ``P(data, model)``).  Inside ``shard_map`` each device:

    1. packs its resident θ shards (one local concat, no collective),
    2. modulates and superposes its workers' signals — the analog channel
       use is a ``psum`` over the data axes (or a fully fused receive
       kernel with a shard-width grid when the worker axis is local),
    3. joins the min-α power consensus: per-worker energies are ``psum``-ed
       over the model shards (each element is owned by exactly one shard),
       the min over workers is a ``pmin`` over the data axes,
    4. demodulates its ``d_local`` slice of Θ and updates its λ shard.

    Scenario semantics (``mask``/``h_tx_p``/``Theta_prev``) are identical
    to :func:`ota_tree_round_packed_state`: the (W,)-shaped participation
    mask replicates across the model axis, so truncation and imperfect-CSI
    precoding thread through the shard-local uplink unchanged.

    Noise layout: each model shard draws its own matched-filter noise from
    ``fold_in(key, shard_index)`` — same distribution as the packed path's
    single (D,) draw, different PRNG layout (noise-free results are bitwise
    identical to :func:`ota_tree_round_leafwise`, pinned in
    ``tests/test_shard_local.py``).

    ``fused`` (default True) runs step 2–4's worker-plane work as ONE
    :func:`~repro.core.transport.ota_round_stats` pass per shard (modulate +
    energy + mask + superposition + pilot fused; the energy psum / min-α /
    demodulate epilogue never touches the worker planes) — bitwise identical
    to the composed ``fused=False`` body, which is kept as the benchmark
    baseline.

    Fault tolerance (``repro.faults``): ``faults=(plan, rf, stale)`` and
    ``guard`` mirror :func:`ota_tree_round_packed_state`, with SPMD-safe
    differences (both require the fused path):

    * eviction is *proactive*: offender rows (non-finite θ/λ/h planes,
      OR-reduced over the model shards that each hold part of the row) are
      cut from the mask BEFORE the receive, so no collective ever sits
      inside a ``lax.cond`` branch;
    * retransmission attempts are statically unrolled ``where``-selects
      (same fold_in noise keys and power backoff as the packed guard's
      ``while_loop``, so the accepted attempt's bits match what lazy
      retries would have produced);
    * noise AND burst interference draw per model shard
      (``fold_in(key, j)``), the shard-local noise layout;
    * straggler snapshots live in the shard-packed ``(W, d_pad)`` layout
      (``FaultState.stale`` sharded like λ).

    Returns ``(Theta_tree_f32, lam_new_packed, metrics)``.
    """
    from jax.sharding import PartitionSpec as P

    rho = acfg.rho
    daxes = _mesh_data_axes(mesh, model_axis, fsdp_axis)
    saxes = _shard_grid_axes(mesh, model_axis, fsdp_axis)
    sax_entry = saxes if len(saxes) > 1 else saxes[0]
    has_fsdp = fsdp_axis in saxes
    if sspec.n_fsdp > 1 and not has_fsdp:
        raise ValueError(f"spec has n_fsdp={sspec.n_fsdp} but mesh "
                         f"{mesh.axis_names} has no '{fsdp_axis}' axis")
    wentry = _axes_entry(daxes)
    #: worker axis entirely local -> run the fused (masked) receive kernel
    #: per shard instead of composing around a psum
    local_w = all(mesh.shape[a] == 1 for a in daxes)
    use_fused = fused is not False
    has_mask = mask is not None
    has_htx = h_tx_p is not None
    has_guard = guard is not None
    has_faults = faults is not None
    tel = resolve_telemetry(telemetry)
    has_tel = tel is not None
    # the receive-SNR / tx-energy telemetry needs the fused stats; the
    # composed (fused=False) oracle body still gets the worker-free subset
    want_energy_out = (has_tel and use_fused and tel.per_worker
                       and acfg.power_control)
    if (has_guard or has_faults) and not use_fused:
        raise ValueError("round guards/faults require the fused shard-local "
                         "path (fused=True)")
    if has_guard and Theta_prev is None:
        raise ValueError("guard needs Theta_prev for the skip fallback")
    if has_faults:
        fplan, rf, stale = faults
        has_stale = rf.straggler is not None
        has_corrupt = rf.corrupt is not None
        has_burst = rf.burst_std is not None
    else:
        fplan = rf = stale = None
        has_stale = has_corrupt = has_burst = False
    dummy = jnp.zeros((), jnp.float32)

    def body(theta, lam, h, key, mask, h_tx, stale_b, strag, corr, due,
             burst):
        from repro.faults import guards as _fg, plan as _fp
        mask = mask if has_mask else None      # dummies stand in for None
        h_tx = h_tx if has_htx else None
        jm = jax.lax.axis_index(model_axis)
        jf = jax.lax.axis_index(fsdp_axis) if has_fsdp else jnp.int32(0)
        j = jf * sspec.n_model + jm                       # fsdp-major flat
        theta_p = pack_shard_local(sspec, theta, j)       # (W_l, d_local)
        budget = ccfg.transmit_power * sspec.spec.d       # real elements
        theta_tx = theta_p
        stale_next = None
        if has_faults:
            rf_l = _fp.RoundFaults(
                alive=None, straggler=strag if has_stale else None,
                corrupt=corr if has_corrupt else None,
                snapshot_due=due if has_stale else None,
                burst_std=burst if has_burst else None)
            theta_tx, stale_next = _fp.apply_uplink(
                fplan, rf_l, theta_p, stale_b if has_stale else None)
        evicted_l = None
        if has_guard and guard.evicts:
            planes = [theta_tx, lam.re, lam.im, h.re, h.im]
            if h_tx is not None:
                planes += [h_tx.re, h_tx.im]
            # a worker's row spans every shard: OR the local verdicts
            bad = _fg._rows_nonfinite(*planes).astype(jnp.float32)
            bad = jax.lax.psum(bad, sax_entry) > 0.0
            base = jnp.ones(bad.shape, bool) if mask is None else mask
            evicted_l = bad & base
            mask = base & ~evicted_l
        with layer("ota_receive"):
            healthy_l = retries_l = None
            if use_fused:
                # one pass over this shard's worker planes (modulate + energy +
                # mask + superposition + pilot fused); only the O(d_local)
                # epilogue and the scalar/energy consensus collectives remain
                y_l, p2_l, energy_l, _ = transport.ota_round_stats(
                    theta_tx, lam, h, rho, mask=mask, h_tx=h_tx,
                    backend=backend, block_cols=block_cols)
                mrf = None if local_w else (lambda a: jax.lax.pmin(a, daxes))
                energy = (jax.lax.psum(energy_l, sax_entry)
                          if acfg.power_control else None)
                if not local_w:
                    y_l = jax.lax.psum(y_l, daxes)
                    p2_l = jax.lax.psum(p2_l, daxes)
                noise_key = jax.random.fold_in(key, j)
                if has_guard:
                    from repro.core import power as _power

                    def gsum(s):
                        return jax.lax.psum(s, sax_entry)

                    def epi(k, attempt, with_burst):
                        if acfg.power_control:
                            b = _power.retry_power_budget(budget, attempt,
                                                          guard.power_backoff)
                            ia = transport.inv_alpha_from_energy(
                                energy, b, min_reduce_fn=mrf, mask=mask)
                        else:
                            ia = jnp.asarray(1.0, jnp.float32)
                        n = transport.matched_filter_noise_re(k, y_l.shape,
                                                              ccfg)
                        if with_burst:
                            kb = jax.random.fold_in(k, _fg.BURST_SALT)
                            n = n + burst * jax.random.normal(kb, n.shape,
                                                              jnp.float32)
                        n_eff = n * ia
                        Th = transport.demodulate(y_l, p2_l, n_eff, 1.0,
                                                  backend=backend)
                        bad = gsum(jnp.sum((~jnp.isfinite(Th))
                                           .astype(jnp.float32)))
                        ok = bad == 0.0
                        sig = npw = dummy
                        if guard.snr_floor_db is not None or has_tel:
                            sig = gsum(jnp.sum(y_l * y_l))
                            npw = gsum(jnp.sum(n_eff * n_eff))
                        if guard.snr_floor_db is not None:
                            thr = 10.0 ** (guard.snr_floor_db / 10.0)
                            ok &= sig >= thr * npw
                        return Th, ia, ok, sig, npw

                    Theta_p, inv_alpha, ok, sig_g, npw_g = epi(
                        noise_key, jnp.int32(0), has_burst)
                    retries_l = jnp.zeros((), jnp.int32)
                    # statically unrolled retries: SPMD-safe (no collective in
                    # control flow), same keys/backoff a lazy loop would use
                    for a in range(1, guard.retries + 1):
                        ka = jax.random.fold_in(noise_key, _fg.RETRY_SALT + a)
                        Th_a, ia_a, ok_a, sig_a, npw_a = epi(ka, jnp.int32(a),
                                                             False)
                        take = ~ok
                        Theta_p = jnp.where(take, Th_a, Theta_p)
                        inv_alpha = jnp.where(take, ia_a, inv_alpha)
                        sig_g = jnp.where(take, sig_a, sig_g)
                        npw_g = jnp.where(take, npw_a, npw_g)
                        retries_l = retries_l + take.astype(jnp.int32)
                        ok = jnp.where(take, ok_a, ok)
                    healthy_l = ok
                else:
                    if acfg.power_control:
                        inv_alpha = transport.inv_alpha_from_energy(
                            energy, budget, min_reduce_fn=mrf, mask=mask)
                    else:
                        inv_alpha = jnp.asarray(1.0, jnp.float32)
                    noise_re = transport.matched_filter_noise_re(
                        noise_key, y_l.shape, ccfg)
                    if has_burst:
                        kb = jax.random.fold_in(noise_key, _fg.BURST_SALT)
                        noise_re = noise_re + burst * jax.random.normal(
                            kb, noise_re.shape, jnp.float32)
                    Theta_p = transport.demodulate(y_l, p2_l, noise_re,
                                                   inv_alpha, backend=backend)
                    sig_g = npw_g = dummy
                    if has_tel:
                        # y_l is replicated over the data axes here, so the
                        # global power sums reduce over the shard grid only —
                        # the guard's exact gsum
                        n_eff = noise_re * inv_alpha
                        sig_g = jax.lax.psum(jnp.sum(y_l * y_l), sax_entry)
                        npw_g = jax.lax.psum(jnp.sum(n_eff * n_eff), sax_entry)
                e_tx = dummy
                if want_energy_out:
                    alpha = jnp.where(inv_alpha > 0,
                                      1.0 / jnp.maximum(inv_alpha, 1e-38), 0.0)
                    e_tx = energy * (alpha * alpha)
                    if mask is not None:
                        e_tx = jnp.where(mask, e_tx, 0.0)
                h_wkr = h if h_tx is None else h_tx
            else:
                h_wkr = h if h_tx is None else h_tx
                signals = transport.modulate(theta_p, lam, h_wkr, rho,
                                             backend=backend)
                if acfg.power_control:
                    # per-worker TOTAL energy: every element owned by one shard
                    energy = jax.lax.psum(transport.worker_energy(signals),
                                          sax_entry)
                    inv_alpha = transport.inv_alpha_from_energy(
                        energy, budget,
                        min_reduce_fn=None if local_w
                        else (lambda a: jax.lax.pmin(a, daxes)),
                        mask=mask)
                else:
                    inv_alpha = jnp.asarray(1.0, jnp.float32)
                noise_key = jax.random.fold_in(key, j)
                Theta_p = transport.receive(
                    signals, h, noise_key, ccfg, inv_alpha,
                    reduce_fn=None if local_w
                    else (lambda x: jax.lax.psum(jnp.sum(x, axis=0), daxes)),
                    mask=mask, backend=backend)
        # duals update from the worker's TRUE planes (theta_p, not the
        # faulted theta_tx); `mask` already excludes evicted offenders
        lam_new = transport.dual_update(lam, h_wkr, theta_p, Theta_p, rho,
                                        backend=backend)
        if mask is not None:
            lam_new = cplx.cwhere(mask[:, None], lam_new, lam)
        if healthy_l is not None:
            lam_new = cplx.cwhere(healthy_l, lam_new, lam)
        if evicted_l is not None:
            lam_new = cplx.cwhere(evicted_l[:, None],
                                  cplx.czero(lam_new.re.shape), lam_new)
        if sspec.has_padding:
            # padding never re-enters the air: Θ is garbage there, so the
            # dual update would otherwise seed non-zero λ at padded slots
            valid = shard_valid_mask(sspec, j)
            lam_new = cplx.cwhere(valid[None, :], lam_new,
                                  cplx.czero(lam_new.re.shape))
        with layer("ota_pack"):
            b_seg, c_seg, rep_seg = _segs_psum(sspec, Theta_p, jm, jf,
                                               model_axis, fsdp_axis)
            Theta_tree = unpack_shard_local(sspec, Theta_p, rep_seg,
                                            b_seg=b_seg, c_seg=c_seg)
        out = [Theta_tree, lam_new, inv_alpha]
        if has_stale:
            out.append(stale_next)
        if has_guard:
            out += [healthy_l, retries_l]
            if guard.evicts:
                out.append(evicted_l)
        if has_tel and use_fused:
            out += [sig_g, npw_g]
            if want_energy_out:
                out.append(e_tx)
        return tuple(out)

    theta_specs = _shard_theta_specs(sspec, wentry, model_axis,
                                     worker_dim=True, fsdp_axis=fsdp_axis)
    Theta_specs = _shard_theta_specs(sspec, wentry, model_axis,
                                     worker_dim=False, fsdp_axis=fsdp_axis)
    buf_spec = P(wentry, sax_entry)
    in_specs = (theta_specs, buf_spec, buf_spec, P(),
                P(wentry) if has_mask else P(),
                buf_spec if has_htx else P(),
                buf_spec if has_stale else P(),
                P(wentry) if has_stale else P(),
                P(wentry) if has_corrupt else P(),
                P(), P())
    out_specs = [Theta_specs, buf_spec, P()]
    if has_stale:
        out_specs.append(buf_spec)
    if has_guard:
        out_specs += [P(), P()]
        if guard.evicts:
            out_specs.append(P(wentry))
    if has_tel and use_fused:
        out_specs += [P(), P()]
        if want_energy_out:
            out_specs.append(P(wentry))
    outs = jax.shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=tuple(out_specs),
        check_vma=False)(
        theta, lam_p, h_p, key,
        mask if has_mask else dummy,
        h_tx_p if has_htx else dummy,
        stale if has_stale else dummy,
        rf.straggler if has_stale else dummy,
        rf.corrupt if has_corrupt else dummy,
        rf.snapshot_due if has_stale else dummy,
        rf.burst_std if has_burst else dummy)
    outs = list(outs)
    Theta_new, lam_new_p, inv_alpha = outs[:3]
    outs = outs[3:]
    aux = {}
    healthy = evicted = None
    guard_metrics = {}
    if has_stale:
        aux["stale"] = outs.pop(0)
    if has_guard:
        healthy = outs.pop(0)
        guard_metrics["guard/healthy"] = healthy.astype(jnp.float32)
        guard_metrics["guard/retries"] = outs.pop(0).astype(jnp.float32)
        if guard.evicts:
            evicted = outs.pop(0)
            aux["evicted"] = evicted
            guard_metrics["guard/evicted"] = jnp.sum(
                evicted.astype(jnp.float32))
    obs_metrics = {}
    if has_tel:
        ia = jnp.asarray(inv_alpha, jnp.float32)
        obs_metrics["obs/min_alpha"] = jnp.where(
            ia > 0, 1.0 / jnp.maximum(ia, 1e-38), 0.0)
        active = (jnp.ones(lam_p.re.shape[:1], bool) if mask is None
                  else mask)
        if evicted is not None:
            active = active & ~evicted
        obs_metrics["obs/active_workers"] = jnp.sum(
            active.astype(jnp.float32))
        if use_fused:
            sig_g = outs.pop(0)
            npw_g = outs.pop(0)
            obs_metrics["obs/rx_snr_db"] = transport.snr_db_from_power(
                sig_g, npw_g)
            if want_energy_out:
                obs_metrics["obs/tx_energy"] = outs.pop(0)

    metrics = merge_disjoint({"inv_alpha": jnp.asarray(inv_alpha)},
                             guard_metrics, obs_metrics,
                             who="ota_tree_round_shard_local")
    if mask is not None:
        metrics["participation"] = jnp.mean(mask.astype(jnp.float32))
    keep = None
    if mask is not None or evicted is not None:
        active = (jnp.ones(lam_p.re.shape[:1], bool) if mask is None
                  else mask)
        if evicted is not None:
            active = active & ~evicted
        keep = jnp.any(active)
    if healthy is not None:
        keep = healthy if keep is None else keep & healthy
    if keep is not None and Theta_prev is not None:
        Theta_new = jax.tree.map(
            lambda new, old: jnp.where(keep, new, old.astype(new.dtype)),
            Theta_new, Theta_prev)
    if has_tel and Theta_prev is not None:
        sq = sum(jnp.sum((jnp.asarray(n, jnp.float32)
                          - jnp.asarray(o, jnp.float32)) ** 2)
                 for n, o in zip(jax.tree.leaves(Theta_new),
                                 jax.tree.leaves(Theta_prev)))
        metrics["obs/theta_update_norm"] = jnp.sqrt(sq)
    if aux:
        metrics["_fault_aux"] = aux
    return Theta_new, lam_new_p, metrics
