#!/usr/bin/env python3
"""Run the A-FADMM trainer on a TPU and check what it computes.

    python3 chip_smoke.py              # one chip: the main and cross-device phases
    python3 chip_smoke.py --chips 4    # four chips: the sharded layouts only

Each phase runs first on the Pallas path (Mosaic kernels) and then, from
the same seed, on the plain ``jax.numpy`` path, and compares the two
within the tolerances below.

* main: replicated A-FADMM training of granite-8b at its published widths
  (one layer, a slice of the vocabulary): W=2 workers take two local SGD
  steps, then the fused over-the-air round with power control and the dual
  update, jitted with donation as ``repro.launch.train`` jits it.
* cross-device: the flat paper round over the frequency-flat
  ``urban-mobility`` scenario, a 10⁶-worker population and a 256-worker
  cohort, built as ``benchmarks/scaleup.py`` builds it.
* ``--chips 4``: the main-phase model on a (data, fsdp, model) = (1, 2, 2)
  mesh, replicated and then sketched, each compared with the same mode on
  one device.

Compile seconds, seconds per round and peak device memory are printed as
smoke numbers from one short run, not as benchmark metrics.  Everything
runs in this one process.  The last line of standard output is
``{"ok": true, "device": {...}}``; a failed check, or a JAX that finds no
TPU, exits non-zero without it.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import math
import os
import sys
import time
from typing import Optional

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

ARCH = "granite-8b"
#: the published vocabulary is 49,152 rows; one layer and this slice give
#: 243 M parameters, whose state (θ in bf16 and packed complex f32 λ and h
#: for each of the two workers, Θ in bf16: 9.2 GB) and step fit one v5e
#: chip's 16 GB
VOCAB = 6144
SEQ = 2048
WORKERS = 2
LOCAL_STEPS = 2
LOCAL_LR = 1e-2
ROUNDS = 5
#: per-subcarrier SNR, which sets the transmit energy budget per parameter
#: (``ChannelConfig.transmit_power``; the round's budget is that times D).
#: With matched-filter noise std σ and 1/α ≈ rms(s)/sqrt(budget), the
#: noise term z/(α·Σ|h|²) then has about 3σ/sqrt(budget) = 0.3 of Θ's RMS
#: at any D (two Rayleigh workers: E[1/(Σ|h|²)²] ≈ 9 at D ≈ 10⁸), so
#: dropping it, or demodulating with a wrong α, moves Θ far past
#: THETA_RTOL.  The paper's 40 dB leaves it at 0.5%, under bf16 rounding.
SNR_DB = 5.0

#: loss per round, pallas vs jnp: the first local step sees identical
#: weights and tokens, so the losses differ by attention alone — bf16
#: flash attention with f32 online softmax against XLA's bf16 attention
LOSS_RTOL = 1e-2
#: relative RMS of the final Θ difference.  The transport is f32
#: elementwise on both paths, so what differs is the summation order of
#: the energies and the bf16 rounding of θ and Θ (2⁻⁸ relative on the few
#: elements whose f32 values straddle a rounding boundary)
THETA_RTOL = 2e-2
#: 1/α per round: an f32 energy sum over D elements in another order
#: (column-block partials in the stats kernel, a tree reduction in XLA)
ALPHA_RTOL = 1e-3

#: four-chip phase: rounds per run
MESH_ROUNDS = 3
#: the shard-local layout packs parameters in another order and draws its
#: receiver noise per model shard, so the mesh phase runs noise-free with
#: one fading coefficient per worker, and then compares like the main
#: phase; what differs is the order of the model-sharded reductions in the
#: bf16 local steps.  The sketched uplink is the sketch of θ_H − Θ, a small difference
#: of two bf16 trees, so those rounding differences are a larger share of
#: its energy and of 1/α than in the replicated mode
MESH_ALPHA_RTOL = {"replicated": ALPHA_RTOL, "sketched": 1e-2}

#: cross-device phase (``benchmarks/scaleup.py``'s sampled point)
POPULATION = 10**6
COHORT = 256
XD_ROUNDS = 3
#: the flat round is f32 end to end and both paths draw the same random
#: planes; what differs is reduction order and the f32 transcendentals of
#: the population step (Mosaic's and XLA's sqrt/exp/pow), 4e-5 relative
#: on a v5e.  The receiver noise moves Θ by 3e-3 (20 dB, cohort 256)
XD_RTOL = 3e-4


def _log(msg: str) -> None:
    print(msg, flush=True)


@contextlib.contextmanager
def _kernels(on: bool):
    """``REPRO_USE_PALLAS`` is read while tracing (model attention, the
    phy scenario): set it for one run and restore it after."""
    old = os.environ.get("REPRO_USE_PALLAS")
    os.environ["REPRO_USE_PALLAS"] = "1" if on else "0"
    try:
        yield
    finally:
        if old is None:
            os.environ.pop("REPRO_USE_PALLAS", None)
        else:
            os.environ["REPRO_USE_PALLAS"] = old


def _peak_gb(jax) -> float:
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use", float("nan")) / 1e9


def _rel_rms(got, want) -> float:
    num = den = 0.0
    for g, w in zip(got, want):
        g = np.asarray(g, np.float64)
        w = np.asarray(w, np.float64)
        num += float(np.sum((g - w) ** 2))
        den += float(np.sum(w ** 2))
    return math.sqrt(num / den)


def _max_rel(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want) / np.abs(want)))


class Checks:
    """Collects pass/fail lines; the run is ok only if every check was."""

    def __init__(self):
        self.failed = []

    def __call__(self, name: str, ok: bool, detail: str) -> None:
        _log(f"check {'ok  ' if ok else 'FAIL'} {name}: {detail}")
        if not ok:
            self.failed.append(name)

    def close(self, name: str, value: float, tol: float) -> None:
        self(name, bool(value <= tol), f"{value:.3e} <= {tol:.0e}")


def main_model():
    from repro.models.registry import build_model, get_config
    cfg = dataclasses.replace(get_config(ARCH), n_layers=1,
                              vocab_size=VOCAB)
    return build_model(cfg)


def _state_shardings(shapes, cfg, mesh):
    """Where a train state lives on ``mesh``: parameter trees as
    ``repro.launch.shardings`` places them, the packed ``(W, d_pad)``
    planes of the shard-local layout over the (fsdp, model) shard grid its
    transport packs them on, the sketch-space planes and scalars
    replicated."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.shardings import tree_pspecs
    from repro.train.llm_trainer import SketchFLState

    def params(tree, worker_dim):
        return tree_pspecs(tree, cfg, mesh, worker_dim=worker_dim, fsdp=True,
                           multi_pod=False)

    def rep(tree):
        return jax.tree.map(lambda _: P(), tree)

    if isinstance(shapes, SketchFLState):
        spec = shapes._replace(Theta=params(shapes.Theta, False),
                               lam=rep(shapes.lam), chan=rep(shapes.chan),
                               step=P())
    else:
        plane = lambda x: P("data", ("fsdp", "model")) if x.ndim == 2 else P()
        spec = shapes._replace(
            theta=params(shapes.theta, True),
            Theta=params(shapes.Theta, False),
            lam=jax.tree.map(plane, shapes.lam),
            chan=jax.tree.map(plane, shapes.chan),
            opt=shapes.opt._replace(mu=params(shapes.opt.mu, True),
                                    nu=params(shapes.opt.nu, True),
                                    count=P()),
            step=P())
    return jax.tree.map(lambda p: NamedSharding(mesh, p), spec,
                        is_leaf=lambda x: isinstance(x, P))


def prepare(model, backend: str, *, flash: Optional[bool] = None,
            mode: str = "replicated", rounds: int = ROUNDS,
            noisy: bool = True, flat_channel: bool = False,
            mesh=None) -> dict:
    """Trace ``rounds`` rounds of ``make_fl_train`` with the ``backend``
    transport, and flash attention if ``flash`` (default: with the pallas
    transport), down to the lowered step; :func:`train` compiles and runs
    it.  Tracing reads ``REPRO_USE_PALLAS``, so it happens here, one run at
    a time; compiling does not, so several runs may compile at once.

    With ``mesh`` the state is placed on the mesh (``_state_shardings``)
    and the step runs under the model's sharding rules.
    ``flat_channel`` gives each worker one fading coefficient for all its
    parameters, the same whatever the layout of the packed planes."""
    import jax
    import jax.numpy as jnp

    from repro.core.admm import AdmmConfig
    from repro.core.channel import ChannelConfig, rayleigh
    from repro.core.cplx import Complex
    from repro.train.llm_trainer import FLConfig, make_fl_train

    key = jax.random.PRNGKey(0)
    flcfg = FLConfig(mode=mode, n_workers=WORKERS, local_steps=LOCAL_STEPS,
                     local_lr=LOCAL_LR, transport_backend=backend)
    with _kernels(backend == "pallas" if flash is None else flash):
        init_fn, train_step = make_fl_train(
            model, flcfg, AdmmConfig(),
            ChannelConfig(n_workers=WORKERS, snr_db=SNR_DB, noisy=noisy),
            mesh=mesh)
        shapes = jax.eval_shape(init_fn, key)
        sh = rules = None
        jit_kw = {}
        if mesh is not None:
            from repro.models.sharding import axis_rules
            rules = axis_rules(mesh)
            sh = _state_shardings(shapes, model.cfg, mesh)
            shapes = jax.tree.map(
                lambda x, s_: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                   sharding=s_), shapes, sh)
            jit_kw = dict(in_shardings=(sh, None, None),
                          out_shardings=(sh, None))

        def init(k):
            st = init_fn(k)
            if flat_channel:
                h = rayleigh(jax.random.fold_in(k, 7), (WORKERS, 1))
                st = st._replace(chan=st.chan._replace(h=Complex(
                    jnp.broadcast_to(h.re, st.chan.h.re.shape),
                    jnp.broadcast_to(h.im, st.chan.h.im.shape))))
            return st

        batch = {"tokens": jax.ShapeDtypeStruct((WORKERS, 1, SEQ), jnp.int32)}
        with rules or contextlib.nullcontext():
            lowered = jax.jit(train_step, donate_argnums=(0,), **jit_kw).lower(
                shapes, batch, jax.ShapeDtypeStruct(key.shape, key.dtype))
    return dict(key=key, init=jax.jit(init, out_shardings=sh),
                lowered=lowered, model=model, rounds=rounds)


def compile_all(plans, parallel: int = 1) -> None:
    """Compile each plan's step (``parallel`` at a time), timing each."""
    from concurrent.futures import ThreadPoolExecutor

    def one(plan):
        t0 = time.perf_counter()
        plan["step"] = plan["lowered"].compile()
        plan["compile_s"] = time.perf_counter() - t0

    with ThreadPoolExecutor(parallel) as pool:
        for f in [pool.submit(one, plan) for plan in plans]:
            f.result()


def train(plan) -> dict:
    """Run a compiled plan; returns the per-round losses and 1/α, the
    final Θ on the host, the final state and smoke timings."""
    import jax

    from repro.data.synthetic import token_dataset

    key, rounds, step = plan["key"], plan["rounds"], plan["step"]
    data = token_dataset(jax.random.fold_in(key, 1), n_sequences=rounds,
                         seq_len=SEQ,
                         vocab_size=plan["model"].cfg.vocab_size,
                         n_workers=WORKERS)
    st = plan["init"](key)
    losses, inv_alpha, secs = [], [], []
    for r in range(rounds):
        t0 = time.perf_counter()
        st, m = jax.block_until_ready(step(
            st, {"tokens": data[:, r:r + 1]}, jax.random.fold_in(key, 2000 + r)))
        secs.append(time.perf_counter() - t0)
        losses.append(float(m["loss"]))
        inv_alpha.append(float(m["inv_alpha"]))
    return dict(losses=losses, inv_alpha=inv_alpha,
                compile_s=plan["compile_s"],
                s_per_round=float(np.median(secs[1:] or secs)),
                kernels="tpu_custom_call" in step.as_text(),
                Theta=[np.asarray(l, np.float32)
                       for l in jax.tree.leaves(jax.device_get(st.Theta))],
                n_params=sum(l.size for l in jax.tree.leaves(st.Theta)),
                state=st)


def cross_device(backend: str) -> dict:
    """``XD_ROUNDS`` sampled rounds of the flat paper round at N=10⁶."""
    import jax
    import jax.numpy as jnp

    from benchmarks.scaleup import D, RHO, make_alg, proximal_solver

    key = jax.random.PRNGKey(0)
    with _kernels(backend == "pallas"):
        alg = dataclasses.replace(make_alg(POPULATION, COHORT),
                                  backend=backend)
        solve = proximal_solver(RHO)
        theta0 = jax.random.normal(jax.random.fold_in(key, 1),
                                   (POPULATION, D), jnp.float32)
        st = jax.jit(alg.init)(key, theta0)
        t0 = time.perf_counter()
        rnd = jax.jit(lambda s, k: alg.round(k, s, solve, jnp.zeros_like),
                      donate_argnums=(0,)).lower(st, key).compile()
        compile_s = time.perf_counter() - t0
        inv_alpha, secs = [], []
        for r in range(XD_ROUNDS):
            t0 = time.perf_counter()
            st, m = jax.block_until_ready(
                rnd(st, jax.random.fold_in(key, r + 1)))
            secs.append(time.perf_counter() - t0)
            inv_alpha.append(float(m["inv_alpha"]))
    st = jax.device_get(st)
    return dict(inv_alpha=inv_alpha, compile_s=compile_s,
                s_per_round=float(np.median(secs[1:] or secs)),
                kernels="tpu_custom_call" in rnd.as_text(),
                Theta=np.asarray(st.Theta), theta=np.asarray(st.theta),
                lam=(np.asarray(st.lam.re), np.asarray(st.lam.im)),
                h=(np.asarray(st.phys.h.re), np.asarray(st.phys.h.im)))


def _report(label: str, run: dict, jax) -> None:
    _log(f"{label}: compile {run['compile_s']:.1f} s, "
         f"{run['s_per_round']:.4f} s/round, peak "
         f"{_peak_gb(jax):.2f} GB in use so far (smoke numbers, one run)")


def one_chip(check: Checks) -> None:
    import jax

    from repro.models.registry import get_config

    model = main_model()
    published = get_config(ARCH)
    _log(f"main: {ARCH} at published widths (d_model "
         f"{model.cfg.d_model}, {model.cfg.n_heads} q / "
         f"{model.cfg.n_kv_heads} kv heads of {model.cfg.hd}, d_ff "
         f"{model.cfg.d_ff}), {model.cfg.n_layers} layer, vocabulary "
         f"{VOCAB} of {published.vocab_size}, W={WORKERS}, "
         f"local_steps={LOCAL_STEPS}, batch 1 x {SEQ}, {ROUNDS} rounds")
    _log(f"main: cut: depth {published.n_layers} -> {model.cfg.n_layers} "
         f"layer and vocabulary {published.vocab_size} -> {VOCAB} rows; "
         f"no width and not the sequence")
    runs = {}
    for backend in ("pallas", "jnp"):
        plan = prepare(model, backend)
        compile_all([plan])
        run = train(plan)
        del run["state"], plan
        runs[backend] = run
        _report(f"main[{backend}]", run, jax)
        _log(f"main[{backend}]: loss {run['losses']}, "
             f"1/alpha {run['inv_alpha']}, D {run['n_params']}")
    p, j = runs["pallas"], runs["jnp"]
    check("main: Mosaic kernels in the pallas step", p["kernels"],
          "tpu_custom_call in the compiled HLO")
    check("main: finite losses", all(map(math.isfinite, p["losses"]
                                         + j["losses"])),
          f"{p['losses']} / {j['losses']}")
    check.close("main: loss per round, max rel diff",
                _max_rel(p["losses"], j["losses"]), LOSS_RTOL)
    check.close("main: 1/alpha per round, max rel diff",
                _max_rel(p["inv_alpha"], j["inv_alpha"]), ALPHA_RTOL)
    check.close("main: final Theta, rel RMS diff",
                _rel_rms(p["Theta"], j["Theta"]), THETA_RTOL)
    runs.clear()

    xd = {}
    for backend in ("pallas", "jnp"):
        xd[backend] = cross_device(backend)
        _report(f"cross-device[{backend}]", xd[backend], jax)
    p, j = xd["pallas"], xd["jnp"]
    check("cross-device: Mosaic kernels in the pallas round", p["kernels"],
          "tpu_custom_call in the compiled HLO")
    check.close("cross-device: 1/alpha per round, max rel diff",
                _max_rel(p["inv_alpha"], j["inv_alpha"]), XD_RTOL)
    for name in ("Theta", "theta", "lam", "h"):
        got = p[name] if isinstance(p[name], tuple) else (p[name],)
        want = j[name] if isinstance(j[name], tuple) else (j[name],)
        check.close(f"cross-device: final {name}, rel RMS diff",
                    _rel_rms(got, want), XD_RTOL)


def four_chips(check: Checks) -> None:
    """The main-phase model on a (data, fsdp, model) = (1, 2, 2) mesh,
    replicated and then sketched, each against the same mode on one
    device of this process.

    Mosaic kernels cannot be partitioned by GSPMD, so only kernels inside
    ``shard_map`` run on the mesh: the shard-local round of the
    replicated mode does; the model's attention (model-sharded local
    steps) and the sketched mode's round do not, and take the XLA path.
    """
    import jax

    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1, 2, 2), ("data", "fsdp", "model"),
                     devices=jax.devices()[:4])
    model = main_model()
    _log(f"mesh: {dict(mesh.shape)} over {len(mesh.devices.flat)} devices, "
         f"{ARCH} as in the main phase, {MESH_ROUNDS} rounds per run, "
         f"noise-free, flat fading, XLA attention")
    runs = [(mode, backend, placed)
            for mode, backend in (("replicated", "pallas"), ("sketched", "jnp"))
            for placed in (None, mesh)]
    plans = [prepare(model, backend, mode=mode, flash=False, noisy=False,
                     flat_channel=True, rounds=MESH_ROUNDS, mesh=placed)
             for mode, backend, placed in runs]
    # the four steps compile at once: compiling is host work, and the
    # one-device steps of the full model take minutes each
    compile_all(plans, parallel=len(plans))
    results = {}
    for (mode, backend, placed), plan in zip(runs, plans):
        where = "1 device" if placed is None else "(1, 2, 2)"
        run = results[mode, where] = train(plan)
        _report(f"mesh[{mode}, {backend}, {where}]", run, jax)
        st = run.pop("state")
        if placed is not None:
            planes = [l for l in jax.tree.leaves(st)
                      if l.size >= len(mesh.devices.flat)]
            spread = all(len(l.sharding.device_set) == 4 for l in planes)
            split = [l for l in planes
                     if l.addressable_shards[0].data.size < l.size]
            check(f"mesh[{mode}]: state on all 4 devices",
                  spread and bool(split),
                  f"{len(split)} of {len(planes)} arrays split across "
                  f"devices, every one placed on all 4")
        del st, plan
    for mode, backend in (("replicated", "pallas"), ("sketched", "jnp")):
        one, sharded = results[mode, "1 device"], results[mode, "(1, 2, 2)"]
        _log(f"mesh[{mode}]: loss {sharded['losses']} vs {one['losses']}")
        if backend == "pallas":
            check(f"mesh[{mode}]: Mosaic kernels in the sharded step",
                  sharded["kernels"], "tpu_custom_call in the compiled HLO")
        check.close(f"mesh[{mode}]: loss per round, max rel diff",
                    _max_rel(sharded["losses"], one["losses"]), LOSS_RTOL)
        check.close(f"mesh[{mode}]: 1/alpha per round, max rel diff",
                    _max_rel(sharded["inv_alpha"], one["inv_alpha"]),
                    MESH_ALPHA_RTOL[mode])
        check.close(f"mesh[{mode}]: final Theta, rel RMS diff",
                    _rel_rms(sharded["Theta"], one["Theta"]), THETA_RTOL)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: main and cross-device phases on one chip; "
                         "4: only the sharded (1, 2, 2) mesh phase")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} devices", file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.mesh import enable_compile_cache
    cache = enable_compile_cache()
    _log(f"chip_smoke: {dev.device_kind} x{len(devices)}, jax "
         f"{jax.__version__}, compile cache {cache}")

    check = Checks()
    (four_chips if args.chips == 4 else one_chip)(check)
    if check.failed:
        print(f"chip_smoke: {len(check.failed)} check(s) failed: "
              f"{check.failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
