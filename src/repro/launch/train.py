"""Training launcher: federated A-FADMM training of any assigned arch.

    PYTHONPATH=src python -m repro.launch.train --arch granite-8b --reduced \
        --rounds 50 --workers 4 --local-steps 2

On this CPU container ``--reduced`` is the executable path (full configs are
exercised by launch/dryrun.py).  The same ``train_step`` object lowers on the
production mesh — the launcher is mesh-agnostic.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import save
from repro.core.admm import AdmmConfig
from repro.core.channel import ChannelConfig
from repro.data.synthetic import token_dataset
from repro.launch.mesh import enable_compile_cache, make_mesh
from repro.models.registry import get_model, list_archs
from repro.phy import list_scenarios
from repro.train.llm_trainer import FLConfig, make_fl_train


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list_archs())
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--mode", default="replicated",
                    choices=["replicated", "sketched"])
    ap.add_argument("--sketch-ratio", type=int, default=256,
                    help="sketched mode: compression ratio, "
                         "d_s = ceil(packed_size / ratio)")
    ap.add_argument("--sketch-lr", type=float, default=1.0,
                    help="step size applied to the decoded sketch delta")
    ap.add_argument("--fsdp", type=int, default=1,
                    help="shard parameters over a dedicated 'fsdp' mesh "
                         "axis of this size (requires fsdp to divide the "
                         "local device count; the launcher builds a "
                         "(data, fsdp, model) mesh and both FL modes run "
                         "their packed transport shard-locally on it)")
    ap.add_argument("--backend", default=None, choices=["jnp", "pallas"],
                    help="OTA transport backend (default: REPRO_USE_PALLAS "
                         "env var)")
    ap.add_argument("--driver", default="loop", choices=["loop", "scan"],
                    help="round driver: python loop (one dispatch/round) or "
                         "scan-compiled blocks of --log-every rounds")
    ap.add_argument("--scenario", default=None, choices=list_scenarios(),
                    help="repro.phy wireless scenario preset (default: the "
                         "legacy block-fading channel, bit-identical)")
    ap.add_argument("--doppler-hz", type=float, default=None,
                    help="override the scenario's Doppler frequency "
                         "(rho = J0(2*pi*f_d*T))")
    ap.add_argument("--csi-err", type=float, default=None,
                    help="worker CSI error std sigma_e "
                         "(h_hat = h + CN(0, sigma_e^2))")
    ap.add_argument("--h-min", type=float, default=None,
                    help="deep-fade truncation threshold on the per-worker "
                         "RMS |h| (workers below it skip the round)")
    ap.add_argument("--slots-per-round", type=int, default=None,
                    help="wall-clock slots the scenario physics advances "
                         "per round (default: the preset's 1; raise it so "
                         "mobility/Doppler gain dynamics show up in short "
                         "runs)")
    ap.add_argument("--ota-fused", default=None,
                    choices=["on", "off"],
                    help="one-pass fused OTA receive (default on; off keeps "
                         "the composed per-primitive chain)")
    ap.add_argument("--ota-worker-chunk", type=int, default=None,
                    help="stream the receive over worker cohorts of this "
                         "size (peak signal memory O(chunk*D) instead of "
                         "O(W*D); 0/None = monolithic, or set "
                         "REPRO_OTA_WORKER_CHUNK)")
    ap.add_argument("--ota-block-rows", type=int, default=None,
                    help="pallas OTA kernel row tile (sets "
                         "REPRO_OTA_BLOCK_ROWS)")
    ap.add_argument("--ota-block-cols", type=int, default=None,
                    help="pallas fused-round kernel column tile (default "
                         "1024, or REPRO_OTA_BLOCK_COLS)")
    ap.add_argument("--rounds", type=int, default=50)
    ap.add_argument("--workers", type=int, default=4)
    # --- population/cohort sampling (repro.core.cohort) --------------------
    ap.add_argument("--population", type=int, default=None,
                    help="worker-population size N: θ/λ/phy/fault state all "
                         "carry N rows while only --cohort workers uplink "
                         "per round (supersedes --workers; replicated mode)")
    ap.add_argument("--cohort", type=int, default=None,
                    help="workers sampled per round (requires --population; "
                         "cohort == population disables sampling bitwise)")
    ap.add_argument("--cohort-policy", default="uniform",
                    choices=["uniform", "top-gain", "prop-h2"],
                    help="cohort sampling policy (channel-aware policies "
                         "rank by mean |h|^2)")
    ap.add_argument("--autotune-cache", default=None,
                    help="JSON file caching autotuned OTA round tiles per "
                         "(W, d, backend); measured once, reused across "
                         "runs — fills REPRO_OTA_BLOCK_COLS / "
                         "REPRO_OTA_WORKER_CHUNK unless set explicitly")
    ap.add_argument("--batch", type=int, default=2, help="per-worker batch")
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--local-steps", type=int, default=2)
    ap.add_argument("--local-lr", type=float, default=1e-2)
    ap.add_argument("--rho", type=float, default=0.5)
    ap.add_argument("--snr-db", type=float, default=40.0)
    ap.add_argument("--coherence", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--log-every", type=int, default=10)
    # --- fault injection / round health guard (repro.faults) ---------------
    ap.add_argument("--crash-prob", type=float, default=0.0,
                    help="per-round per-worker permanent-crash hazard")
    ap.add_argument("--crash-at", default=None,
                    help="deterministic crash schedule 'round:worker,...' "
                         "(e.g. '10:0,25:3')")
    ap.add_argument("--straggler-prob", type=float, default=0.0,
                    help="per-round probability a worker uploads its stale "
                         "snapshot instead of the fresh model")
    ap.add_argument("--straggler-delay", type=int, default=4)
    ap.add_argument("--nan-workers", type=int, default=0,
                    help="workers [0,k) corrupt every upload (persistent "
                         "byzantine rows the evict policy removes)")
    ap.add_argument("--corrupt-prob", type=float, default=0.0)
    ap.add_argument("--corrupt-mode", default="nan",
                    choices=["nan", "inf", "spike"])
    ap.add_argument("--burst-prob", type=float, default=0.0,
                    help="per-round PS interference-burst hazard")
    ap.add_argument("--burst-std", type=float, default=10.0)
    ap.add_argument("--guard", default=None,
                    choices=["skip", "retransmit", "evict",
                             "evict-retransmit"],
                    help="round health guard policy (default: no guard)")
    ap.add_argument("--snr-floor-db", type=float, default=None,
                    help="guard receive-SNR floor (default: finiteness "
                         "check only)")
    ap.add_argument("--max-retries", type=int, default=2)
    ap.add_argument("--power-backoff", type=float, default=2.0,
                    help="per-retry transmit power ramp gamma")
    # --- durable progress (checkpoint/resume) ------------------------------
    ap.add_argument("--checkpoint-dir", default=None,
                    help="directory for periodic full-state snapshots "
                         "(round_NNNNNNNN.npz)")
    ap.add_argument("--checkpoint-every", type=int, default=0,
                    help="snapshot cadence in rounds (scan driver: at the "
                         "first block boundary crossing each multiple)")
    ap.add_argument("--resume", action="store_true",
                    help="resume from the latest snapshot in "
                         "--checkpoint-dir; bitwise the uninterrupted run")
    # --- observability (repro.obs) -----------------------------------------
    ap.add_argument("--run-dir", default=None,
                    help="structured run logs: manifest.json + one "
                         "metrics.jsonl event per round (EVERY round, both "
                         "drivers) + compile_report.json; --resume appends "
                         "to the same log")
    ap.add_argument("--telemetry", default=None, choices=["on", "off"],
                    help="in-graph obs/ channel telemetry (default: on iff "
                         "--run-dir is set; off is bitwise the pre-obs "
                         "trainer)")
    ap.add_argument("--profile", action="store_true",
                    help="jax.profiler trace into RUN_DIR/trace plus "
                         "wall-clock spans in RUN_DIR/profile.json: "
                         "compile, execute, and each round's batch, "
                         "dispatch, readback, log and checkpoint (also "
                         "trace annotations inside a 'round' trace step), "
                         "compilations after the first dispatch, the "
                         "OTA kernels' grid steps and padded columns per "
                         "round (ota_grid_steps, ota_pad_cols), and with "
                         "telemetry on the rounds whose fading step "
                         "redrew the channel (chan_redraws, the sum of "
                         "obs/chan_redraw)")
    args = ap.parse_args()
    enable_compile_cache()

    if args.ota_block_rows is not None:
        # knobs are read lazily at trace time (repro.optflags), so setting
        # the env here — after import — still takes effect
        os.environ["REPRO_OTA_BLOCK_ROWS"] = str(args.ota_block_rows)

    #: telemetry defaults on exactly when the run is being logged
    telemetry_on = (args.telemetry == "on") if args.telemetry is not None \
        else args.run_dir is not None

    key = jax.random.PRNGKey(args.seed)
    model = get_model(args.arch, reduced=args.reduced)
    cfg = model.cfg
    W = args.workers
    #: rows the batch (and the uplink) carries per round: the cohort width
    #: under population sampling, else every worker
    W_round = args.cohort if args.population is not None else W
    if args.population is not None and args.cohort is None:
        raise SystemExit("--population requires --cohort (use "
                         "--cohort == --population to disable sampling)")

    mesh = None
    if args.fsdp > 1:
        n_dev = jax.device_count()
        if n_dev % args.fsdp:
            raise SystemExit(f"--fsdp {args.fsdp} must divide the local "
                             f"device count ({n_dev})")
        mesh = make_mesh((n_dev // args.fsdp, args.fsdp, 1),
                         ("data", "fsdp", "model"))

    faults = guard = None
    crash_at = ()
    if args.crash_at:
        crash_at = tuple(tuple(int(x) for x in pair.split(":"))
                         for pair in args.crash_at.split(","))
    if (args.crash_prob > 0 or crash_at or args.straggler_prob > 0
            or args.nan_workers > 0 or args.corrupt_prob > 0
            or args.burst_prob > 0):
        from repro.faults import FaultPlan
        faults = FaultPlan(
            crash_prob=args.crash_prob, crash_at=crash_at,
            straggler_prob=args.straggler_prob,
            straggler_delay=args.straggler_delay,
            nan_workers=args.nan_workers, corrupt_prob=args.corrupt_prob,
            corrupt_mode=args.corrupt_mode, burst_prob=args.burst_prob,
            burst_std=args.burst_std)
    if args.guard is not None:
        from repro.faults import GuardConfig
        guard = GuardConfig(policy=args.guard,
                            snr_floor_db=args.snr_floor_db,
                            max_retries=args.max_retries,
                            power_backoff=args.power_backoff)
    flcfg = FLConfig(mode=args.mode, n_workers=W,
                     local_steps=args.local_steps, local_lr=args.local_lr,
                     sketch_ratio=args.sketch_ratio,
                     sketch_lr=args.sketch_lr,
                     transport_backend=args.backend,
                     scenario=args.scenario, doppler_hz=args.doppler_hz,
                     csi_err=args.csi_err, h_min=args.h_min,
                     slots_per_round=args.slots_per_round,
                     ota_fused=None if args.ota_fused is None
                     else args.ota_fused == "on",
                     ota_worker_chunk=args.ota_worker_chunk,
                     ota_block_cols=args.ota_block_cols,
                     faults=faults, guard=guard,
                     telemetry=True if telemetry_on else None,
                     population=args.population, cohort=args.cohort,
                     cohort_policy=args.cohort_policy)
    acfg = AdmmConfig(rho=args.rho, flip_on_change=False)
    ccfg = ChannelConfig(n_workers=args.population or W, snr_db=args.snr_db,
                         coherence_iters=args.coherence)
    init_fn, train_step = make_fl_train(model, flcfg, acfg, ccfg, mesh=mesh)

    sink = timer = None
    if args.run_dir:
        import dataclasses
        from repro.obs.sink import MetricsSink, run_manifest
        sink = MetricsSink(args.run_dir, resume=args.resume)
        sink.write_manifest(run_manifest(
            arch=args.arch, reduced=args.reduced, mode=args.mode,
            driver=args.driver, backend=args.backend,
            telemetry=telemetry_on, rounds=args.rounds, workers=W,
            seed=args.seed, log_every=args.log_every,
            mesh_shape=dict(mesh.shape) if mesh is not None else None,
            flconfig=dataclasses.asdict(flcfg),
            admm=dataclasses.asdict(acfg),
            channel=dataclasses.asdict(ccfg),
            argv=vars(args)))
    if args.run_dir or args.profile:
        from repro.obs.profiling import SpanTimer
        timer = SpanTimer()

    # per-worker non-IID token streams (data pipeline) — cohort-width under
    # population sampling: stream i feeds the round's i-th sampled worker
    data = token_dataset(jax.random.fold_in(key, 1), n_sequences=64,
                         seq_len=args.seq, vocab_size=cfg.vocab_size,
                         n_workers=W_round)

    st = init_fn(key)
    # zeros-initialised leaves may alias one buffer; donation needs them
    # distinct (only matters for the very first execute)
    st = jax.tree.map(jnp.array, st)

    if args.autotune_cache:
        from repro.core.cplx import Complex as _Cplx
        if args.mode == "replicated" and isinstance(st.lam, _Cplx):
            from repro.core.transport import autotune_ota_round_cached
            res = autotune_ota_round_cached(
                W_round, st.lam.re.shape[-1], ccfg, backend=args.backend,
                cache_path=args.autotune_cache)
            best = res["best"]
            # knobs are read lazily at trace time, so the envs land before
            # the first compile; explicit flags win over the autotuner
            if args.ota_block_cols is None and best["block_cols"]:
                os.environ["REPRO_OTA_BLOCK_COLS"] = str(best["block_cols"])
            if args.ota_worker_chunk is None:
                os.environ["REPRO_OTA_WORKER_CHUNK"] = \
                    str(best["worker_chunk"])
            print(f"autotune[{'cache' if res.get('cached') else 'measured'}]"
                  f": block_cols={best['block_cols']} "
                  f"worker_chunk={best['worker_chunk']}", flush=True)
        else:
            print("autotune: skipped (replicated packed state only)",
                  flush=True)

    r0 = 0
    if args.resume and args.checkpoint_dir:
        from repro.checkpoint import latest_round, restore, round_path
        latest = latest_round(args.checkpoint_dir)
        if latest is not None:
            st = restore(round_path(args.checkpoint_dir, latest), st)
            r0 = latest
            print(f"resumed from round {r0} "
                  f"({round_path(args.checkpoint_dir, latest)})", flush=True)
            if sink is not None:
                sink.log_resume(r0)

    def maybe_checkpoint(stop: int, st, last: int) -> int:
        """Snapshot the FULL train state (θ, λ, Θ, channel/fault state —
        every PRNG input is re-derived from the global round index, so the
        snapshot alone resumes bitwise)."""
        if (args.checkpoint_dir and args.checkpoint_every > 0
                and (stop - last >= args.checkpoint_every
                     or stop == args.rounds)):
            from repro.checkpoint import round_path, save as save_tree
            save_tree(round_path(args.checkpoint_dir, stop), st)
            return stop
        return last

    def make_batch(data, kb):
        idx = jax.random.randint(kb, (W_round, args.batch), 0, data.shape[1])
        batch = {"tokens": jnp.take_along_axis(
            data, idx[:, :, None], axis=1)}
        if cfg.family == "vlm":
            batch["patches"] = jax.random.normal(
                kb, (W_round, args.batch, cfg.frontend_tokens,
                     cfg.frontend_dim))
        if cfg.family == "audio":
            batch["frames"] = jax.random.normal(
                kb, (W_round, args.batch, cfg.frontend_tokens, cfg.d_model))
        return batch

    def log(r, metrics):
        # stdout keeps the scalar summary; vector leaves (obs/tx_energy)
        # only go to the structured sink
        m = {k: float(v) for k, v in metrics.items() if jnp.ndim(v) == 0}
        print(f"round {r:4d}  loss={m['loss']:.4f}  "
              f"{json.dumps({k: round(v, 4) for k, v in m.items() if k != 'loss'})}",
              flush=True)

    def span(name):
        """The host phase ``name`` as a ``SpanTimer`` span, which is also a
        trace annotation; nothing when the run is neither logged nor
        profiled."""
        return timer.span(name) if timer is not None \
            else contextlib.nullcontext()

    #: the worker-grid kernel launches traced into one round's step
    step_launches = []

    def aot_compile(jitted, sample_args, rounds_per_dispatch):
        """AOT lower + compile (timed, so the compile/execute split is
        real), note the step's worker-grid launches, and write
        ``compile_report.json`` from the optimized HLO."""
        if timer is None:
            return jitted
        from repro.obs.profiling import compile_report, grid_launches
        t_l = time.perf_counter()
        with grid_launches() as launches:
            lowered = jitted.lower(*sample_args)
        step_launches[:] = launches
        t_c = time.perf_counter()
        with timer.span("compile"):
            compiled = lowered.compile()
        dt_c = timer.series["compile"][-1]
        if args.run_dir:
            compile_report(
                compiled.as_text(),
                os.path.join(args.run_dir, "compile_report.json"),
                compile_seconds=dt_c, lower_seconds=t_c - t_l,
                rounds_per_dispatch=rounds_per_dispatch)
        return compiled

    trace_ctx = contextlib.nullcontext()
    if args.profile and args.run_dir:
        from repro.obs.profiling import trace_session
        trace_ctx = trace_session(os.path.join(args.run_dir, "trace"))

    #: rounds whose fading step redrew the channel (telemetry on)
    chan_redraws = 0
    t0 = time.time()
    with trace_ctx:
        if args.driver == "scan":
            # batch sampling folded into the scan body: one dispatch per
            # block instead of one per round.  Block = gcd(log_every,
            # remaining) so every block has the SAME static length — one XLA
            # compile even when log_every doesn't divide rounds (a ragged
            # tail block would force a second full compile of the scanned
            # train_step).  A fresh run (r0 = 0) keeps the historical
            # gcd(log_every, rounds) blocks; batch and round keys fold in
            # the GLOBAL round index, so a resumed run's shifted block
            # boundaries change nothing about the math.
            import math
            block = max(1, math.gcd(args.log_every, args.rounds - r0))

            def block_body(data, s, r):
                batch = make_batch(data, jax.random.fold_in(key, 1000 + r))
                return train_step(s, batch, jax.random.fold_in(key, 2000 + r))

            # data rides as a jit argument (not a closed-over constant baked
            # into the executable)
            run_block = jax.jit(
                lambda d, s, rs: jax.lax.scan(
                    lambda ss, r: block_body(d, ss, r), s, rs),
                donate_argnums=(1,))
            run_block = aot_compile(
                run_block,
                (data, st, jnp.arange(r0, r0 + block, dtype=jnp.int32)),
                block)
            last = r0
            for start in range(r0, args.rounds, block):
                # one trace step per block, numbered by its first round
                with jax.profiler.StepTraceAnnotation("round",
                                                      step_num=start):
                    with span("execute"):
                        with span("batch"):
                            rs = jnp.arange(start, start + block,
                                            dtype=jnp.int32)
                        with span("dispatch"):
                            st, ms = run_block(data, st, rs)
                        if timer is not None:     # logged or profiled
                            with span("readback"):
                                # host sync: timing is real
                                ms = jax.device_get(ms)
                            chan_redraws += int(np.sum(
                                ms.get("obs/chan_redraw", 0.0)))
                    with span("log"):
                        if sink is not None:
                            # EVERY round of the block goes to the
                            # structured log; stdout keeps the last-round
                            # summary below
                            sink.log_rounds(start, ms)
                            sink.log_block(start + block - 1,
                                           timer.series["execute"][-1],
                                           block)
                        log(start + block - 1,
                            jax.tree.map(lambda x: x[-1], ms))
                    with span("checkpoint"):
                        last = maybe_checkpoint(start + block, st, last)
        else:
            step = jax.jit(train_step, donate_argnums=(0,))
            step = aot_compile(
                step,
                (st, make_batch(data, jax.random.fold_in(key, 1000 + r0)),
                 jax.random.fold_in(key, 2000 + r0)), 1)
            last = r0
            for r in range(r0, args.rounds):
                with jax.profiler.StepTraceAnnotation("round", step_num=r):
                    with span("execute"):
                        with span("batch"):
                            batch = make_batch(
                                data, jax.random.fold_in(key, 1000 + r))
                            kr = jax.random.fold_in(key, 2000 + r)
                        with span("dispatch"):
                            st, metrics = step(st, batch, kr)
                        if timer is not None:     # logged or profiled
                            with span("readback"):
                                metrics = jax.device_get(metrics)
                            chan_redraws += int(np.sum(
                                metrics.get("obs/chan_redraw", 0.0)))
                    with span("log"):
                        if sink is not None:
                            sink.log_round(r, metrics)
                        if r % args.log_every == 0 or r == args.rounds - 1:
                            log(r, metrics)
                    with span("checkpoint"):
                        last = maybe_checkpoint(r + 1, st, last)
    dt = time.time() - t0
    print(f"done: {args.rounds} rounds in {dt:.1f}s "
          f"({dt / args.rounds:.2f}s/round)")
    if sink is not None:
        sink.log_done(args.rounds - r0, dt)
        sink.close()
    if timer is not None:
        summ = timer.summary()
        recompiles = timer.compiles_after_first
        # per round: the grid steps of the OTA kernels' column grids, and
        # the columns their planes were padded by in HBM; over the run, the
        # rounds whose fading step redrew the channel
        grid = {"ota_grid_steps": sum(l["steps"] for l in step_launches),
                "ota_pad_cols": sum(l["pad_cols"] for l in step_launches)}
        if telemetry_on:
            grid["chan_redraws"] = chan_redraws
        if args.run_dir:
            with open(os.path.join(args.run_dir, "profile.json"), "w") as f:
                json.dump({"spans": summ, "series": timer.series,
                           "compiles_after_first": recompiles, **grid}, f,
                          indent=2, sort_keys=True)
                f.write("\n")
        parts = ", ".join(f"{k}={v['seconds']:.2f}s/{int(v['count'])}x"
                          for k, v in sorted(summ.items()))
        print(f"profile: {parts}, compiles_after_first={recompiles}, "
              + ", ".join(f"{k}={v}" for k, v in grid.items()), flush=True)

    if args.checkpoint:
        Theta = st.Theta
        save(args.checkpoint, Theta)
        print(f"saved global model to {args.checkpoint}")


if __name__ == "__main__":
    main()
