"""One run of one cell: set-up, the measured window, the check.

    python3 bench/run.py --workload NAME --seed N --seconds S --trace 0|1

Set-up (``setup_s``, from process start to the first timed round) builds
the program's trainer, compiles its step, makes the state from the seed
and drives the step through its first three rounds; their losses, 1/α
and the state's changes are what the reference is compared with.  The
same step and state then run the window: one dispatch per round, the
round's loss and 1/α read back every round, as a logging run reads them,
until ``--seconds`` have passed.  ``round_s`` is the window over the
rounds completed.  After the window the peak memory is read, the
program's state is freed, the reference runs the same first rounds, and
``correct`` is decided.

With ``--trace 1`` the window runs under the profiler and the result
carries the per-layer metrics; with ``--trace 0`` the end-to-end ones.
The last line of standard output is the result; the numbers compared,
each beside its limit, are the last lines of standard error.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Any, Callable, Dict, Optional, Tuple

from harness import compare, device, spec

#: rounds that set-up drives and the reference follows
CHECK_ROUNDS = 3
#: distinct token batches and round keys; the window cycles through them
POOL = 64
CACHE_DIR = os.path.join(spec.ROOT, ".bench_cache", "jax")


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    where it is set, else a fixed directory inside the checkout."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or CACHE_DIR
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


@dataclasses.dataclass
class Context:
    """What a per-layer reader may read."""

    workload: str
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    chips: int
    peaks: Dict[str, Any]
    #: the configuration's plain model (``bench/models``): its counts
    model: Any
    n_params: int
    rounds: int
    window_s: float
    trace: Any = None
    #: the compiled step's HLO text, whose instruction names the trace uses
    program_text: str = ""
    trace_window_s: float = 0.0
    busy_s: float = 0.0

    def op_time_s(self, match: Callable[[str], bool]) -> float:
        from harness import trace as tr
        return tr.op_time_ns(self.trace, match) / 1e9


class _Compiles:
    """Counts backend compilations, to show none happens in the window."""

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, *_args, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


def prepare(cell: spec.Cell, devices, wrap_step: Optional[Callable] = None):
    """The program's system for the cell (``bench/systems``, named by the
    traffic file) and its compiled step."""
    system = spec.load_system(cell.traffic).System(
        cell.config, cell.traffic, devices, wrap_step=wrap_step)
    return system, system.compile()


def first_rounds(cell: spec.Cell, system, step, seed: int):
    """The state from the seed, driven through the step's first rounds.

    Returns the state, the window's batches and round keys, and the
    readings the reference is compared with."""
    from harness import inputs
    key = inputs.seed_key(seed)
    batches, keys = system.feed(key, POOL)
    state = system.make_state(key)
    prog = {"losses": [], "inv_alpha": []}
    for r in range(CHECK_ROUNDS):
        state, m = step(state, batches[r], keys[r])
        prog["losses"].append(float(m["loss"]))
        prog["inv_alpha"].append(float(m["inv_alpha"]))
        prog.update(system.readings(key, state, r + 1, CHECK_ROUNDS,
                                    cell.limits))
    return state, batches, keys, prog


def reference_readings(cell: spec.Cell, prog: dict, seed: int, devices,
                       **control) -> Tuple[dict, Dict[str, float]]:
    """The reference's first rounds from the seed (``bench/references``,
    named by the traffic file), and the numbers that compare ``prog`` (the
    program's, or the control's) with them."""
    ref = spec.load_reference(cell.traffic).run(
        cell.config, cell.traffic, seed, CHECK_ROUNDS, devices=devices,
        theta1_program=prog.get("Theta1"), **control)
    return ref, compare.readings(prog, ref, cell.limits)


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             devices, t_start: float,
             wrap_step: Optional[Callable] = None) -> Dict[str, Any]:
    """Everything after the chip check; returns the result line's dict."""
    import jax
    from harness import inputs

    compiles = _Compiles()
    tr = cell.traffic
    since = lambda: f"{time.perf_counter() - t_start:.1f} s"
    system, step = prepare(cell, devices, wrap_step)
    _log(f"step compiled or loaded at {since()}")
    state, batches, keys, prog = first_rounds(cell, system, step, seed)
    _log(f"state and check rounds done at {since()}")

    trace_dir = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(trace_dir)
    compiles_before = compiles.n
    r, n, failed = CHECK_ROUNDS, 0, 0
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    while True:
        with jax.profiler.TraceAnnotation("data"):
            batch, k = batches[r % POOL], keys[r % POOL]
        with jax.profiler.TraceAnnotation("dispatch"):
            state, m = step(state, batch, k)
        with jax.profiler.TraceAnnotation("readback"):
            loss, ia = float(m["loss"]), float(m["inv_alpha"])
        failed += not (loss == loss and ia == ia)
        r, n = r + 1, n + 1
        if time.perf_counter() - t0 >= seconds:
            break
    window_s = time.perf_counter() - t0
    if trace:
        jax.profiler.stop_trace()
    _log(f"window closed at {since()}")
    in_window = compiles.n - compiles_before
    dev = device.describe(devices)
    program_text = step.as_text() if trace else ""

    ctx = Context(workload=cell.name, config=cell.config, traffic=tr,
                  chips=len(devices),
                  peaks=device.peaks(dev["kind"]) if trace else {},
                  model=spec.load_model(cell.config),
                  n_params=inputs.param_count(cell.config), rounds=n,
                  window_s=window_s, program_text=program_text)
    breakdown = None
    if trace:
        from harness import trace as trmod
        ctx.trace = trmod.load(trmod.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        ws, we = trmod.window(ctx.trace)
        ctx.trace_window_s = (we - ws) / 1e9
        busy = trmod.busy_ns(ctx.trace)
        ctx.busy_s = sum(busy.values()) / max(len(busy), 1) / 1e9
        dev["busy_s"], dev["window_s"] = ctx.busy_s, ctx.trace_window_s
        breakdown = {"device_ops": trmod.top_ops(ctx.trace),
                     "idle_gaps": trmod.idle_gaps(ctx.trace)}

    metrics = {}
    if trace:
        for m_ in cell.per_layer:
            v = spec.load_reader(m_["name"]).read(ctx)
            if v is None and "workloads" in m_:
                # the metric names this cell as one it finds work in
                raise RuntimeError(f"{m_['name']} read nothing in "
                                   f"{cell.name}, which it lists")
            if v is not None:
                metrics[m_["name"]] = {"value": v, "unit": m_["unit"]}
    else:
        e2e = {"round_s": window_s / n, "setup_s": setup_s,
               "peak_hbm_gb": dev["memory_peak_bytes"] / 1e9}
        for m_ in cell.end_to_end:
            metrics[m_["name"]] = {"value": e2e[m_["name"]],
                                   "unit": m_["unit"]}

    # the check: free the program's state, then run the reference
    del state, step, system, m, batches, keys
    gc.collect()
    ref, read = reference_readings(cell, prog, seed, devices)
    _log(f"reference done at {since()}")
    ok, checks = compare.verdict(read, cell.limits)
    ok = ok and failed == 0 and in_window == 0
    _log(f"rounds in window {n}, non-finite rounds {failed}, compiles in "
         f"window {in_window}, setup_s {setup_s:.3f}, window_s "
         f"{window_s:.3f}")
    _log(f"program loss {prog['losses']} 1/alpha {prog['inv_alpha']}")
    _log(f"reference loss {ref['losses']} 1/alpha {ref['inv_alpha']}")
    for name, c in checks.items():
        _log(f"check {name}: {c['value']:.6e} limit {c['limit']:.6e}")
    out = {"correct": bool(ok), "attempted": n, "failed": failed,
           "metrics": metrics, "device": dev}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, t_start: Optional[float] = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    args = parse(argv)
    try:
        cell = spec.load_cell(args.workload)
    except spec.SpecError as e:
        _log(f"bench: {e}")
        return 2
    try:
        devices = device.chips(cell.chips)
    except device.NoChip as e:
        _log(f"bench: {e}")
        return 3
    cache = enable_compile_cache()
    _log(f"bench: {args.workload} seed {args.seed} on "
         f"{devices[0].device_kind} x{len(devices)}, compile cache {cache}")
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), devices,
                   t_start)
    print(json.dumps(out), flush=True)
    return 0
