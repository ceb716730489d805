"""Profiling hooks: trace annotations, layer scopes, wall-clock spans,
compile reports.

Five independent pieces, all safe no-ops when profiling is off:

* :func:`annotate` / :func:`trace_session` — ``jax.profiler`` named trace
  annotations and a start/stop trace context around a run.  A trace that
  cannot start or stop raises: ``--profile`` never exits 0 without one.
* :func:`layer` — ``jax.named_scope`` over the vocabulary
  ``repro.obs.LAYERS``: compile-time metadata on the step's HLO ops that
  names the layer each op belongs to (no op, no run-time cost).
* :func:`grid_launches` / :func:`record_grid_launch` — a trace-time record
  of the worker-grid Pallas launches (``kernels/ota``): each launch's
  kernel, W, columns, column tile, grid steps and the columns it padded
  in HBM (no op, no run-time cost).
* :class:`SpanTimer` — wall-clock spans (compile vs execute split, the
  host's per-round phases) accumulated into a JSON-serialisable dict,
  each span also a trace annotation, plus a count of backend compilations
  after the first dispatch.
* :func:`compile_report` — static analysis of a compiled module's optimized
  HLO via :mod:`repro.launch.hlo_analysis`: dispatch flops/bytes,
  per-collective byte/op counts, and the collective-permute reshard
  tripwire, written as ``compile_report.json`` next to the run's JSONL.
"""
from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from typing import Any, Dict, Optional

__all__ = ["annotate", "trace_session", "layer", "grid_launches",
           "record_grid_launch", "SpanTimer", "compile_report"]


@contextlib.contextmanager
def annotate(name: str):
    """``jax.profiler.TraceAnnotation`` that degrades to a no-op."""
    try:
        import jax.profiler
        ctx = jax.profiler.TraceAnnotation(name)
    except Exception:
        ctx = contextlib.nullcontext()
    with ctx:
        yield


@contextlib.contextmanager
def trace_session(trace_dir: Optional[str]):
    """Start/stop a ``jax.profiler`` trace writing to ``trace_dir``.

    ``None`` disables tracing entirely.  Profiler failures propagate.
    """
    if not trace_dir:
        yield
        return
    import jax.profiler
    os.makedirs(trace_dir, exist_ok=True)
    jax.profiler.start_trace(trace_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


#: the top-level layer scope open on this thread while a step is traced
_LAYER = threading.local()


def layer(name: str):
    """``jax.named_scope(name)`` for a name of ``repro.obs.LAYERS``; usable
    as a context manager or a decorator.

    The scope nests as the vocabulary says: a top-level scope opened while
    another top-level scope is open, or a nested scope outside its parent,
    opens nothing, so an op carries at most one top-level scope.  Raises
    ``ValueError`` on a name outside the vocabulary.
    """
    from repro.obs import LAYERS
    if name not in LAYERS:
        raise ValueError(f"unknown layer scope {name!r}; the vocabulary is "
                         f"repro.obs.LAYERS: {sorted(LAYERS)}")
    return _layer_scope(name, LAYERS[name])


@contextlib.contextmanager
def _layer_scope(name: str, parent: Optional[str]):
    import jax
    # a top-level scope (parent None) opens where no top-level scope is
    # open; a nested one, inside its parent
    if getattr(_LAYER, "top", None) != parent:
        yield
        return
    if parent is None:
        _LAYER.top = name
    try:
        with jax.named_scope(name):
            yield
    finally:
        if parent is None:
            _LAYER.top = None


#: the open ``grid_launches`` record on this thread, or None
_LAUNCHES = threading.local()


@contextlib.contextmanager
def grid_launches():
    """Collect the worker-grid kernel launches traced inside the block.

    Yields a list that :func:`record_grid_launch` appends one dict to per
    launch traced on this thread while the block is open: ``kernel``,
    ``workers``, ``n`` (columns), ``block_cols``, ``steps`` (grid steps)
    and ``pad_cols`` (columns the launch's planes were padded by in HBM).
    A record opened inside another passes its launches on to it.
    """
    outer = getattr(_LAUNCHES, "log", None)
    log: list = []
    _LAUNCHES.log = log
    try:
        yield log
    finally:
        _LAUNCHES.log = outer
        if outer is not None:
            outer.extend(log)


def record_grid_launch(kernel: str, *, workers: int, n: int,
                       block_cols: int, steps: int, pad_cols: int) -> None:
    """Note one worker-grid launch in the open :func:`grid_launches` record;
    nothing when none is open."""
    log = getattr(_LAUNCHES, "log", None)
    if log is not None:
        log.append({"kernel": kernel, "workers": workers, "n": n,
                    "block_cols": block_cols, "steps": steps,
                    "pad_cols": pad_cols})


#: backend compilations in this process, counted by one jax.monitoring
#: listener registered on first use
_COMPILES = {"n": 0, "listening": False}
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _on_event(event: str, *_args, **_kw) -> None:
    if event == _COMPILE_EVENT:
        _COMPILES["n"] += 1


def _compiles() -> int:
    if not _COMPILES["listening"]:
        import jax
        jax.monitoring.register_event_duration_secs_listener(_on_event)
        _COMPILES["listening"] = True
    return _COMPILES["n"]


class SpanTimer:
    """Named wall-clock spans, accumulated + counted, each one a
    ``jax.profiler`` trace annotation of the same name.

    >>> t = SpanTimer()
    >>> with t.span("dispatch"): step(...)
    >>> t.summary()["dispatch"]["seconds"]

    ``compiles_after_first`` counts backend compilations after the first
    ``dispatch`` span closed: a run whose step recompiles shows it there.
    """

    def __init__(self):
        self.spans: Dict[str, Dict[str, float]] = {}
        #: per-span list of individual durations (s/round series etc.)
        self.series: Dict[str, list] = {}
        self._compiles_at_first: Optional[int] = None

    @contextlib.contextmanager
    def span(self, name: str):
        t0 = time.perf_counter()
        try:
            with annotate(name):
                yield
        finally:
            dt = time.perf_counter() - t0
            s = self.spans.setdefault(name, {"seconds": 0.0, "count": 0.0})
            s["seconds"] += dt
            s["count"] += 1.0
            self.series.setdefault(name, []).append(dt)
            if name == "dispatch" and self._compiles_at_first is None:
                self._compiles_at_first = _compiles()

    @property
    def compiles_after_first(self) -> int:
        if self._compiles_at_first is None:
            return 0
        return _compiles() - self._compiles_at_first

    def summary(self) -> Dict[str, Dict[str, float]]:
        return {k: dict(v) for k, v in self.spans.items()}


def compile_report(hlo_text: str, path: Optional[str] = None,
                   **extra) -> Dict[str, Any]:
    """Static compile report from one module's optimized HLO text.

    Returns (and optionally writes to ``path``) a JSON-serialisable dict::

        {"flops": ..., "mem_bytes": ..., "coll_bytes": {...},
         "coll_count": {...}, "coll_bytes_total": ...,
         "collective_permutes": ..., **extra}

    ``extra`` fields (e.g. ``compile_seconds``, ``rounds_per_dispatch``)
    are merged verbatim.
    """
    from repro.launch import hlo_analysis
    s = hlo_analysis.analyze(hlo_text)
    rep: Dict[str, Any] = {
        "flops": s.flops,
        "mem_bytes": s.mem_bytes,
        "coll_bytes": dict(s.coll_bytes),
        "coll_count": dict(s.coll_count),
        "coll_bytes_total": s.coll_bytes_total,
        "collective_permutes": hlo_analysis.collective_permutes(s),
    }
    rep.update(extra)
    if path is not None:
        with open(path, "w") as f:
            json.dump(rep, f, indent=2, sort_keys=True)
            f.write("\n")
    return rep
