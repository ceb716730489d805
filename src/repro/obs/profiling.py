"""Profiling hooks: trace annotations, wall-clock spans, compile reports.

Three independent pieces, all safe no-ops when profiling is off:

* :func:`annotate` / :func:`trace_session` — ``jax.profiler`` named trace
  annotations and a start/stop trace context around a run.  A trace that
  cannot start or stop raises: ``--profile`` never exits 0 without one.
* :class:`SpanTimer` — wall-clock spans (compile vs execute split, per-block
  seconds) accumulated into a JSON-serialisable dict.
* :func:`compile_report` — static analysis of a compiled module's optimized
  HLO via :mod:`repro.launch.hlo_analysis`: dispatch flops/bytes,
  per-collective byte/op counts, and the collective-permute reshard
  tripwire, written as ``compile_report.json`` next to the run's JSONL.
"""
from __future__ import annotations

import contextlib
import json
import os
import time
from typing import Any, Dict, Optional

__all__ = ["annotate", "trace_session", "SpanTimer", "compile_report"]


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


class SpanTimer:
    """Named wall-clock spans, accumulated + counted.

    >>> t = SpanTimer()
    >>> with t.span("execute"): run_block()
    >>> t.summary()["execute"]["seconds"]
    """

    def __init__(self):
        self.spans: Dict[str, Dict[str, float]] = {}
        #: per-span list of individual durations (s/round series etc.)
        self.series: Dict[str, list] = {}

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

    def add(self, name: str, seconds: float) -> None:
        s = self.spans.setdefault(name, {"seconds": 0.0, "count": 0.0})
        s["seconds"] += float(seconds)
        s["count"] += 1.0
        self.series.setdefault(name, []).append(float(seconds))

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
