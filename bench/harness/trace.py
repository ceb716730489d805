"""From a profiler trace (``.xplane.pb``) to intervals, and from intervals to
the numbers the per-layer metrics read.

A device's operations are the events on the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane, each named by its HLO instruction (an event's
name is the instruction's whole text, ``%fusion.12 = f32[...] fusion(...)``;
the name is what comes before `` = ``).  An operation that holds others, as
a ``while`` holds its body's, spans them.  The harness's own host spans
(``data``, ``dispatch``, ``readback``) are events of those names on the
host plane.
Both share the trace's clock.  Everything below the loader is plain
interval arithmetic on ``(start_ns, end_ns, name)`` tuples, so it can be
checked on hand-built traces.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Callable, Dict, Iterable, List, Sequence, Tuple

Interval = Tuple[int, int, str]

HOST_SPANS = ("data", "dispatch", "readback")

#: an operation that moves data between chips
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all")


@dataclasses.dataclass
class Trace:
    #: device plane name -> its operations
    devices: Dict[str, List[Interval]]
    #: the harness's host spans
    host: List[Interval]


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir}, "
                                f"found {paths}")
    return paths[0]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[str, List[Interval]] = {}
    host: List[Interval] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend((int(e.start_ns), int(e.start_ns + e.duration_ns),
                                instruction(e.name)) for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((int(e.start_ns), int(e.start_ns + e.duration_ns),
                             e.name) for e in line.events
                            if e.name in HOST_SPANS)
    return Trace(devices={k: sorted(v) for k, v in devices.items() if v},
                 host=sorted(host))


def instruction(event_name: str) -> str:
    """``fusion.12`` for ``%fusion.12 = f32[8]{0} fusion(...)``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------

def union(intervals: Iterable[Interval]) -> List[Tuple[int, int]]:
    """Merged, sorted ``(start, end)`` pairs covering the intervals."""
    out: List[List[int]] = []
    for s, e, *_ in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def measure(merged: Sequence[Tuple[int, int]]) -> int:
    return sum(e - s for s, e in merged)


def minus(a: Sequence[Tuple[int, int]], b: Sequence[Tuple[int, int]]) -> int:
    """Length of the part of merged ``a`` that merged ``b`` does not cover."""
    total, j = 0, 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                total += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            total += e - cur
    return total


def gaps(merged: Sequence[Tuple[int, int]], start: int, end: int
         ) -> List[Tuple[int, int]]:
    """The idle stretches of ``[start, end]`` between merged busy spans."""
    out, cur = [], start
    for s, e in merged:
        if s > cur:
            out.append((cur, min(s, end)))
        cur = max(cur, e)
        if cur >= end:
            break
    if cur < end:
        out.append((cur, end))
    return [(s, e) for s, e in out if e > s]


# ---------------------------------------------------------------------------
# what the readers take from a trace
# ---------------------------------------------------------------------------

def window(tr: Trace) -> Tuple[int, int]:
    """The traced window: from the first harness span to the last."""
    if not tr.host:
        raise ValueError("the trace holds none of the harness's host spans")
    return tr.host[0][0], max(e for _, e, _ in tr.host)


def busy_ns(tr: Trace) -> Dict[str, int]:
    """Per device, the union of its operations' intervals."""
    return {d: measure(union(ops)) for d, ops in tr.devices.items()}


def op_time_ns(tr: Trace, match: Callable[[str], bool]) -> int:
    """Summed device time of the operations whose names ``match``, over all
    devices."""
    return sum(e - s for ops in tr.devices.values()
               for s, e, n in ops if match(n))


def exposed_ns(tr: Trace, is_collective: Callable[[str], bool]
               ) -> Dict[str, int]:
    """Per device, the time in which a collective runs and no other
    operation does."""
    out = {}
    for d, ops in tr.devices.items():
        coll = union(o for o in ops if is_collective(o[2]))
        comp = union(o for o in ops if not is_collective(o[2]))
        out[d] = minus(coll, comp)
    return out


def mosaic_calls(program_text: str) -> Dict[str, str]:
    """The Mosaic kernels of a compiled program's HLO text: ``{instruction
    name: its line}``.  A device trace names an operation by its HLO
    instruction."""
    out = {}
    for line in program_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line and " = " in line:
            out[line.split(" = ", 1)[0].strip().lstrip("%")] = line
    return out


def top_ops(tr: Trace, n: int = 10) -> List[List]:
    """The ``n`` operations that took most device time, in seconds averaged
    over the devices."""
    tot: Dict[str, int] = {}
    for ops in tr.devices.values():
        for s, e, name in ops:
            tot[name] = tot.get(name, 0) + (e - s)
    k = max(len(tr.devices), 1)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / k / 1e9] for name, ns in best]


def idle_gaps(tr: Trace, n: int = 10) -> List[List]:
    """The ``n`` longest stretches in which the first device ran nothing,
    each named by the harness span the host was in at its middle."""
    if not tr.devices:
        return []
    start, end = window(tr)
    first = sorted(tr.devices)[0]
    idle = gaps(union(tr.devices[first]), start, end)
    out = []
    for s, e in sorted(idle, key=lambda g: g[0] - g[1])[:n]:
        mid = (s + e) // 2
        name = next((h[2] for h in tr.host if h[0] <= mid < h[1]), "host")
        out.append([name, (e - s) / 1e9])
    return out
