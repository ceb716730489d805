"""Device time by the program's layer scopes.

The program names its layers with ``jax.named_scope`` over a fixed
vocabulary (``repro.obs.LAYERS``).  A scope is compile-time metadata: it
lands in each HLO instruction's ``metadata={op_name="jit(train_step)/
<scope>/..."}`` as one path segment, wrapped by the transforms it was
opened under (``transpose(jvp(penalty))``).  A device trace names an op by
its instruction (``harness.trace``), so the compiled step's HLO text maps
each op of the trace to the scopes it ran in.

A scope's device time is, per device, the union of the intervals of its
ops (a ``while`` and its body's ops count once), averaged over the
devices and divided by the window's rounds.  The five top-level scopes
never nest in one another, so their times and ``unscoped_ms`` (the busy
time outside all five) add up to the busy time.  A fusion counts under
the scope of its root instruction; a fusion that carries no op_name of
its own (XLA makes some, for layout) takes that of the last of its fused
instructions that carries one.
"""
from __future__ import annotations

import functools
import re
from typing import Callable, Dict, Optional

from harness import trace as tr

#: the program's top-level layer scopes, which never nest in one another
TOP = ("chan_step", "local_steps", "ota_pack", "ota_receive", "ota_dual")
#: scopes that nest in one top-level scope: ``penalty`` in
#: ``local_steps``, ``ota_noise`` in ``ota_receive``
NESTED = ("penalty", "ota_noise")

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+) .*\{$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'metadata=\{.*?\bop_name="((?:[^"\\]|\\.)*)"')
_CALLS = re.compile(r"\bfusion\(.*\bcalls=%?([\w.\-]+)")


@functools.lru_cache(maxsize=4)
def op_names(program_text: str) -> Dict[str, str]:
    """``{instruction: op_name}`` for every instruction of the HLO text
    that carries one, or that is a fusion whose fused instructions do."""
    out, last, fusions, comp = {}, {}, {}, None
    for line in program_text.splitlines():
        c = _COMPUTATION.match(line)
        if c is not None:
            comp = c.group(1)
            continue
        m = _INSTR.match(line)
        if m is None:
            continue
        o = _OP_NAME.search(line)
        if o is not None:
            out[m.group(1)] = last[comp] = o.group(1)
        else:
            f = _CALLS.search(line)
            if f is not None:
                fusions[m.group(1)] = f.group(1)
    for name, called in fusions.items():
        if called in last:
            out[name] = last[called]
    return out


@functools.lru_cache(maxsize=None)
def _segment(scope: str):
    return re.compile(r"(^|[/(])" + re.escape(scope) + r"([)/]|$)")


def carries(op_name: str, scope: str) -> bool:
    """Whether ``scope`` is a whole path segment of ``op_name``, bare or
    wrapped in transforms: ``penalty`` matches ``.../penalty/mul`` and
    ``vmap(transpose(jvp(penalty)))/mul``, not ``penalty_grad``."""
    return _segment(scope).search(op_name) is not None


def labelled(program_text: str, scope: str) -> set:
    """The instructions whose op_name carries ``scope``."""
    return {i for i, op in op_names(program_text).items()
            if carries(op, scope)}


def names_layers(program_text: str) -> bool:
    """Whether the program opens any layer scope at all (a program built
    before the scopes names none)."""
    return any(carries(op, s) for op in op_names(program_text).values()
               for s in TOP + NESTED)


def _per_round_ms(ctx, in_scope: Callable[[str], bool],
                  outside: bool = False) -> float:
    """Per device, the union of the intervals of the ops ``in_scope`` (or,
    with ``outside``, the busy time outside them), averaged over the
    devices, in ms per round."""
    total = 0
    for ops in ctx.trace.devices.values():
        inside = tr.union(o for o in ops if in_scope(o[2]))
        if outside:
            total += tr.minus(tr.union(ops), inside)
        else:
            total += tr.measure(inside)
    return total / max(len(ctx.trace.devices), 1) / ctx.rounds / 1e6


def scope_ms(ctx, scope: str) -> Optional[float]:
    """Device ms per round of the ops in ``scope``.

    None where the run has no trace, or where the program names its
    layers but no op of ``scope`` ran on a device (a dropped or renamed
    scope, which fails the traced run of a cell that lists the metric).
    0 on a program that opens no layer scope at all: none of its ops is
    in the scope."""
    if ctx.trace is None or not ctx.rounds:
        return None
    if not names_layers(ctx.program_text):
        return 0.0
    inside = labelled(ctx.program_text, scope)
    ms = _per_round_ms(ctx, lambda n: n in inside)
    return ms if ms > 0.0 else None


def unscoped_ms(ctx) -> Optional[float]:
    """Device ms per round outside the five top-level scopes: the busy
    time less the union of every top-level scope's ops."""
    if ctx.trace is None or not ctx.rounds:
        return None
    scoped = set().union(*(labelled(ctx.program_text, s) for s in TOP))
    return _per_round_ms(ctx, lambda n: n in scoped, outside=True)
