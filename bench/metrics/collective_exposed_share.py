"""collective_exposed_share (%): the share of the traced window in which a
collective (all-reduce, all-gather, reduce-scatter, collective-permute,
all-to-all) runs on a device and no other operation does, the largest over
the cell's devices."""


def read(ctx):
    if ctx.trace is None:
        return None
    from harness import trace as tr
    exposed = tr.exposed_ns(ctx.trace, lambda n: bool(tr.COLLECTIVE.search(n)))
    if not any(tr.COLLECTIVE.search(n) for ops in ctx.trace.devices.values()
               for _, _, n in ops):
        return None
    return 100.0 * max(exposed.values()) / 1e9 / ctx.trace_window_s
