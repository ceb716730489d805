"""Faults planted underneath the harness, to show that ``correct`` catches
them.  Runs never use this module: the tests under ``bench/tests`` and
``bench/tools/calibrate.py`` wrap the program's ``train_step`` with one of
these before it is jitted.
"""
from __future__ import annotations


def unchanged(step):
    """A step that returns its state unchanged."""
    def f(state, batch, key):
        _, m = step(state, batch, key)
        return state, m
    return f


def half_batch(step):
    """Half of each worker's batch left out, the loss the mean over the
    rest (the batch is one sequence a worker: its first half of positions
    is kept)."""
    def f(state, batch, key):
        t = batch["tokens"]
        return step(state, {"tokens": t[..., :t.shape[-1] // 2]}, key)
    return f


def altered(step):
    """An answer altered where it is produced: the round's new global
    model off by a gain of 1%, as a receiver whose power scaling is 1%
    wrong would make it."""
    import jax

    def f(state, batch, key):
        new, m = step(state, batch, key)
        Theta = jax.tree.map(lambda x: (x * 1.01).astype(x.dtype), new.Theta)
        return new._replace(Theta=Theta), m
    return f


def no_exchange(step):
    """The exchange between chips left out: every explicit ``psum`` of the
    step (the shard-local round's energy consensus and segment sums) is
    traced as the identity, so each chip keeps its own partial sums."""
    import jax

    def f(state, batch, key):
        psum = jax.lax.psum
        jax.lax.psum = lambda x, *a, **k: x
        try:
            return step(state, batch, key)
        finally:
            jax.lax.psum = psum
    return f


FAULTS = {"unchanged": unchanged, "half_batch": half_batch,
          "altered": altered}
#: faults that exist only across chips
MESH_FAULTS = {"no_exchange": no_exchange}
