"""chan_step_ms (ms): device time per round of the ops in the program's
``chan_step`` scope: the fading step, a fresh (W, D) Rayleigh draw kept
at coherence boundaries.  See ``harness/scopes.py``."""


def read(ctx):
    from harness import scopes
    return scopes.scope_ms(ctx, "chan_step")
