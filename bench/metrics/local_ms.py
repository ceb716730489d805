"""local_ms (ms): device time per round of the ops in the program's
``local_steps`` scope: the workers' local-step scan (forward, backward,
penalty gradient, optimizer update), a ``while`` counted once with its
body's ops.  See ``harness/scopes.py``."""


def read(ctx):
    from harness import scopes
    return scopes.scope_ms(ctx, "local_steps")
