"""penalty_ms (ms): device time per round of the ops in the program's
``penalty`` scope, inside ``local_steps``: the prox penalty gradient
Re{λ*h} + ρ|h|²(θ − Θ) of every local step.  See ``harness/scopes.py``."""


def read(ctx):
    from harness import scopes
    return scopes.scope_ms(ctx, "penalty")
