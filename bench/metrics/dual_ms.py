"""dual_ms (ms): device time per round of the ops in the program's
``ota_dual`` scope: the dual update λ' = λ + ρh(θ − Θ).  See
``harness/scopes.py``."""


def read(ctx):
    from harness import scopes
    return scopes.scope_ms(ctx, "ota_dual")
