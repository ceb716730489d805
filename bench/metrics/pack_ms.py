"""pack_ms (ms): device time per round of the ops in the program's
``ota_pack`` scope: packing θ into the (W, D) plane, and the slice views
of λ, h and Θ out of their planes.  See ``harness/scopes.py``."""


def read(ctx):
    from harness import scopes
    return scopes.scope_ms(ctx, "ota_pack")
