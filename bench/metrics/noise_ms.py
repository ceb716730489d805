"""noise_ms (ms): device time per round of the ops in the program's
``ota_noise`` scope, inside ``ota_receive``: the receiver's D-long
matched-filter noise draw.  See ``harness/scopes.py``."""


def read(ctx):
    from harness import scopes
    return scopes.scope_ms(ctx, "ota_noise")
