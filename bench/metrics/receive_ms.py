"""receive_ms (ms): device time per round of the ops in the program's
``ota_receive`` scope: modulate, power scale, superpose, the noise draw,
demodulate.  See ``harness/scopes.py``."""


def read(ctx):
    from harness import scopes
    return scopes.scope_ms(ctx, "ota_receive")
