"""unscoped_ms (ms): device time per round outside the program's five
top-level layer scopes (``chan_step``, ``local_steps``, ``ota_pack``,
``ota_receive``, ``ota_dual``): what no scope names yet.  With them it
adds up to the busy time per round.  See ``harness/scopes.py``."""


def read(ctx):
    from harness import scopes
    return scopes.unscoped_ms(ctx)
