"""sketch_update_step_ms: device milliseconds per train step inside the
program's `sketch_update` scope (`ema_triple_update` and
`ema_apply_increment`, whichever kernel path runs), in the window's own
trace (`bench/scopes.py`)."""
from bench import scopes


def read(ctx):
    return scopes.step_ms(ctx, "sketch_update")
