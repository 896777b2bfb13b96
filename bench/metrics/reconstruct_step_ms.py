"""reconstruct_step_ms: device milliseconds per train step inside the
program's `reconstruct` scope (`reconstruct`, run in the sketched
linears' backward), in the window's own trace (`bench/scopes.py`)."""
from bench import scopes


def read(ctx):
    return scopes.step_ms(ctx, "reconstruct")
