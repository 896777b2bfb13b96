"""adamw_step_ms: device milliseconds per train step inside the
program's `adamw` scope (`adamw_update`: clipping and the update of
every parameter and moment, and the NaN guard's select that XLA fuses
the update into), in the window's own trace (`bench/scopes.py`)."""
from bench import scopes


def read(ctx):
    return scopes.step_ms(ctx, "adamw")
