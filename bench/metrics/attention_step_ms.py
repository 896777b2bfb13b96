"""attention_step_ms: device milliseconds per train step inside the
program's `attention` scope (`chunked_causal_attention`: the causal
core's forward, its backward and the layer scan's recompute of it), in
the window's own trace (`bench/scopes.py`)."""
from bench import scopes


def read(ctx):
    return scopes.step_ms(ctx, "attention")
