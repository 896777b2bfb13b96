"""device_idle_share: the share of the traced window in which no
operation ran on the device. The window runs from the start of the first
to the end of the last train-step program in the trace."""
from bench import trace


def read(ctx):
    if ctx.window is None:
        return None
    lo, hi = ctx.window
    return 100.0 * (1.0 - trace.busy_us(ctx.window_trace, lo, hi)
                    / (hi - lo))
