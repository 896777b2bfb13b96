"""mfu: the model operations of one training step (`flops.train_step`,
from shapes) over the device's period per step in the trace (from the
start of the first to the start of the last train-step program, over
the steps between), as a share of the chip's bf16 peak."""
from bench import peaks


def read(ctx):
    if ctx.window_trace is None:
        return None
    starts = [e["ts"] for p in ctx.window_trace.device_pids()[:1]
              for e in ctx.window_trace.modules(p, ctx.step_module)]
    if len(starts) < 2:
        return None
    period_s = (starts[-1] - starts[0]) / (len(starts) - 1) * 1e-6
    peak = peaks.peaks(ctx.device_kind)["bf16_flops_per_s"]
    return 100.0 * ctx.flops_per_step / period_s / peak
