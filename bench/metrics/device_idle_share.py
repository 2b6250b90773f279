"""Share of the traced window in which no op ran on the chip: one minus
the union of the XLA Ops intervals over the window."""
import devtrace


def read(ctx):
    if not ctx.trace or not ctx.trace["ops"]:
        return None
    a, b = devtrace.window(ctx.trace)
    return 100.0 * (1.0 - devtrace.busy_s(ctx.trace) / ((b - a) * 1e-9))
