"""Device time of the decode stages per decode step, from the trace's XLA
Modules line: the depth segments (``jit_stage``), the exit probes
(``jit_probe``) and finalize (``jit_finalize``), over the number of
finalize executions (one per step)."""
import devtrace

NAMES = [r"^jit_stage\b", r"^jit_probe\b", r"^jit_finalize\b"]


def read(ctx):
    if not ctx.trace:
        return None
    w = devtrace.window(ctx.trace)
    ev = devtrace.matching(ctx.trace["modules"], NAMES, w)
    steps = devtrace.matching(ctx.trace["modules"], NAMES[2:], w)
    return sum(e[2] for e in ev) / len(steps) * 1e-6 if steps else None
