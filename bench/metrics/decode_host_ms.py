"""Host time of a decode step, from the scheduler's host spans: each
``sched.step`` span that starts in the traced window, less the part of it
its ``sched.sync.*`` spans cover (time blocked on the chip), mean over the
steps.  What is left is uploads, dispatch, the commit loop and the host
side of counter flushes.  None where the trace holds no ``sched.step``."""
import devtrace
import spans


def read(ctx):
    if not ctx.trace:
        return None
    steps = spans.named(ctx.trace, "sched.step")
    if not steps:
        return None
    syncs = [e for e in ctx.trace["spans"] if e[0].startswith("sched.sync.")]
    host = 0.0
    for e in steps:
        _, s, d = e
        host += d - devtrace.union_ns(spans.inside(syncs, e), (s, s + d))
    return host / len(steps) * 1e-6
