"""Blocking device-to-host reads per decode step, from the scheduler's
host spans: the ``sched.sync.*`` spans that start inside a ``sched.step``
span, over the ``sched.step`` spans that start in the traced window.  Two
exit probes, the token readback and a counter flush every 32 steps read
3.03.  None where the trace holds no ``sched.step``."""
import spans


def read(ctx):
    if not ctx.trace:
        return None
    steps = spans.named(ctx.trace, "sched.step")
    if not steps:
        return None
    syncs = [e for e in ctx.trace["spans"] if e[0].startswith("sched.sync.")]
    return sum(len(spans.inside(syncs, e)) for e in steps) / len(steps)
