"""Mean time an admission takes, from the scheduler's host spans: for each
``sched.go_live`` that ends in the traced window, from the start of the
latest ``sched.admit`` before it (one admission is pending at a time) to
the end of the ``go_live``: staging the prompts, the fresh arena, every
prefill chunk, the merge and the first tokens.  None where the trace holds
no such span."""
import bisect

import devtrace


def read(ctx):
    sp = ctx.trace.get("spans") if ctx.trace else None
    if not sp:
        return None
    w = devtrace.window(ctx.trace)
    admits = sorted(s for name, s, _ in sp if name == "sched.admit")
    took = []
    for name, s, d in sp:
        if name == "sched.go_live" and w[0] < s + d <= w[1]:
            i = bisect.bisect_right(admits, s) - 1
            if i >= 0:
                took.append(s + d - admits[i])
    return sum(took) / len(took) * 1e-6 if took else None
