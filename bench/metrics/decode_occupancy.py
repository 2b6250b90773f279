"""Mean share of the pool's slots that held a request, over the decode
steps of the window (StepReport.n_active / n_slots)."""


def read(ctx):
    a, b = ctx.span
    occ = [p.n_active / ctx.n_slots for p in ctx.log.polls
           if p.decode_stepped and a <= p.start < b]
    return 100.0 * sum(occ) / len(occ) if occ else None
