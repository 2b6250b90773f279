"""Whole-step share of the chip's bf16 peak: the model FLOPs of every
prompt and output token processed in the span, over the span and the peak.

Counted from shapes, the same however the work is done: a token at
position p (context c = p + 1) costs

    2 * N + A(c)

with N the matmul parameters of the full-depth forward that one token
uses and A(c) the attention FLOPs of one token that attends to c
positions.  Both come from the configuration's reference module
(``ctx.reference``: ``matmul_params(m)``, ``attention_flops(m, c)``),
which knows the architecture: a dense decoder counts every layer's
attention and FFN projections and the output head, a mixture of experts
its router, shared experts and the experts a token is routed to, never
the experts it skips.  A request whose first token showed had its whole
prompt processed; each later token showed after one decode step at the
next position.  The step that only discards a request's last sample is
not counted."""


def token_flops(ref, m, c):
    """Forward FLOPs of one token that attends to c positions."""
    return 2.0 * ref.matmul_params(m) + ref.attention_flops(m, c)


def request_flops(ref, m, plen, first, last):
    """FLOPs behind tokens first..last (1-based) of a request's output:
    token 1 needs the prompt, token j > 1 one decode at position
    plen + j - 2."""
    f = 0.0
    if first <= 1 <= last:
        f += 2.0 * ref.matmul_params(m) * plen + sum(
            ref.attention_flops(m, c) for c in range(1, plen + 1))
    for j in range(max(first, 2), last + 1):
        f += token_flops(ref, m, plen + j - 1)
    return f


def read(ctx):
    if ctx.peaks is None:
        return None
    a, b = ctx.span
    lg = ctx.log
    total = 0.0
    for i, vis in lg.visible.items():
        js = [j + 1 for j, t in enumerate(vis) if a < t <= b]
        if js:
            total += request_flops(ctx.reference, ctx.model,
                                   lg.requests[i].tokens.size, js[0], js[-1])
    return 100.0 * total / ((b - a) * ctx.peaks["bf16_flops_per_s"])
