"""Whole-step share of the chip's bf16 peak: the model FLOPs of every
prompt and output token processed in the span, over the span and the peak.

Counted from shapes, the same however the work is done: a token at
position p (context c = p + 1) costs

    2 * N + 4 * L * Nq * H * c

with N the matmul parameters of the full-depth forward (attention and FFN
projections of every layer, plus the output head; the embedding gather and
the exit probes excluded).  A request whose first token showed had its
whole prompt processed; each later token showed after one decode step at
the next position.  The step that only discards a request's last sample is
not counted."""

def matmul_params(m):
    d, f = m["hidden_size"], m["intermediate_size"]
    nq, nkv, h = (m["num_attention_heads"], m["num_key_value_heads"],
                  m["head_dim"])
    ffn = (3 if m["hidden_act"] == "silu" else 2) * d * f
    layer = 2 * d * nq * h + 2 * d * nkv * h + ffn
    return m["num_hidden_layers"] * layer + m["vocab_size"] * d


def token_flops(m, c):
    """Forward FLOPs of one token that attends to c positions."""
    return 2.0 * matmul_params(m) + 4.0 * m["num_hidden_layers"] \
        * m["num_attention_heads"] * m["head_dim"] * c


def request_flops(m, plen, first, last):
    """FLOPs behind tokens first..last (1-based) of a request's output:
    token 1 needs the prompt, token j > 1 one decode at position
    plen + j - 2."""
    f = 0.0
    if first <= 1 <= last:
        f += 2.0 * matmul_params(m) * plen + 4.0 * m["num_hidden_layers"] \
            * m["num_attention_heads"] * m["head_dim"] * plen * (plen + 1) / 2
    for j in range(max(first, 2), last + 1):
        f += token_flops(m, plen + j - 1)
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
            total += request_flops(ctx.model, lg.requests[i].tokens.size,
                                   js[0], js[-1])
    return 100.0 * total / ((b - a) * ctx.peaks["bf16_flops_per_s"])
