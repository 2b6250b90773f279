"""Share of its roofline that the exit-head entropy kernel reaches.

The kernel (``repro/kernels/exit_head.py``; in the trace the op
``%exit_head_entropy.<n>``, named after its jitted wrapper) turns B decode hiddens [B, D] and one exit head's
bf16 weights [D, V] into B entropies.  What the algorithm needs, from the
shapes alone (the wrapper's padding is not counted):

    flops = 2 * B * D * V
    bytes = 2 * D * V + 2 * B * D          (bf16 weights and inputs)

The least time is the larger of flops / peak FLOP/s and bytes / peak
bytes/s; the share is that over the kernel's mean device time per call in
the trace.  At decode batch sizes the bytes bound it."""
import devtrace

NAMES = [r"^%exit_head_entropy\b"]


def least_time_s(b, d, v, peaks):
    """(seconds, which bound) the chip needs at least for one call."""
    t_flops = 2.0 * b * d * v / peaks["bf16_flops_per_s"]
    t_bytes = (2.0 * d * v + 2.0 * b * d) / peaks["hbm_bytes_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_flops else (t_flops, "flops")


def read(ctx):
    if not ctx.trace or ctx.peaks is None:
        return None
    ev = devtrace.matching(ctx.trace["ops"], NAMES,
                           devtrace.window(ctx.trace))
    if not ev:
        return None
    per_call = sum(e[2] for e in ev) / len(ev) * 1e-9
    t, bound = least_time_s(ctx.n_slots, ctx.model["hidden_size"],
                            ctx.model["vocab_size"], ctx.peaks)
    print(f"exit_head_roofline: {len(ev)} calls, {per_call * 1e6:.1f} us "
          f"each, bound by {bound} at {t * 1e6:.1f} us")
    return 100.0 * t / per_call
