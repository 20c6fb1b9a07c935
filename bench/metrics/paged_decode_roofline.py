"""paged_decode_roofline (kernels): the paged decode-attention kernel's
share of its roofline, in percent.

Least time is, per call, the larger of FLOPs over peak FLOP/s and bytes
over peak HBM bytes/s, for the pages the rows' lengths need (the
architecture's ``Shape.paged_decode_cost``), summed over every layer of
every decode pass in the traced window; divided by the kernel's device time
there. Dead rows and table entries past a row's length need no work, so the
time the kernel spends walking them counts against it."""
from roofline import least_time


def read(ctx):
    calls, seconds = ctx.kernel("paged_decode")
    if not calls or seconds <= 0:
        return None
    least = 0.0
    for it in ctx.window_iterations():
        if it.decode_lengths:
            f, b = ctx.shape.paged_decode_cost(it.decode_lengths)
            least += least_time(f, b, ctx.peak)[0] * ctx.shape.layers
    return 100.0 * least / seconds
