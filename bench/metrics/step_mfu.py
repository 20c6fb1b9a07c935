"""step_mfu (whole step): useful model FLOPs over the chip's peak, percent.

The FLOPs of the real prompt tokens written and the output tokens streamed
in the traced window (the architecture's ``Shape.span_flops``,
``token_flops`` and ``head_flops``, counted by ``serve.Feeder``; padded
chunk positions and recompute re-prefills are not useful work),
over the traced window's length times chips times peak bf16 FLOP/s."""


def read(ctx):
    its = ctx.window_iterations()
    flops = sum(it.flops for it in its)
    if not flops:
        return None
    peak = ctx.peak["bf16_flops_per_s"] * ctx.chips
    return 100.0 * flops / (ctx.reduction.window_s * peak)
