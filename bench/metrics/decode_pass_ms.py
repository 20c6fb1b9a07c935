"""decode_pass_ms (steps): mean device time per call of the decode program
(``steps.serve_step``, jitted by the engine as ``_decode``)."""


def read(ctx):
    calls, seconds = ctx.program("decode")
    return seconds / calls * 1e3 if calls else None
