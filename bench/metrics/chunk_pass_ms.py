"""chunk_pass_ms (steps): mean device time per call of the chunked-prefill
program (``steps.chunk_step``, jitted by the engine as ``_chunk``)."""


def read(ctx):
    calls, seconds = ctx.program("chunk")
    return seconds / calls * 1e3 if calls else None
