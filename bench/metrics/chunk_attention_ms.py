"""chunk_attention_ms (kernels): device time of the chunk pass's attention
per call of the chunk program.

The device time of the chunk program's ops under the named scope
``paged_chunk_attention`` (``kernels/ops.py``), over the program's
executions that start in the traced window, divided by their number. The
scope of each op comes from the compiled program (``enginetrace``), and the
map is written beside the trace. None where the trace holds no engine span
(a program from before the spans and scopes); raises where the window ran
the chunk program but none of its ops carries that scope: a renamed or
lost scope, not a zero."""
import enginetrace

SCOPE = "paged_chunk_attention"


def read(ctx):
    et = enginetrace.for_run(ctx)
    if et is None or not et.engine:
        return None
    scopes = enginetrace.scopes_for_run(ctx)
    calls, _ = ctx.reduction.program("chunk")
    if not calls:
        return None
    ns = et.scoped(SCOPE, "chunk", scopes)
    if not ns:
        raise LookupError(f"the window ran the chunk program but none of "
                          f"its ops is scoped {SCOPE}: was the scope "
                          f"renamed or lost?")
    return ns / calls / 1e6
