"""chunk_fill (steps): share of the chunk program's positions that hold a
real prompt token, in percent.

100 times the sum of the ``tokens`` counters (prompt tokens the pass
writes) over the sum of the ``positions`` counters (``max_batch x
chunk_size``, what the program computes) of the engine's ``engine.chunk``
spans that start in the traced window and dispatched the program
(``enginetrace``). None where the trace holds no engine span (a program
from before them); raises where the window ran chunk passes but no
``engine.chunk`` span counted positions: a renamed span or counter."""
import enginetrace


def read(ctx):
    et = enginetrace.for_run(ctx)
    if et is None or not et.engine:
        return None
    passes = [s for _, _, s in et.engine_spans("engine.chunk")
              if s.get("positions")]
    positions = sum(s["positions"] for s in passes)
    if not positions:
        if any(it.prompt_tokens for it in ctx.window_iterations()):
            raise LookupError("the window ran chunk passes but no "
                              "engine.chunk span counted positions: was "
                              "the span or its counter renamed?")
        return None
    return 100.0 * sum(s["tokens"] for s in passes) / positions
