"""engine_host_ms (engine): host work per scheduler iteration, timed inside
the engine.

Mean, over the engine's ``engine.step`` spans that start in the traced
window, of the span's length less the time in its ``engine.decode.sync``
and ``engine.chunk.sync`` spans, in which the host waits for the device's
tokens (``enginetrace``). The inside twin of ``iter_host_ms``. None where
the trace holds no engine span (a program from before them); a step or a
pass that dispatched without its sync span raises: a renamed span, not a
zero."""
import enginetrace

PASSES = (("engine.decode", "engine.decode.sync"),
          ("engine.chunk", "engine.chunk.sync"))


def read(ctx):
    et = enginetrace.for_run(ctx)
    if et is None or not et.engine:
        return None
    steps = et.engine_spans("engine.step")
    if not steps:
        raise LookupError("the trace holds engine spans but no engine.step "
                          "in the window: was it renamed?")
    host = []
    for a, b, _ in steps:
        wait = 0
        for name, sync in PASSES:
            for x, y, stats in et.spans_within(name, a, b):
                inner = et.spans_within(sync, x, y)
                # a chunk pass that scheduled no row dispatches nothing
                if not inner and stats.get("positions") != 0:
                    raise LookupError(f"an {name} span holds no {sync} "
                                      f"span: was it renamed?")
                wait += sum(q - p for p, q, _ in inner)
        host.append(b - a - wait)
    return sum(host) / len(host) / 1e6
