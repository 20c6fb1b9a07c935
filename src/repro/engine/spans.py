"""The engine's host spans: their names, and the dispatch of a jitted pass.

A span is a ``jax.profiler.TraceAnnotation``. While a profiler session runs
it writes one event into the same ``.xplane.pb`` as the device's operations,
on the same clock, with its keyword arguments (and ``set_metadata``'s) as
the event's stats; with no session it costs about a microsecond.
``bench/enginetrace.py`` reads every host span whose name starts with
``engine.``.

One ``Engine.run`` call, and one iteration of the mixed (chunked) path in
it, with the counters a span carries:

    engine.run                   the call: its loop around the steps
    engine.step                  one iteration
      engine.admit
      engine.grow                (engine.preempt inside, when the pool is full)
      engine.decode
        engine.decode.push       block tables and token rows to the device
        engine.decode.dispatch   compiled=1 when the call compiled a program
        engine.decode.sync       the host waits for the pass's tokens
        engine.decode.emit       stream tokens, advance the store, finish rows
      engine.chunk               tokens (prompt tokens written), positions
                                 (max_batch x chunk_size; 0 when no row ran)
        engine.chunk.schedule    budget and takes (engine.grow inside)
        engine.chunk.push / .dispatch / .sync / .emit, as for decode
    engine.preempt
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation

RUN = "engine.run"
STEP = "engine.step"
ADMIT = "engine.admit"
GROW = "engine.grow"
PREEMPT = "engine.preempt"
DECODE = "engine.decode"
DECODE_PUSH = "engine.decode.push"
DECODE_DISPATCH = "engine.decode.dispatch"
DECODE_SYNC = "engine.decode.sync"
DECODE_EMIT = "engine.decode.emit"
CHUNK = "engine.chunk"
CHUNK_SCHEDULE = "engine.chunk.schedule"
CHUNK_PUSH = "engine.chunk.push"
CHUNK_DISPATCH = "engine.chunk.dispatch"
CHUNK_SYNC = "engine.chunk.sync"
CHUNK_EMIT = "engine.chunk.emit"


def dispatch(name: str, fn, *args):
    """``fn(*args)`` under span ``name``, for a jitted ``fn``. The span
    carries ``compiled=1`` when the call grew ``fn``'s cache of programs
    (compiled one, or loaded it from the persistent cache), so a compile
    has a name in the trace."""
    with TraceAnnotation(name) as s:
        before = fn._cache_size()
        out = fn(*args)
        if fn._cache_size() > before:
            s.set_metadata(compiled=1)
    return out
