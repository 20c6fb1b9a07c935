"""The engine's host spans and counters, read back from a profiler trace.

A tiny chunked ``Engine`` serves a few requests on the CPU under
``jax.profiler.start_trace``; the benchmark's own reduction
(``bench/enginetrace.py``) reads the ``.xplane.pb``. What must hold there:
each ``engine.step`` holds its decode and chunk passes in order, each pass
its phases in order; the counters equal what the passes computed; the
child spans cover the step; a compile is named on its dispatch span; and
the profiler changes no token.
"""
import os
import sys

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_reduced_config
from repro.engine.runner import Engine, EngineConfig
from repro.models import transformer as tf

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
B, CHUNK, BT, MAX_LEN = 2, 16, 16, 96
PROMPTS = (37, 9, 21)
NEW = 6


def _enginetrace():
    # bench/ is put on the path only while the module imports: it holds
    # modules (run, serve, ...) whose names must not shadow others
    bench = os.path.join(ROOT, "bench")
    sys.path.insert(0, bench)
    try:
        import enginetrace
    finally:
        sys.path.remove(bench)
    return enginetrace


enginetrace = _enginetrace()


class Spy:
    """A jitted pass that records what each call was given."""

    def __init__(self, fn, record):
        self.fn, self.record, self.calls = fn, record, []

    def __call__(self, *args):
        self.calls.append(self.record(*args))
        return self.fn(*args)

    def __getattr__(self, name):
        return getattr(self.fn, name)


@pytest.fixture(scope="module")
def cfg():
    return get_reduced_config("gemma_2b")


@pytest.fixture(scope="module")
def params(cfg):
    p, _ = tf.init_model(cfg, jax.random.PRNGKey(0), zero_out=False)
    return p


def _engine(cfg, params, **kw):
    return Engine(cfg, params=params, max_batch=B, max_len=MAX_LEN,
                  block_tokens=BT, config=EngineConfig(chunk_size=CHUNK),
                  **kw)


def _submit(eng, prompts=PROMPTS):
    rng = np.random.default_rng(3)
    return [eng.submit(rng.integers(0, 200, n).astype(np.int32), NEW)
            for n in prompts]


def _traced(eng, trace_dir):
    jax.profiler.start_trace(str(trace_dir))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            eng.run()
    finally:
        jax.profiler.stop_trace()
    pd = ProfileData.from_file(
        enginetrace.devtrace.find_xplane(str(trace_dir)))
    return enginetrace.read_profile(pd)


@pytest.fixture(scope="module")
def served(cfg, params, tmp_path_factory):
    eng = _engine(cfg, params)
    eng._chunk = Spy(eng._chunk,
                     lambda p, toks, q_valid, c: np.asarray(q_valid).copy())
    eng._decode = Spy(eng._decode, lambda *args: None)
    reqs = _submit(eng)
    red = _traced(eng, tmp_path_factory.mktemp("trace"))
    return eng, reqs, red


def _inside(red, name, span):
    a, b, _ = span
    return red.spans_within(name, a, b)


def _in_order(spans):
    return all(x[1] <= y[0] for x, y in zip(spans, spans[1:]))


def test_one_step_span_per_iteration(served):
    eng, _, red = served
    steps = red.engine_spans("engine.step")
    assert len(steps) == eng.steps > 0
    (call,) = red.engine_spans("engine.run")       # one Engine.run call
    assert call[0] <= steps[0][0] and steps[-1][1] <= call[1]
    # each step admits before its passes
    for step in steps:
        (admit,) = _inside(red, "engine.admit", step)
        passes = (_inside(red, "engine.decode", step)
                  + _inside(red, "engine.chunk", step))
        assert passes and all(admit[1] <= p[0] for p in passes)


def test_every_step_holds_its_passes_in_order(served):
    _, _, red = served
    for step in red.engine_spans("engine.step"):
        dec = _inside(red, "engine.decode", step)
        chunk = _inside(red, "engine.chunk", step)
        assert len(dec) <= 1 and len(chunk) == 1
        assert _in_order(dec + chunk)
        for d in dec:
            phases = [_inside(red, f"engine.decode.{p}", d)
                      for p in ("push", "dispatch", "sync", "emit")]
            assert [len(p) for p in phases] == [1, 1, 1, 1]
            assert _in_order([p[0] for p in phases])
        c = chunk[0]
        names = (("schedule", "push", "dispatch", "sync", "emit")
                 if c[2]["positions"] else ("schedule",))
        phases = [_inside(red, f"engine.chunk.{p}", c) for p in names]
        assert [len(p) for p in phases] == [1] * len(names)
        assert _in_order([p[0] for p in phases])


def test_chunk_counters_are_what_the_pass_computed(served):
    eng, _, red = served
    passes = [s for _, _, s in red.engine_spans("engine.chunk")
              if s["positions"]]
    q_valid = eng._chunk.calls
    assert [s["tokens"] for s in passes] == [int(q.sum()) for q in q_valid]
    assert all(s == {"tokens": s["tokens"], "positions": B * CHUNK}
               for s in passes)
    # chunk passes that dispatched nothing say so
    assert all(s == {"tokens": 0, "positions": 0}
               for _, _, s in red.engine_spans("engine.chunk")
               if not s["positions"])
    assert sum(s["tokens"] for s in passes) == sum(PROMPTS)


def test_one_decode_span_per_decode_dispatch(served):
    eng, _, red = served
    dec = red.engine_spans("engine.decode")
    assert len(dec) == len(eng._decode.calls) > 0
    for d in dec:
        assert len(_inside(red, "engine.decode.dispatch", d)) == 1


def test_a_compile_is_named_on_its_dispatch(served):
    _, _, red = served
    for name in ("engine.decode.dispatch", "engine.chunk.dispatch"):
        flags = [s.get("compiled", 0) for _, _, s in red.engine_spans(name)]
        assert flags[0] == 1 and not any(flags[1:]), name


def test_child_spans_cover_the_step(served):
    _, _, red = served
    assert red.step_self_share() < 0.05


def test_preemption_is_named_inside_growth(cfg, params, tmp_path):
    # two requests of 3 pages each in a pool of 4: growth preempts
    eng = _engine(cfg, params, num_blocks=4, preemption="recompute")
    reqs = _submit(eng, (40, 40))
    red = _traced(eng, tmp_path)
    pre = red.engine_spans("engine.preempt")
    assert len(pre) == sum(r.preemptions for r in reqs) > 0
    # a preemption happens while the engine grows a table
    for p in pre:
        assert any(g[0] <= p[0] and p[1] <= g[1]
                   for g in red.engine_spans("engine.grow"))


def test_streams_are_identical_with_the_profiler_on_and_off(served, cfg,
                                                            params):
    _, traced_reqs, _ = served
    eng = _engine(cfg, params)
    reqs = _submit(eng)
    eng.run()
    assert [r.tokens for r in reqs] == [r.tokens for r in traced_reqs]
