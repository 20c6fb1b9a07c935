"""The engine's spans and counters and the step programs' named scopes, as
``enginetrace`` reads them: on a trace written out by hand, on the programs
of a tiny engine compiled on the CPU, and on a short window recorded on the
chip."""
import json
import os
import types

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

import devtrace
import enginetrace
import run

# one chip; times in ns. A decode execution 1000..3000 (fusion.7
# 1000..1500, the paged kernel 1500..3000), a chunk execution 5000..9000
# (fusion.7 5000..8000, fusion.9 8000..9000) and a chunk execution after
# the window, 10000..10500. Host: window 0..10000; one harness iteration
# 500..9500 holding one engine step 600..9500 with its decode pass
# 600..3400 and chunk pass 3500..9400; arrivals 9500..9900.
TRACE = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 10 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 11 offset_ps: 5000000 duration_ps: 4000000 }
    events { metadata_id: 11 offset_ps: 10000000 duration_ps: 500000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 500000 }
    events { metadata_id: 2 offset_ps: 1500000 duration_ps: 1500000 }
    events { metadata_id: 1 offset_ps: 5000000 duration_ps: 3000000 }
    events { metadata_id: 3 offset_ps: 8000000 duration_ps: 1000000 }
    events { metadata_id: 1 offset_ps: 10000000 duration_ps: 500000 } }
  event_metadata { key: 10 value { id: 10 name: "jit__decode(3)" } }
  event_metadata { key: 11 value { id: 11 name: "jit__chunk(4)" } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.7 = bf16[8] fusion(%a)" } }
  event_metadata { key: 2 value { id: 2 name: "%paged_decode_attention.3 = bf16[8] custom-call(%b)" } }
  event_metadata { key: 3 value { id: 3 name: "%fusion.9 = bf16[8] fusion(%c)" } }
}
planes { id: 2 name: "/host:CPU"
  lines { id: 5 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 500000 duration_ps: 9000000 }
    events { metadata_id: 3 offset_ps: 9500000 duration_ps: 400000 }
    events { metadata_id: 20 offset_ps: 600000 duration_ps: 8900000 }
    events { metadata_id: 21 offset_ps: 600000 duration_ps: 2800000 }
    events { metadata_id: 22 offset_ps: 600000 duration_ps: 300000 }
    events { metadata_id: 23 offset_ps: 900000 duration_ps: 100000 }
    events { metadata_id: 24 offset_ps: 1000000 duration_ps: 2100000 }
    events { metadata_id: 25 offset_ps: 3100000 duration_ps: 300000 }
    events { metadata_id: 30 offset_ps: 3500000 duration_ps: 5900000
      stats { metadata_id: 5 int64_value: 96 }
      stats { metadata_id: 6 int64_value: 256 } }
    events { metadata_id: 31 offset_ps: 3500000 duration_ps: 500000 }
    events { metadata_id: 32 offset_ps: 4000000 duration_ps: 800000 }
    events { metadata_id: 33 offset_ps: 4800000 duration_ps: 200000 }
    events { metadata_id: 34 offset_ps: 5000000 duration_ps: 4100000 }
    events { metadata_id: 35 offset_ps: 9100000 duration_ps: 300000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.engine_iter" } }
  event_metadata { key: 3 value { id: 3 name: "bench.arrivals" } }
  event_metadata { key: 20 value { id: 20 name: "engine.step" } }
  event_metadata { key: 21 value { id: 21 name: "engine.decode" } }
  event_metadata { key: 22 value { id: 22 name: "engine.decode.push" } }
  event_metadata { key: 23 value { id: 23 name: "engine.decode.dispatch" } }
  event_metadata { key: 24 value { id: 24 name: "engine.decode.sync" } }
  event_metadata { key: 25 value { id: 25 name: "engine.decode.emit" } }
  event_metadata { key: 30 value { id: 30 name: "engine.chunk" } }
  event_metadata { key: 31 value { id: 31 name: "engine.chunk.schedule" } }
  event_metadata { key: 32 value { id: 32 name: "engine.chunk.push" } }
  event_metadata { key: 33 value { id: 33 name: "engine.chunk.dispatch" } }
  event_metadata { key: 34 value { id: 34 name: "engine.chunk.sync" } }
  event_metadata { key: 35 value { id: 35 name: "engine.chunk.emit" } }
  stat_metadata { key: 5 value { id: 5 name: "tokens" } }
  stat_metadata { key: 6 value { id: 6 name: "positions" } }
}
"""
SCOPES = {"decode": {"paged_decode_attention.3": "paged_decode_attention"},
          "chunk": {"fusion.7": "paged_chunk_attention", "fusion.9": "mlp"}}
DATA = os.path.join(os.path.dirname(__file__), "data")


def profile():
    return ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(TRACE))


def engine_trace():
    return enginetrace.read_profile(profile())


def read(monkeypatch, name, et, scopes=SCOPES):
    """Metric ``name`` of a run whose trace is ``et`` and whose programs'
    scope map is ``scopes``."""
    monkeypatch.setattr(enginetrace, "for_run", lambda ctx: et)
    monkeypatch.setattr(enginetrace, "scopes_for_run", lambda ctx: scopes)
    # the feeder's one iteration in the window: a decode row and a chunk
    # pass that wrote 96 prompt tokens
    its = [types.SimpleNamespace(decode_lengths=[100], prompt_tokens=96)]
    ctx = types.SimpleNamespace(reduction=devtrace.reduce_profile(profile()),
                                window_iterations=lambda: its)
    return run.metric_reader(name)(ctx)


def test_engine_spans_and_their_counters():
    et = engine_trace()
    (step,) = et.engine_spans("engine.step")
    assert step == (600, 9500, {})
    (chunk,) = et.engine_spans("engine.chunk")
    assert chunk[2] == {"tokens": 96, "positions": 256}
    assert [s[:2] for s in et.spans_within("engine.decode.sync", *step[:2])
            ] == [(1000, 3100)]


def test_engine_host_ms_is_the_step_less_its_syncs(monkeypatch):
    # 8900 ns of step less 2100 + 4100 ns of waiting
    assert read(monkeypatch, "engine_host_ms", engine_trace()
                ) == pytest.approx(2700 / 1e6)


def test_chunk_fill_is_tokens_over_positions(monkeypatch):
    assert read(monkeypatch, "chunk_fill", engine_trace()
                ) == pytest.approx(37.5)


def test_chunk_attention_ms_reads_the_scoped_ops_of_window_calls(
        monkeypatch):
    # fusion.7 of the chunk execution in the window, over one call; the
    # decode program's fusion.7 and the chunk execution after the window do
    # not count
    et = engine_trace()
    assert et.program_ops[("chunk", "fusion.7")] == (1, 3000)
    assert et.program_ops[("decode", "fusion.7")] == (1, 500)
    assert read(monkeypatch, "chunk_attention_ms", et
                ) == pytest.approx(3000 / 1e6)


def test_new_metrics_read_nothing_without_spans_or_scopes(monkeypatch):
    bare = engine_trace()
    bare.engine = {}
    for name in ("engine_host_ms", "chunk_fill"):
        assert read(monkeypatch, name, bare) is None
        assert read(monkeypatch, name, None) is None
    assert read(monkeypatch, "chunk_attention_ms", None) is None
    assert read(monkeypatch, "chunk_attention_ms", bare,
                {"decode": {}, "chunk": {}}) is None


def _renamed(old, new="engine.renamed"):
    def edit(et):
        et.engine[new] = et.engine.pop(old)
    return edit


def _uncounted(et):
    (chunk,) = et.engine["engine.chunk"]
    chunk[2]["filled"] = chunk[2].pop("positions")


@pytest.mark.parametrize("name,edit,scopes", [
    ("engine_host_ms", _renamed("engine.step"), SCOPES),
    ("engine_host_ms", _renamed("engine.decode.sync"), SCOPES),
    ("engine_host_ms", _renamed("engine.chunk.sync"), SCOPES),
    ("chunk_fill", _renamed("engine.chunk"), SCOPES),
    ("chunk_fill", _uncounted, SCOPES),
    ("chunk_attention_ms", lambda et: None,
     {"decode": SCOPES["decode"], "chunk": {"fusion.9": "mlp"}}),
], ids=["step", "decode_sync", "chunk_sync", "chunk", "positions",
        "scope"])
def test_a_renamed_span_counter_or_scope_raises(monkeypatch, name, edit,
                                                scopes):
    """A trace with engine spans that lacks what a reader needs is a
    renamed span, counter or scope, not a zero."""
    et = engine_trace()
    edit(et)
    with pytest.raises(LookupError):
        read(monkeypatch, name, et, scopes)


def test_a_chunk_pass_that_ran_no_row_needs_no_sync(monkeypatch):
    et = engine_trace()
    (chunk,) = et.engine["engine.chunk"]
    chunk[2].update(tokens=0, positions=0)
    del et.engine["engine.chunk.sync"]
    # the whole chunk pass is host time then: 8900 ns less 2100 ns
    assert read(monkeypatch, "engine_host_ms", et
                ) == pytest.approx(6800 / 1e6)


def test_a_run_without_a_trace_file_reads_nothing(tmp_path, monkeypatch):
    monkeypatch.setattr(enginetrace, "TRACE_ROOT", str(tmp_path))
    ctx = types.SimpleNamespace(spec={"workload": {"name": "cell"}})
    assert enginetrace.for_run(ctx) is None


def test_children_cover_the_step_but_two_gaps():
    # decode 600..3400 and chunk 3500..9400 leave 100 + 100 of 8900 ns
    assert engine_trace().step_self_share() == pytest.approx(200 / 8900)


def test_gaps_are_named_by_the_innermost_engine_span():
    got = sorted((round(t * 1e9), w)
                 for w, t in engine_trace().breakdown(SCOPES)["idle_gaps"])
    # 3000..5000 (midpoint 4000: the chunk pass's push, inside the chunk
    # pass and the step), 0..1000 (500: no engine span, the harness's
    # iteration), 9000..10000 (9500: arrivals)
    assert got == [(1000, "bench.arrivals"), (1000, "bench.engine_iter"),
                   (2000, "engine.chunk.push")]


def test_device_ops_carry_their_scope():
    ops = dict(engine_trace().breakdown(SCOPES)["device_ops"])
    assert ops == {"fusion.7 [paged_chunk_attention]": pytest.approx(3e-6),
                   "paged_decode_attention.3 [paged_decode_attention]":
                       pytest.approx(1.5e-6),
                   "fusion.9 [mlp]": pytest.approx(1e-6),
                   "fusion.7 [unscoped]": pytest.approx(5e-7)}
    # a program without scopes: every op says so
    bare = dict(engine_trace().breakdown({})["device_ops"])
    assert all(n.endswith(" [unscoped]") for n in bare)


def test_scope_map_takes_the_innermost_known_scope():
    text = """HloModule jit__chunk, entry_computation_layout={()->f32[2]}

%fused_computation.1 (param_0: f32[2]) -> f32[2] {
  %param_0 = f32[2]{0} parameter(0)
  ROOT %exp.1 = f32[2]{0} exponential(f32[2]{0} %param_0), metadata={op_name="jit(_chunk)/while/body/attention/paged_chunk_attention/exp" source_file="a.py" source_line=3}
}

ENTRY %main (p: f32[2]) -> f32[2] {
  %p = f32[2]{0} parameter(0)
  %fusion.3 = f32[2]{0} fusion(f32[2]{0} %p), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(_chunk)/while/body/attention/paged_chunk_attention/exp"}
  %dot.2 = f32[2]{0} add(f32[2]{0} %fusion.3, f32[2]{0} %p), metadata={op_name="jit(_chunk)/while/body/mlp/add"}
  ROOT %copy.4 = f32[2]{0} copy(f32[2]{0} %dot.2), metadata={op_name="jit(_chunk)/jit(main)/copy"}
}
"""
    assert enginetrace.scope_map(text) == {
        "exp.1": "paged_chunk_attention", "fusion.3": "paged_chunk_attention",
        "dot.2": "mlp"}


@pytest.fixture(scope="module")
def engine():
    from repro.configs import get_reduced_config
    from repro.engine.core import Engine, EngineConfig
    from repro.models import transformer as tf
    cfg = get_reduced_config("gemma_2b")
    params = tf.init_params(cfg, jax.random.PRNGKey(0), False)
    return Engine(cfg, params=params, max_batch=2, max_len=64,
                  block_tokens=16, config=EngineConfig(chunk_size=16))


def test_the_compiled_programs_name_their_parts(engine):
    """``program_scopes`` finds the chunk pass's attention, the decode
    kernel's reference and the block's parts by scope."""
    scopes = enginetrace.program_scopes(engine)
    assert set(scopes) == {"decode", "chunk"}
    assert {"paged_chunk_attention", "attention", "mlp", "lm_head"} <= set(
        scopes["chunk"].values())
    assert {"paged_decode_attention", "attention", "mlp", "lm_head"} <= set(
        scopes["decode"].values())


def test_the_engine_programs_are_named_as_the_reduction_matches(engine):
    """A rename of the engine's jitted passes fails here, on the CPU."""
    b, c = engine.max_batch, engine.chunk_size
    texts = {
        "decode": engine._decode.lower(engine.params,
                                       np.zeros((b, 1), np.int32),
                                       engine.caches),
        "chunk": engine._chunk.lower(engine.params,
                                     np.zeros((b, c), np.int32),
                                     np.zeros((b,), np.int32), engine.caches)}
    for key, low in texts.items():
        module = low.compile().as_text().split(",", 1)[0].split()[-1]
        assert devtrace.PROGRAMS[key].search(module), (key, module)
        assert enginetrace.program_label(module + "(7)") == key


def test_the_chip_recording_names_its_gaps_and_hot_ops():
    """``internlm2.chat``, ``bench/run.py --trace 1`` with a 3-s window on a
    TPU v5e, and the scope map of its programs."""
    et = enginetrace.read_file(
        os.path.join(DATA, "internlm2.chat.spans.xplane.pb.gz"))
    with open(os.path.join(DATA, "internlm2.chat.spans.scopes.json")) as f:
        scopes = json.load(f)
    assert et.step_self_share() < 0.05
    b = et.breakdown(scopes)
    ops = [n for n, _ in b["device_ops"]]
    assert sum(n.endswith(" [paged_chunk_attention]") for n in ops) >= 3
    assert ops[0] == "paged_decode_attention.6 [paged_decode_attention]"
    assert all(w.startswith(("engine.", "bench.arrivals", "bench.accounting"))
               for w, _ in b["idle_gaps"])
