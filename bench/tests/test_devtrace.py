"""The trace reduction, on a trace written out by hand."""
import types

import pytest
from jax.profiler import ProfileData

import devtrace

# one chip; times in ns. Ops: a decode module 1000..5000 holding two ops
# (1000..3000, 2000..4000: busy 1000..4000), a chunk module 6000..9000 with
# one op; host: window 0..10000, two engine iterations 500..5500 and
# 5500..9500, an arrival span 9500..9900.
TRACE = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 10 offset_ps: 1000000 duration_ps: 4000000 }
    events { metadata_id: 11 offset_ps: 6000000 duration_ps: 3000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 2000000 duration_ps: 2000000 }
    events { metadata_id: 1 offset_ps: 6000000 duration_ps: 3000000 } }
  event_metadata { key: 10 value { id: 10 name: "jit__decode(3)" } }
  event_metadata { key: 11 value { id: 11 name: "jit__chunk" } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.7 = bf16[8] fusion(%a)" } }
  event_metadata { key: 2 value { id: 2 name: "%paged_decode_attention.3 = bf16[8] custom-call(%b)" } }
}
planes { id: 2 name: "/host:CPU"
  lines { id: 5 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 500000 duration_ps: 5000000 }
    events { metadata_id: 2 offset_ps: 5500000 duration_ps: 4000000 }
    events { metadata_id: 3 offset_ps: 9500000 duration_ps: 400000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.engine_iter" } }
  event_metadata { key: 3 value { id: 3 name: "bench.arrivals" } }
}
"""


def reduction(text=TRACE):
    pd = ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(text))
    return devtrace.reduce_profile(pd)


def test_busy_is_the_union_of_ops():
    red = reduction()
    assert red.window_s == pytest.approx(1e-5)
    assert red.busy_s == pytest.approx(6e-6)          # 1000..4000, 6000..9000
    assert red.busy_between(500, 5500) == 3000


def test_programs_and_kernels_by_name():
    red = reduction()
    assert red.program("decode") == (1, pytest.approx(4e-6))
    assert red.program("chunk") == (1, pytest.approx(3e-6))
    assert red.kernel("paged_decode") == (1, pytest.approx(2e-6))


def test_breakdown_names_gaps_by_host_span():
    b = reduction().breakdown()
    assert b["device_ops"] == [["fusion.7", pytest.approx(5e-6)],
                               ["paged_decode_attention.3", pytest.approx(2e-6)]]
    # idle 4000..6000 (midpoint 5000, first iteration), 0..1000 (midpoint
    # 500, first iteration), 9000..10000 (midpoint 9500, arrivals)
    got = [(w, round(t * 1e9)) for w, t in b["idle_gaps"]]
    assert got[0] == ("bench.engine_iter", 2000)
    assert sorted(got[1:]) == [("bench.arrivals", 1000),
                               ("bench.engine_iter", 1000)]


def test_iteration_host_time():
    import importlib.util, os
    path = os.path.join(os.path.dirname(devtrace.__file__), "metrics",
                        "iter_host_ms.py")
    spec = importlib.util.spec_from_file_location("m", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    ctx = types.SimpleNamespace(reduction=reduction())
    # spans of 5000 and 4000 ns hold 3000 and 3000 ns of device time
    assert mod.read(ctx) == pytest.approx((2000 + 1000) / 2 / 1e6)


def test_a_renamed_program_fails_loudly():
    red = reduction(TRACE.replace("jit__decode(3)", "jit_decode_renamed"))
    it = types.SimpleNamespace(end=1.0, decode_lengths=[5], prompt_tokens=0)
    win = types.SimpleNamespace(inside=lambda t: True)
    ctx = devtrace.Context(reduction=red, feeder=types.SimpleNamespace(
        iterations=[it]), window=win, shape=None, peak={}, chips=1, spec={})
    with pytest.raises(LookupError):
        ctx.program("decode")
