"""The reduction on a trace recorded on the chip: ``internlm2.chat``, a
TPU v5e (``TPU v5 lite``), ``bench/run.py --trace 1`` with a short window.
What it must find there: the decode and chunk programs by name, the Pallas
paged decode kernel once per layer of every decode pass, the harness's
spans, and a device busy time inside the window."""
import os

import pytest

import devtrace

TRACE = os.path.join(os.path.dirname(__file__), "data",
                     "internlm2.chat.xplane.pb.gz")
LAYERS = 8


@pytest.fixture(scope="module")
def red():
    return devtrace.reduce_file(TRACE)


def test_one_chip_busy_inside_the_window(red):
    assert red.chips == 1
    assert 0 < red.busy_s <= red.window_s


def test_programs_and_kernel_are_found_by_name(red):
    dec_calls, dec_s = red.program("decode")
    chunk_calls, chunk_s = red.program("chunk")
    assert dec_calls > 0 and chunk_calls > 0
    k_calls, k_s = red.kernel("paged_decode")
    assert k_calls == LAYERS * dec_calls
    assert 0 < k_s < dec_s
    # every program execution lies inside the busy time
    assert dec_s + chunk_s <= red.busy_s * 1.001


def test_one_iteration_span_per_engine_call(red):
    iters = red.host_spans("bench.engine_iter")
    assert len(iters) >= red.program("decode")[0]
    host = [(b - a) - red.busy_between(a, b) for a, b in iters]
    assert all(h >= 0 for h in host)


def test_breakdown_is_bounded_and_named(red):
    b = red.breakdown()
    assert 0 < len(b["device_ops"]) <= devtrace.TOP
    assert 0 < len(b["idle_gaps"]) <= devtrace.TOP
    assert not any(n.startswith("while") for n, _ in b["device_ops"])
    assert any(n.startswith("paged_decode_attention") for n, _ in b["device_ops"])
    idle = sum(t for _, t in b["idle_gaps"])
    assert idle <= red.window_s - red.busy_s + 1e-6
