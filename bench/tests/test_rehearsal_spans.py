"""CPU rehearsal of a ``--trace 1`` run whose trace holds the engine's spans
and whose programs carry named scopes: the trace and the scope map are
those recorded on the chip (a 3-s window of ``internlm2.chat`` on a TPU
v5e), so every reader runs on the feeder's own iterations and on what the
program puts into the trace."""
import json
import os

import jax
import pytest

import devtrace
import enginetrace
import roofline
import run

from conftest import workload_names

DATA = os.path.join(os.path.dirname(__file__), "data")
RECORDED_ON = "TPU v5 lite"
WITH_SPANS = os.path.join(DATA, "internlm2.chat.spans.xplane.pb.gz")
WITH_SPANS_SCOPES = os.path.join(DATA, "internlm2.chat.spans.scopes.json")
# recorded before the engine opened spans or the programs named scopes
WITHOUT_SPANS = os.path.join(DATA, "internlm2.chat.xplane.pb.gz")
NEW = {"engine_host_ms", "chunk_fill", "chunk_attention_ms"}


def traced(path, scopes):
    """The profiler left off; the reductions read ``path``, the programs'
    scope map is ``scopes``, the peaks those of the recording chip."""
    def patch(monkeypatch):
        monkeypatch.setattr(run, "peaks",
                            lambda kind: roofline.peaks(RECORDED_ON))
        monkeypatch.setattr(jax.profiler, "start_trace", lambda d: None)
        monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
        monkeypatch.setattr(devtrace, "reduce_dir",
                            lambda d: devtrace.reduce_file(path))
        monkeypatch.setattr(enginetrace, "for_run",
                            lambda ctx: enginetrace.read_file(path))
        monkeypatch.setattr(enginetrace, "scopes_for_run",
                            lambda ctx: scopes)
    return patch


def line_of(rehearse, capsys, workload, patch):
    assert rehearse(workload, seconds=2.0, trace=1, patch=patch) == 0
    out = capsys.readouterr()
    line = json.loads(out.out.strip().splitlines()[-1])
    assert line["correct"] is True, out.err[-2000:]
    return line


@pytest.mark.parametrize("workload", workload_names())
def test_traced_run_reads_every_per_layer_metric(rehearse, capsys,
                                                 workload):
    with open(WITH_SPANS_SCOPES) as f:
        scopes = json.load(f)
    line = line_of(rehearse, capsys, workload, traced(WITH_SPANS, scopes))
    listed = {m["name"] for m in run.cell_spec(workload)["per_layer"]}
    assert set(line["metrics"]) == listed
    for name in NEW:
        assert line["metrics"][name]["value"] > 0


@pytest.mark.parametrize("workload", workload_names())
def test_traced_run_of_a_program_without_spans(rehearse, capsys, workload):
    """The new metrics are left out of the line, and nothing raises."""
    line = line_of(rehearse, capsys, workload,
                   traced(WITHOUT_SPANS, {"decode": {}, "chunk": {}}))
    listed = {m["name"] for m in run.cell_spec(workload)["per_layer"]}
    assert set(line["metrics"]) == listed - NEW
