"""CPU rehearsal of a ``--trace 1`` run whose trace holds the engine's spans
and whose programs carry named scopes: the trace and the scope map are
those recorded on the chip (a 3-s window of ``internlm2.chat`` on a TPU
v5e), so every reader runs on the feeder's own iterations and on what the
program puts into the trace."""
import json

import pytest

import run

from conftest import (WITH_SPANS, WITH_SPANS_SCOPES, WITHOUT_SPANS, traced,
                      workload_names)

NEW = {"engine_host_ms", "chunk_fill", "chunk_attention_ms"}


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
