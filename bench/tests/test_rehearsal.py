"""CPU rehearsal of every cell's harness, end to end, at a small size.

A rehearsal: no chip, a small decoder, the kernels' jnp references. It
shows that the harness drives the engine, counts, checks and prints a
well-formed line; its numbers are CPU numbers and are never reported.
"""
import json

import pytest

import devtrace
import run

from conftest import (WITH_SPANS, WITH_SPANS_SCOPES, WITHOUT_SPANS, traced,
                      workload_names)


@pytest.mark.parametrize("workload", workload_names())
def test_cell_runs_end_to_end(rehearse, capsys, workload):
    assert rehearse(workload, seconds=2.0) == 0
    out = capsys.readouterr()
    line = json.loads(out.out.strip().splitlines()[-1])
    assert list(line)[-1] == "checks"
    assert line["correct"] is True, out.err[-2000:]
    assert line["device"]["platform"] == "cpu"
    assert line["attempted"] > 0
    listed = {m["name"] for m in run.cell_spec(workload)["end_to_end"]}
    assert set(line["metrics"]) == listed
    for m in line["metrics"].values():
        assert m["value"] > 0
    assert "compiles in window 0" in out.err
    assert "check logit_gap_max" in out.err.strip().splitlines()[-1]


@pytest.mark.parametrize("workload", workload_names())
def test_traced_run_reads_every_per_layer_metric(rehearse, capsys, workload):
    with open(WITH_SPANS_SCOPES) as f:
        scopes = json.load(f)
    patch = traced(WITH_SPANS, scopes)
    assert rehearse(workload, seconds=2.0, trace=1, patch=patch) == 0
    out = capsys.readouterr()
    line = json.loads(out.out.strip().splitlines()[-1])
    assert list(line)[-1] == "checks"
    assert line["correct"] is True, out.err[-2000:]
    listed = {m["name"] for m in run.cell_spec(workload)["per_layer"]}
    assert set(line["metrics"]) == listed
    assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
    for key in ("device_ops", "idle_gaps"):
        assert 0 < len(line["breakdown"][key]) <= devtrace.TOP


@pytest.mark.parametrize("workload", workload_names())
def test_traced_run_without_the_chunk_program_fails_loudly(rehearse,
                                                           workload):
    """The feeder sees the window's chunk passes, so a trace without the
    chunk program is a renamed program, not a zero."""
    def reduce():
        red = devtrace.reduce_file(WITHOUT_SPANS)
        red.modules = [m for m in red.modules
                       if not devtrace.PROGRAMS["chunk"].search(m[0])]
        return red
    with pytest.raises(LookupError, match="chunk"):
        rehearse(workload, seconds=2.0, trace=1,
                 patch=traced(WITHOUT_SPANS, {"decode": {}, "chunk": {}},
                              reduce))
