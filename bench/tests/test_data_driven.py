"""A later change adds a cell, a traffic mix or a per-layer metric by adding
files and BENCHMARK.json entries only: the harness finds them by name."""
import json
import os
import shutil

import run
from conftest import ROOT, small_spec


def _copy_checkout(tmp_path):
    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    return root


def _add_burst_cell(root):
    (root / "bench" / "traffic" / "azure-conv-burst.json").write_text(
        json.dumps({"prompt": {"median": 1020, "sigma": 0.85, "min": 16,
                               "max": 7168},
                    "output": {"median": 210, "sigma": 0.7, "min": 4,
                               "max": 1020},
                    "arrivals": "bursty", "burst_factor": 5, "stratum": 32}))
    cell = json.loads((root / "bench" / "cells" /
                       "internlm2.chat.json").read_text())
    (root / "bench" / "cells" / "internlm2.chat-burst.json").write_text(
        json.dumps(cell))
    (root / "bench" / "metrics" / "queue_wait_ms.py").write_text(
        "def read(ctx):\n    return 1.5\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({
        "name": "internlm2.chat-burst", "config": "internlm2-20b.s8",
        "traffic": "azure-conv-burst", "chips": 1,
        "why": "cell 1 with bursty arrivals"})
    bench["per_layer"].append({
        "name": "queue_wait_ms", "unit": "ms", "better": "lower",
        "source": "program_counter", "layer": "engine", "moves": "itl_p95_s",
        "workloads": ["internlm2.chat-burst"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


def test_new_files_are_found_by_name(tmp_path):
    root = _copy_checkout(tmp_path)
    before = {p: (root / "bench" / p).read_bytes()
              for p in ("traffic/azure-conv.json", "cells/internlm2.chat.json")}
    _add_burst_cell(root)
    spec = run.cell_spec("internlm2.chat-burst", root=str(root))
    assert spec["mix"]["arrivals"] == "bursty"
    assert spec["config"]["name"] == "internlm2-20b.s8"
    assert [m["name"] for m in spec["per_layer"]][-1] == "queue_wait_ms"
    assert run.metric_reader("queue_wait_ms", root=str(root))(None) == 1.5
    # the cells that were there do not see the new metric
    old = run.cell_spec("internlm2.chat", root=str(root))
    assert "queue_wait_ms" not in [m["name"] for m in old["per_layer"]]
    for p, data in before.items():
        assert (root / "bench" / p).read_bytes() == data


def test_new_cell_rehearses(tmp_path, monkeypatch, capsys):
    root = _copy_checkout(tmp_path)
    _add_burst_cell(root)
    real = run.cell_spec
    monkeypatch.setattr(run, "cell_spec", lambda name, root_=None:
                        small_spec(real(name, str(root))))
    monkeypatch.setattr(run, "use_cache", lambda jax: None)
    monkeypatch.setattr(run, "require_chip", lambda jax, chips: {
        "platform": "cpu", "kind": "rehearsal", "count": chips})
    args = run.parse(["--workload", "internlm2.chat-burst", "--seed", "9",
                      "--seconds", "2", "--trace", "0"])
    assert run.run(args) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True
    assert set(line["metrics"]) == {"itl_p95_s", "setup_s"}
