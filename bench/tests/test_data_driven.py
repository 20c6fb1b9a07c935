"""A later change adds a cell, a traffic mix, a per-layer metric or an
architecture by adding files and BENCHMARK.json entries only: the harness
finds them by name."""
import hashlib
import json
import os

import run
from conftest import copy_checkout, small_spec

# an architecture added as a new file: the dense decoder under another name
TOY_ARCH = '''"""The dense GQA decoder under another name."""
import importlib.util
import os
import sys

_spec = importlib.util.spec_from_file_location(
    "toy_dense_gqa",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "dense_gqa.py"))
_base = sys.modules["toy_dense_gqa"] = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_base)
model_config = _base.model_config
Reference = _base.Reference
Shape = _base.Shape
PALLAS_OPS = _base.PALLAS_OPS
SMALL = _base.SMALL
'''


def _hashes(root):
    out = {}
    for d, dirs, files in os.walk(root / "bench"):
        dirs[:] = [x for x in dirs if x != "__pycache__"]
        for f in files:
            path = os.path.join(d, f)
            with open(path, "rb") as fh:
                out[path] = hashlib.sha256(fh.read()).hexdigest()
    return out


def _add_entries(root, config=None, workload=None, per_layer=None):
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for key, entry in (("configs", config), ("workloads", workload),
                       ("per_layer", per_layer)):
        if entry is not None:
            bench[key].append(entry)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


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
    _add_entries(root, workload={
        "name": "internlm2.chat-burst", "config": "internlm2-20b.s8",
        "traffic": "azure-conv-burst", "chips": 1,
        "why": "cell 1 with bursty arrivals"}, per_layer={
        "name": "queue_wait_ms", "unit": "ms", "better": "lower",
        "source": "program_counter", "layer": "engine", "moves": "itl_p95_s",
        "workloads": ["internlm2.chat-burst"]})


def _add_toy_arch_cell(root):
    (root / "bench" / "archs" / "toy.py").write_text(TOY_ARCH)
    config = json.loads((root / "bench" / "configs" /
                         "internlm2-20b.s8.json").read_text())
    config.update(name="toy-20b.s8", arch="toy")
    (root / "bench" / "configs" / "toy-20b.s8.json").write_text(
        json.dumps(config))
    (root / "bench" / "cells" / "toy.chat.json").write_text(
        (root / "bench" / "cells" / "internlm2.chat.json").read_text())
    _add_entries(root, config={
        "name": "toy-20b.s8", "source": "https://huggingface.co/org/toy",
        "file": "bench/configs/toy-20b.s8.json",
        "reduced": ["num_hidden_layers"], "why": "a second architecture"},
        workload={"name": "toy.chat", "config": "toy-20b.s8",
                  "traffic": "azure-conv", "chips": 1,
                  "why": "the toy architecture under chat traffic"})


def _rehearse_in(root, workload, monkeypatch, capsys):
    """``run.run`` of ``workload`` in the checkout at ``root``, cut to the
    CPU's size, with the look for a chip skipped."""
    real = run.cell_spec
    monkeypatch.setattr(run, "cell_spec", lambda name, root_=None:
                        small_spec(real(name, str(root))))
    monkeypatch.setattr(run, "use_cache", lambda jax: None)
    monkeypatch.setattr(run, "require_chip", lambda jax, chips: {
        "platform": "cpu", "kind": "rehearsal", "count": chips})
    args = run.parse(["--workload", workload, "--seed", "9",
                      "--seconds", "2", "--trace", "0"])
    assert run.run(args) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_new_files_are_found_by_name(tmp_path):
    root = copy_checkout(tmp_path)
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
    root = copy_checkout(tmp_path)
    _add_burst_cell(root)
    line = _rehearse_in(root, "internlm2.chat-burst", monkeypatch, capsys)
    assert line["correct"] is True
    assert set(line["metrics"]) == {"itl_p95_s", "setup_s"}


def test_new_architecture_is_new_files_only(tmp_path, monkeypatch, capsys):
    """An architecture module, a configuration that names it, a cell and
    the BENCHMARK.json entries: the cell rehearses end to end through the
    new module, and no file that was there changed."""
    root = copy_checkout(tmp_path)
    before = _hashes(root)
    _add_toy_arch_cell(root)
    spec = run.cell_spec("toy.chat", root=str(root))
    assert spec["arch"].__file__ == str(root / "bench" / "archs" / "toy.py")
    line = _rehearse_in(root, "toy.chat", monkeypatch, capsys)
    assert line["correct"] is True
    assert set(line["metrics"]) == {"itl_p95_s", "setup_s"}
    after = _hashes(root)
    assert {p: after[p] for p in before} == before
    assert set(after) - set(before) == {
        str(root / "bench" / p) for p in (
            "archs/toy.py", "configs/toy-20b.s8.json", "cells/toy.chat.json")}
