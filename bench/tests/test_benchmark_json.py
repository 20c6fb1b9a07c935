"""BENCHMARK.json keeps to the benchmark's contract, and every name in it
leads to a file the harness can read."""
import os
import re

from conftest import BENCH, ROOT, benchmark

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
WIDTH = re.compile(r"(^hidden_size$|intermediate|latent|state_size|proj|"
                   r"_dim$|_rank$|expan|experts_per_tok)")
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}


def _line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level_and_entries():
    b = benchmark()
    assert set(b) == KEYS
    assert b["command"][:1] == ["python3"] and len(b["command"]) <= 32
    for word in b["command"]:
        assert _line(word) and not word.startswith("/") and ".." not in word
    for p in b["paths"]:
        assert re.match(r"^[A-Za-z0-9_./-]{1,200}$", p) and os.path.isdir(
            os.path.join(ROOT, p))
    assert 1 <= b["run_seconds"] <= 51
    # a full check of 24 cells fits in 43200 s
    assert (2 + 14 * 24) * (b["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["source"]) and _line(c["why"])
        assert c["file"].startswith("bench/") and os.path.isfile(
            os.path.join(ROOT, c["file"]))
        assert len(c["reduced"]) <= 16
        for k in c["reduced"]:
            assert NAME.match(k) and not WIDTH.search(k), k
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and _line(w["why"])
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert _line(m["layer"])
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in b[k]]
    assert len(names) == len(set(names))


def test_every_name_leads_to_a_file():
    b = benchmark()
    configs = {c["name"] for c in b["configs"]}
    pairs = set()
    for w in b["workloads"]:
        assert w["config"] in configs
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        assert os.path.isfile(os.path.join(BENCH, "traffic",
                                           w["traffic"] + ".json"))
        assert os.path.isfile(os.path.join(BENCH, "cells",
                                           w["name"] + ".json"))
    assert configs == {w["config"] for w in b["workloads"]}
    for m in b["per_layer"]:
        assert os.path.isfile(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py"))


def test_every_cell_reports_what_its_metrics_move():
    b = benchmark()
    cells = [w["name"] for w in b["workloads"]]

    def where(m):
        return m.get("workloads", cells)
    e2e = {m["name"]: set(where(m)) for m in b["end_to_end"]}
    assert e2e["setup_s"] == set(cells)
    for c in cells:
        assert len([n for n, ws in e2e.items() if c in ws]) >= 2
        assert any(c in where(m) for m in b["per_layer"])
    for m in b["per_layer"]:
        assert m["moves"] in e2e
        assert set(where(m)) <= e2e[m["moves"]], m["name"]


def test_layers_are_named_in_perf_md():
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    for m in benchmark()["per_layer"]:
        assert f"| {m['layer']} |" in perf, m["layer"]
