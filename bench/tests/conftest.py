"""Shared helpers for the benchmark's own tests (CPU, small sizes).

These tests are not part of the repository's tier-1 suite; run them with
``python -m pytest bench/tests`` from the checkout's root.
"""
import json
import os
import shutil
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

SMALL_ENGINE = {"max_batch": 4, "block_tokens": 16, "max_context": 256,
                "num_blocks": 48}
# The small decoder's (dense_gqa.SMALL) own logit-gap limit, set from
# bench/control.py at this size on the CPU (seeds 3, 2**31+5, 2**33+1,
# 1000-1005; requests due in a 2-s window): served model 0 to 0.0111, fp8
# control 0.0502 to 0.211, the faults of bench/faults.py 0.180 and more.
# The cells' limits are set from chip readings at their own size (PERF.md).
SMALL_GAP_LIMIT = 0.03
SMALL_LENGTHS = {"prompt": {"median": 40, "sigma": 0.6, "min": 4, "max": 160},
                 "output": {"median": 24, "sigma": 0.5, "min": 3, "max": 64}}


# traces recorded on the chip (bench/tests/data), and the chip's kind
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RECORDED_ON = "TPU v5 lite"
# internlm2.chat, a 3-s window, with the engine's spans and the programs'
# scope map
WITH_SPANS = os.path.join(DATA, "internlm2.chat.spans.xplane.pb.gz")
WITH_SPANS_SCOPES = os.path.join(DATA, "internlm2.chat.spans.scopes.json")
# recorded before the engine opened spans or the programs named scopes
WITHOUT_SPANS = os.path.join(DATA, "internlm2.chat.xplane.pb.gz")


def benchmark():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def workload_names():
    return [w["name"] for w in benchmark()["workloads"]]


def copy_checkout(tmp_path):
    """A checkout of BENCHMARK.json and the benchmark's files, without its
    tests, under ``tmp_path``."""
    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    return root


def arch(name: str):
    """The architecture module ``bench/archs/<name>.py``."""
    import run
    return run.arch_module({"name": name, "arch": name})


def small_spec(spec: dict) -> dict:
    """A cell's spec with the model (by its architecture's ``SMALL``),
    engine and traffic cut to a CPU's size; load, window logic, checks and
    metrics stay as the cell has them."""
    module = spec["arch"]
    spec = json.loads(json.dumps({k: v for k, v in spec.items()
                                  if k != "arch"}))
    spec["arch"] = module
    spec["config"].update(module.SMALL)
    cell = spec["cell"]
    chunk = min(cell["engine"]["chunk_size"], 32)
    cell["engine"].update(SMALL_ENGINE, chunk_size=chunk,
                          max_batch=min(cell["engine"]["max_batch"], 4))
    spec["mix"].update(SMALL_LENGTHS)
    spec["mix"]["stratum"] = 8
    cell["load"]["rate_per_s"] = 6.0
    cell["drain_limit_s"] = 20
    cell["lead_in_s"] = 0.5
    cell["check"]["sample_tokens"] = 150
    cell["check"]["logit_gap_limit"] = SMALL_GAP_LIMIT
    return spec


@pytest.fixture
def rehearse(monkeypatch):
    """Run ``run.run`` for a cell at the small size on the CPU: the look for
    a chip is skipped, everything else is the harness as it stands."""
    import run

    def go(workload, seconds=2.0, seed=2 ** 31 + 11, trace=0, patch=None):
        real = run.cell_spec
        monkeypatch.setattr(run, "cell_spec",
                            lambda name, root=ROOT: small_spec(real(name, root)))
        monkeypatch.setattr(run, "use_cache", lambda jax: None)
        monkeypatch.setattr(run, "require_chip", lambda jax, chips: {
            "platform": "cpu", "kind": "rehearsal", "count": chips})
        if patch is not None:
            patch(monkeypatch)
        args = run.parse(["--workload", workload, "--seed", str(seed),
                          "--seconds", str(seconds), "--trace", str(trace)])
        return run.run(args)
    return go


def traced(path, scopes, reduce=None):
    """A patch for a ``--trace 1`` rehearsal on the CPU: the profiler left
    off; the reduction is ``reduce()``, by default that of the trace
    recorded on the chip at ``path``; the engine's spans are read from
    ``path`` and the programs' scope map is ``scopes``; the peaks are those
    of the recording chip. Every per-layer reader then runs on the feeder's
    own iterations and on what the program put into the trace."""
    import jax

    import devtrace
    import enginetrace
    import roofline
    import run
    if reduce is None:
        def reduce():
            return devtrace.reduce_file(path)

    def patch(monkeypatch):
        monkeypatch.setattr(run, "peaks",
                            lambda kind: roofline.peaks(RECORDED_ON))
        monkeypatch.setattr(jax.profiler, "start_trace", lambda d: None)
        monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
        monkeypatch.setattr(devtrace, "reduce_dir", lambda d: reduce())
        monkeypatch.setattr(enginetrace, "for_run",
                            lambda ctx: enginetrace.read_file(path))
        monkeypatch.setattr(enginetrace, "scopes_for_run",
                            lambda ctx: scopes)
    return patch
