"""Shared helpers for the benchmark's own tests (CPU, small sizes).

These tests are not part of the repository's tier-1 suite; run them with
``python -m pytest bench/tests`` from the checkout's root.
"""
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# Published widths replaced by a small decoder of the same kind, so a CPU
# can run a cell's whole harness in seconds.
SMALL_CONFIG = {"hidden_size": 64, "num_hidden_layers": 2,
                "num_attention_heads": 4, "num_key_value_heads": 2,
                "head_dim": 16, "intermediate_size": 128, "vocab_size": 512}
SMALL_ENGINE = {"max_batch": 4, "block_tokens": 16, "max_context": 256,
                "num_blocks": 48}
# The small decoder's own logit-gap limit, set from bench/control.py at this
# size on the CPU (seeds 3, 2**31+5, 2**33+1, 1000-1005; requests due in a
# 2-s window): served model 0 to 0.0111, fp8 control 0.0502 to 0.211, the
# faults of bench/faults.py 0.180 and more. The cells' limits are set from
# chip readings at their own size (PERF.md).
SMALL_GAP_LIMIT = 0.03
SMALL_LENGTHS = {"prompt": {"median": 40, "sigma": 0.6, "min": 4, "max": 160},
                 "output": {"median": 24, "sigma": 0.5, "min": 3, "max": 64}}


def benchmark():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def workload_names():
    return [w["name"] for w in benchmark()["workloads"]]


def small_spec(spec: dict) -> dict:
    """A cell's spec with the model, engine and traffic cut to a CPU's size;
    load, window logic, checks and metrics stay as the cell has them."""
    spec = json.loads(json.dumps(spec))
    spec["config"].update(SMALL_CONFIG)
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
