"""The check refuses the control, and refuses a broken timed path.

At a small size on the CPU: the reference computed in fp8 (the precision
below the configuration's bfloat16) must read a wider logit gap than the
served model, and one over the limit; the full-size readings on the chip
that set each cell's limit come from ``bench/control.py`` and are recorded
in PERF.md. Then a whole run, with the look for a chip skipped and the
timed path broken underneath it (``bench/faults.py``), must come out
``correct: false``: once with each token altered where the decode pass
produces it, once with the decode step returning its KV state unchanged."""
import json

import pytest

import control
import faults
import run
from conftest import SMALL_GAP_LIMIT, small_spec, workload_names

SEEDS = (3, 2 ** 31 + 5, 2 ** 33 + 1)


@pytest.fixture
def on_cpu(monkeypatch):
    monkeypatch.setattr(run, "use_cache", lambda jax: None)


@pytest.mark.parametrize("seed", SEEDS)
def test_control_reads_wider_than_the_served_model(on_cpu, seed):
    spec = small_spec(run.cell_spec(workload_names()[0]))
    got = control.readings(spec, seed, 2.0)
    assert got["served_tokens"] > 0
    assert got["program"] <= SMALL_GAP_LIMIT < got["control"], got
    assert got["control"] > 3 * got["program"], got


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
@pytest.mark.parametrize("workload", workload_names())
def test_broken_timed_path_is_not_correct(rehearse, capsys, workload, fault):
    assert rehearse(workload, seconds=2.0,
                    patch=lambda mp: faults.FAULTS[fault](mp.setattr)) == 0
    out = capsys.readouterr()
    line = json.loads(out.out.strip().splitlines()[-1])
    assert line["correct"] is False
    gap = line["checks"]["logit_gap_max"]
    assert gap["value"] > gap["limit"]
