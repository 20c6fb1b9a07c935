"""Traffic generator: every mix is a data file that this one generator reads.

A mix lives in ``bench/traffic/<name>.json``::

    {"prompt": {"median": 1020, "sigma": 0.85, "min": 16, "max": 7168},
     "output": {"median": 210, "sigma": 0.7, "min": 4, "max": 1020},
     "arrivals": "poisson",            # or "bursty", with "burst_factor"
     "stratum": 32}

Lengths are lognormal (median, sigma of the log), clipped to [min, max];
arrivals are Poisson or the hot/cold bursty mixture. The shapes are those
of ``repro.core.workload`` (``TraceSpec``, ``arrival_times``), copied here so
that the yardstick does not move with the program.

Requests come in blocks of ``stratum`` (a cell may set its own block
size). Every block holds the same ``stratum`` quantiles of each
distribution, and the seed only permutes them (independently for prompts,
outputs and gaps) and draws the token ids. The gaps of a block are scaled
to sum to exactly ``stratum`` at unit rate, and a request's gap runs to
the next one, so a block's first request falls due at its start and the
block spans ``stratum / rate`` seconds for every seed. A cell whose lead-in
and window are whole blocks therefore offers every seed the same work in
its window, in a different order, and run-to-run spread is not a spread of
work. The
arrival rate is not part of the mix: it is set per cell
(``bench/cells/<workload>.json``), because the knee depends on the model.
"""
from __future__ import annotations

import json
import os
from dataclasses import dataclass
from statistics import NormalDist
from typing import Iterator

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclass(frozen=True)
class Request:
    index: int
    prompt: np.ndarray       # (p,) int32 token ids
    max_new: int
    gap_s: float             # gap from this request to the next, at unit rate


def load_mix(name: str, root: str = HERE) -> dict:
    path = os.path.join(root, "traffic", f"{name}.json")
    with open(path) as f:
        mix = json.load(f)
    for key in ("prompt", "output", "arrivals", "stratum"):
        if key not in mix:
            raise ValueError(f"traffic file {path} lacks {key!r}")
    return mix


def length_quantiles(spec: dict, n: int) -> np.ndarray:
    """The n mid-quantiles of a clipped lognormal, as whole tokens."""
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.round(spec["median"] * np.exp(spec["sigma"] * z))
    return np.clip(x, spec["min"], spec["max"]).astype(np.int64)


def _exp_quantiles(n: int) -> np.ndarray:
    """The n mid-quantiles of Exp(1), scaled to mean exactly 1."""
    q = -np.log1p(-(np.arange(n) + 0.5) / n)
    return q / q.mean()


def gap_quantiles(mix: dict, n: int) -> np.ndarray:
    """The n inter-arrival gaps of one block at rate 1."""
    if mix["arrivals"] == "poisson":
        return _exp_quantiles(n)
    if mix["arrivals"] == "bursty":
        # half of the gaps hot (rate * bf), half cold (rate / bf); the mean
        # gap is (1/bf + bf) / 2, as in repro.core.workload.arrival_times
        bf = float(mix.get("burst_factor", 5.0))
        return np.concatenate([_exp_quantiles(n - n // 2) / bf,
                               _exp_quantiles(n // 2) * bf])
    raise ValueError(f"unknown arrival process {mix['arrivals']!r}")


def requests(mix: dict, seed: int, vocab: int,
             block: int = 0) -> Iterator[Request]:
    """Endless stream of requests for one seed, block by block (``block``
    requests each, else the mix's ``stratum``)."""
    n = int(block or mix["stratum"])
    prompts = length_quantiles(mix["prompt"], n)
    outputs = length_quantiles(mix["output"], n)
    gaps = gap_quantiles(mix, n)
    rng = np.random.default_rng(int(seed))
    index = 0
    while True:
        pp, oo, gg = (rng.permutation(prompts), rng.permutation(outputs),
                      rng.permutation(gaps))
        for p, o, g in zip(pp, oo, gg):
            toks = rng.integers(0, vocab, int(p), dtype=np.int64)
            yield Request(index, toks.astype(np.int32), int(o), float(g))
            index += 1

