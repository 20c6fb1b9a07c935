#!/usr/bin/env python3
"""Find an open-loop cell's knee: serve it at several fixed rates in one
process and print, per rate, the TTFT tail and whether a backlog grew.

    python3 bench/sweep.py --workload internlm2.chat --seed 3 \
        --rates 0.2,0.3,0.4 --seconds 40

Not part of a benchmark run: a cell's rate is fixed in its file
(``bench/cells/<workload>.json``), set once from this sweep on the chip at
about four fifths of the highest rate the engine sustains. The engine, its
weights and its programs are built once; between rates it serves until
empty.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys

import numpy as np

import run


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--seconds", type=float, default=40.0)
    args = p.parse_args(argv)
    spec = run.cell_spec(args.workload)
    import jax
    run.use_cache(jax)
    run.require_chip(jax, spec["workload"]["chips"])
    sys.path.insert(0, run.os.path.join(run.ROOT, "src"))
    eng = run.build_engine(spec, args.seed)
    run.warm_up(eng, spec, args.seed)
    for rate in (float(r) for r in args.rates.split(",")):
        spec["cell"]["load"]["rate_per_s"] = rate
        feed, _, win = run.serve(eng, spec, args.seed,
                                 lambda name: contextlib.nullcontext(),
                                 args.seconds)
        queued_start = len(eng.waiting)
        feed.serve_until(win.end)
        queued_end = len(eng.waiting)
        feed.drain(win, spec["cell"]["drain_limit_s"])
        e2e, counts = run.e2e_metrics(feed, win)
        ttft = sorted(t.req.first_token_time - t.due for t in feed.due_in(win)
                      if t.req.first_token_time)
        print(json.dumps({
            "rate_per_s": rate, **e2e,
            "ttft_p50_s": float(np.percentile(ttft, 50)) if ttft else None,
            "ttft_p95_s": float(np.percentile(ttft, 95)) if ttft else None,
            "queued_at_start": queued_start, "queued_at_end": queued_end,
            **counts}), flush=True)
        while eng.waiting or any(a is not None for a in eng.active):
            eng.run(max_steps=eng.steps + 1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
