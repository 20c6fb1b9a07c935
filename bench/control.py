#!/usr/bin/env python3
"""Readings that set a cell's ``logit_gap_limit``: the served model's widest
logit gap against the plain reference, the control's, and a planted fault's.

    python3 bench/control.py --workload internlm2.chat --seeds 5,6,7 --seconds 20
    python3 bench/control.py --workload internlm2.chat --seeds 5,6,7 \
        --seconds 20 --fault token-altered

For each seed, in one process: serve the cell as a benchmark run does (its
weights, engine, load and lead-in, then a short window and the drain),
sample the requests due in the window as a run does, free the engine, and
read, over the same prompts and served tokens:

* ``program``: the largest gap by which a served token's reference logit
  lies below the reference's best (what a run compares with the limit);
* ``control`` (without ``--fault``): the same, for the token that the
  reference computed in fp8 (e4m3, the precision below the configuration's
  bfloat16) puts first.

With ``--fault`` (a name in ``bench/faults.py``) the fault is planted in the
program before its engine is built, and ``program`` is the broken program's
reading.

The limit lies between the readings: above the program's over a dozen
seeds or more, below the control's and the faults'. Benchmark runs do not
run this; ``bench/tests/test_control.py`` runs it at a small size.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import sys

import faults
import reference
import run


def readings(spec: dict, seed: int, seconds: float, fault: str = "") -> dict:
    planted = faults.Planted()
    if fault:
        faults.FAULTS[fault](planted.setattr)
    try:
        eng = run.build_engine(spec, seed)
        run.warm_up(eng, spec, seed)
        feed, _, win = run.serve(eng, spec, seed,
                                 lambda name: contextlib.nullcontext(), seconds)
        feed.serve_until(win.end)
        feed.drain(win, spec["cell"]["drain_limit_s"])
        pick = run.sampled(spec, seed, feed, win)
        eng.params = eng.caches = None
        del eng, feed
        gc.collect()
    finally:
        planted.undo()
    ref = spec["arch"].Reference(spec["config"], seed)
    prog, ctrl = 0.0, 0.0
    for r in pick:
        prog = max(prog, float(reference.gaps(ref, r.prompt, r.tokens).max()))
        if not fault:
            ctrl = max(ctrl, float(reference.gaps(ref, r.prompt, r.tokens,
                                                  control=True).max()))
    del ref
    gc.collect()
    out = {"seed": seed, "fault": fault or None, "program": prog,
           "served_tokens": sum(len(r.tokens) for r in pick),
           "requests": len(pick)}
    if not fault:
        out["control"] = ctrl
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, default=20.0)
    p.add_argument("--fault", choices=sorted(faults.FAULTS), default="")
    args = p.parse_args(argv)
    spec = run.cell_spec(args.workload)
    import jax
    run.use_cache(jax)
    device = run.require_chip(jax, spec["workload"]["chips"])
    sys.path.insert(0, run.os.path.join(run.ROOT, "src"))
    for seed in (int(s) for s in args.seeds.split(",")):
        out = readings(spec, seed, args.seconds, args.fault)
        out.update(workload=args.workload, device=device["kind"],
                   limit=spec["cell"]["check"]["logit_gap_limit"])
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
