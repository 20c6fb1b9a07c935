#!/usr/bin/env python3
"""One run of one benchmark cell on the chip.

    python3 bench/run.py --workload internlm2.chat --seed 7 --seconds 51 --trace 0

Reads ``BENCHMARK.json`` at the checkout's root and finds everything else
by name: the configuration's file (``configs`` entry), the module of the
architecture that file names (``bench/archs/<arch>.py``: the program's model
config, the plain reference, the counts and the ops that must take Pallas),
the traffic mix (``bench/traffic/<traffic>.json``), the cell's engine
settings, load and check limits (``bench/cells/<workload>.json``) and, with
``--trace 1``, one reader per per-layer metric (``bench/metrics/<name>.py``).

A run: check the device (a TPU with enough chips, else exit 2 with no
result); make the weights from the seed in one jitted call; build the paged
``Engine``; warm up the decode and chunk programs; serve the lead-in, then
the measured window of ``--seconds``, then drain (serve on, arrivals
continuing, until every request due in the window has finished); read the
device's peak memory; free the engine; compare a sample of the requests due
in the window with the architecture's plain reference; print the checks
on standard error and one JSON line on standard output. ``setup_s`` runs
from process start to the window's start (weights, compile or cache load,
warm-up, lead-in).
"""
from __future__ import annotations

import time

PROCESS_START = time.monotonic()

import argparse
import contextlib
import gc
import importlib.util
import json
import os
import shutil
import sys

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE = os.path.join(ROOT, ".bench_cache")
sys.path.insert(0, BENCH)

import reference                              # noqa: E402
import traffic                                # noqa: E402
from roofline import peaks                    # noqa: E402
from serve import Feeder, Window, percentile  # noqa: E402

WARMUP_NEW_TOKENS = 3


class NoChip(SystemExit):
    pass


# ---------------------------------------------------------------------------
# what the cell is, from BENCHMARK.json and the files it names
# ---------------------------------------------------------------------------

def load_json(path):
    with open(path) as f:
        return json.load(f)


def cell_spec(workload: str, root: str = ROOT) -> dict:
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(os.path.join(root, configs[w["config"]]["file"]))
    return {
        "workload": w,
        "config": config,
        "arch": arch_module(config, root),
        "mix": traffic.load_mix(w["traffic"], os.path.join(root, "bench")),
        "cell": load_json(os.path.join(root, "bench", "cells",
                                       f"{workload}.json")),
        "end_to_end": [m for m in bench["end_to_end"]
                       if workload in m.get("workloads", [workload])],
        "per_layer": [m for m in bench["per_layer"]
                      if workload in m.get("workloads", [workload])],
    }


def _load(name: str, path: str):
    """The module at ``path``, executed afresh under ``name`` (registered,
    as a dataclass in it needs its module while it is made)."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, root: str = ROOT):
    path = os.path.join(root, "bench", "metrics", f"{name}.py")
    return _load(f"bench_metric_{name}", path).read


def arch_module(config: dict, root: str = ROOT):
    """The module of the architecture the configuration names under
    ``arch``: ``bench/archs/<arch>.py``. There is no default."""
    arch = config.get("arch")
    if not (isinstance(arch, str) and arch.isidentifier()):
        raise SystemExit(f"configuration {config.get('name')!r} names no "
                         f"\"arch\" (it has {arch!r}); it has to name its "
                         f"architecture's module, bench/archs/<arch>.py")
    path = os.path.join(root, "bench", "archs", f"{arch}.py")
    if not os.path.isfile(path):
        raise SystemExit(f"configuration {config.get('name')!r} names arch "
                         f"{arch!r}, but bench/archs/{arch}.py does not exist")
    return _load(f"bench_arch_{arch}", path)


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------

def use_cache(jax):
    """JAX's persistent compilation cache, at a fixed path in the checkout,
    for every program (the smallest ones too, so set-up is steady)."""
    jax.config.update("jax_compilation_cache_dir", os.path.join(CACHE, "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def require_chip(jax, chips: int) -> dict:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no accelerator: JAX found {devs[0].platform!r} devices")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    d = devs[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": chips}


def memory_peak(jax, chips: int) -> int:
    peak = 0
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


class CompileCounter:
    """Programs compiled or loaded from the cache while ``counting``."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self, jax):
        self.counting = False
        self.count = 0
        self.names = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.counting and event == self.EVENT:
            self.count += 1
            self.names.append(kw.get("fun_name", "?"))


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def log(msg: str):
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def build_engine(spec: dict, seed: int):
    import jax
    from repro.engine.core import Engine, EngineConfig
    from repro.models import transformer as tf
    cfg = spec["arch"].model_config(spec["config"])
    e = spec["cell"]["engine"]
    params = tf.init_params(cfg, reference.seed_key(seed), False)
    jax.block_until_ready(params)
    eng = Engine(cfg, params=params, max_batch=e["max_batch"],
                 max_len=e["max_context"], block_tokens=e["block_tokens"],
                 num_blocks=e["num_blocks"], preemption=e["preemption"],
                 config=EngineConfig(chunk_size=e["chunk_size"],
                                     token_budget=e.get("token_budget", 0),
                                     max_context=e["max_context"]))
    return eng


def warm_up(eng, spec: dict, seed: int):
    """Every program the window uses: the (max_batch, chunk) chunk pass and
    the (max_batch, 1) decode pass, with the table pushes around them."""
    e = spec["cell"]["engine"]
    rng = np.random.default_rng([int(seed), 1])
    vocab = spec["config"]["vocab_size"]
    for _ in range(2):
        eng.submit(rng.integers(0, vocab, e["chunk_size"] + 5).astype(np.int32),
                   WARMUP_NEW_TOKENS)
    eng.run()


def serve(eng, spec: dict, seed: int, annotate, seconds: float):
    """A feeder for the cell's load, and the measured window of ``seconds``,
    after serving the lead-in.

    The window opens ``lead_in_s`` after the first arrival, less half the
    block's shortest gap, so that no arrival falls on either of its edges:
    a window of whole blocks holds exactly those blocks' requests."""
    cell, mix = spec["cell"], spec["mix"]
    load = cell["load"]
    shape = spec["arch"].Shape.from_config(spec["config"],
                                           cell["engine"]["block_tokens"])
    block = load.get("block", 0)
    arrivals = traffic.requests(mix, seed, spec["config"]["vocab_size"], block)
    feed = Feeder(eng, arrivals, shape, load["rate_per_s"], annotate=annotate)
    edge = (0.5 * traffic.gap_quantiles(mix, block or mix["stratum"]).min()
            / load["rate_per_s"])
    origin = time.monotonic()
    feed.start(origin)
    start = origin + cell["lead_in_s"] - edge
    feed.serve_until(start)
    return feed, shape, Window(start, start + seconds)


def e2e_metrics(feed: Feeder, win: Window):
    """The end-to-end metrics of the window, and what they were read from.

    ITL over every gap between streamed tokens that ends in the window. A
    request due in the window without a first token at the drain limit has
    failed."""
    attempted = feed.due_in(win)
    failed = sum(t.req.first_token_time is None for t in attempted)
    gaps = []
    for t in feed.all:
        tt = t.req.token_times
        gaps.extend(b - a for a, b in zip(tt, tt[1:]) if win.inside(b))
    its = [it for it in feed.iterations if win.inside(it.end)]
    out = {"itl_p95_s": percentile(gaps, 95) if gaps else None}
    counts = {"attempted": len(attempted), "failed": failed,
              "gap_samples": len(gaps), "iterations": len(its),
              "preemptions": sum(it.preemptions for it in its)}
    return out, counts


def sampled(spec: dict, seed: int, feed: Feeder, win: Window) -> list:
    """The requests the output check compares: drawn from the seed among
    those due in the window that finished (the longest always in)."""
    chk = spec["cell"]["check"]
    finished = [t.req for t in feed.due_in(win) if t.req.state == "done"]
    return reference.sample_requests(
        finished, np.random.default_rng([int(seed), 2]),
        chk["sample_tokens"], chk["sample_max_requests"])


def check_outputs(spec: dict, seed: int, pick: list) -> dict:
    """Largest logit gap of the sampled requests' served tokens against the
    plain reference."""
    served = sum(len(r.tokens) for r in pick)
    if not pick:
        return {"logit_gap_max": None, "served_tokens": 0}
    ref = spec["arch"].Reference(spec["config"], seed)
    worst = 0.0
    for r in pick:
        g = reference.gaps(ref, r.prompt, r.tokens)
        worst = max(worst, float(g.max()))
    return {"logit_gap_max": worst, "served_tokens": served,
            "requests": len(pick),
            "longest": max(len(r.prompt) + len(r.tokens) for r in pick)}


def run(args) -> int:
    spec = cell_spec(args.workload)
    chips = spec["workload"]["chips"]
    import jax
    use_cache(jax)
    device = require_chip(jax, chips)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.kernels import ops
    import devtrace

    log(f"device {device}")
    compiles = CompileCounter(jax)
    t = time.monotonic()
    eng = build_engine(spec, args.seed)
    log(f"weights and engine {time.monotonic() - t:.3f} s")
    t = time.monotonic()
    ops.DISPATCH.clear()
    warm_up(eng, spec, args.seed)
    log(f"warm-up {time.monotonic() - t:.3f} s")
    dispatch = ops.dispatch_record()
    log(f"dispatch of the window's programs {dispatch}")
    slow = [op for op in spec["arch"].PALLAS_OPS
            if set(dispatch.get(op, {})) != {"pallas"}]
    if device["platform"] == "tpu" and slow:
        raise RuntimeError(f"{slow} do not take their Pallas kernels: "
                           f"{dispatch}")

    tracing = bool(args.trace)
    annotate = ((lambda name: jax.profiler.TraceAnnotation(name)) if tracing
                else (lambda name: contextlib.nullcontext()))
    feed, shape, win = serve(eng, spec, args.seed, annotate, args.seconds)
    setup_s = win.start - PROCESS_START

    # -- the measured window ---------------------------------------------
    trace_dir = os.path.join(CACHE, "trace", args.workload)
    if tracing:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
    compiles.counting = True
    # the lead-in's last iteration may run past the window's opening; the
    # trace covers the window from here
    traced = Window(time.monotonic(), win.end)
    with annotate("bench.window"):
        feed.serve_until(win.end)
    compiles.counting = False
    if tracing:
        jax.profiler.stop_trace()
    in_window_compiles = compiles.count
    feed.drain(win, spec["cell"]["drain_limit_s"])
    mem_peak = memory_peak(jax, chips)
    late = [l for d, l in feed.lateness if win.inside(d)]
    log(f"compiles in window {in_window_compiles} {sorted(set(compiles.names))}")
    log(f"arrival lateness in window: median "
        f"{float(np.median(late)) if late else 0.0} s, max "
        f"{max(late) if late else 0.0} s over {len(late)} submits")
    log(f"peak HBM {mem_peak} bytes")

    e2e, counts = e2e_metrics(feed, win)
    log(f"window counts {counts}, drained {win.drained_at - win.end:.3f} s "
        f"after the window")
    due = feed.due_in(win)
    log("requests due in the window (prompt, output, ttft s): "
        + str([(t.prompt_len, len(t.req.tokens),
                None if t.req.first_token_time is None
                else round(t.req.first_token_time - t.due, 4)) for t in due]))
    result = {"correct": False, "attempted": counts["attempted"],
              "failed": counts["failed"]}
    device["memory_peak_bytes"] = mem_peak
    metrics = {}
    breakdown = None
    if tracing:
        red = devtrace.reduce_dir(trace_dir)
        ctx = devtrace.Context(reduction=red, feeder=feed, window=traced,
                                shape=shape, peak=peaks(device["kind"]),
                                chips=chips, spec=spec)
        for m in spec["per_layer"]:
            value = metric_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        breakdown = red.breakdown()
        del ctx
    else:
        for m in spec["end_to_end"]:
            if m["name"] == "setup_s":
                metrics["setup_s"] = {"value": setup_s, "unit": m["unit"]}
            elif e2e.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}

    # -- correctness, after the state is freed ----------------------------
    pick = sampled(spec, args.seed, feed, win)
    eng.params = eng.caches = None
    del eng, feed
    gc.collect()
    live = jax.live_arrays()
    log(f"device arrays left before the reference: {len(live)}, "
        f"{sum(a.nbytes for a in live)} bytes")
    del live
    t = time.monotonic()
    chk = check_outputs(spec, args.seed, pick)
    limit = spec["cell"]["check"]["logit_gap_limit"]
    log(f"reference check {time.monotonic() - t:.3f} s over "
        f"{chk.get('requests', 0)} requests, {chk['served_tokens']} served "
        f"tokens, longest sequence {chk.get('longest', 0)}")
    # no finished request to compare is not correct
    result["correct"] = (chk["logit_gap_max"] is not None
                         and chk["logit_gap_max"] <= limit)
    result["metrics"] = metrics
    result["device"] = device
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {"logit_gap_max": {"value": chk["logit_gap_max"],
                                          "limit": limit}}
    print(f"check logit_gap_max {chk['logit_gap_max']} limit {limit}",
          file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


def parse(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def main(argv=None) -> int:
    try:
        return run(parse(argv))
    except NoChip as e:
        print(f"[bench] {e}", file=sys.stderr, flush=True)
        return 2


if __name__ == "__main__":
    sys.exit(main())
