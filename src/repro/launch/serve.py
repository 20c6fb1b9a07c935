"""Serving entry point: the paged continuous-batching ``Engine`` on one device.

    PYTHONPATH=src python -m repro.launch.serve                  # gemma-2b, published width
    PYTHONPATH=src python -m repro.launch.serve --chunk-size 128 # chunked admission
    PYTHONPATH=src python -m repro.launch.serve --reduced        # CPU-sized model

Weights are random, drawn from ``--seed`` with live output projections
(``zero_out=False``: the training init would make every block the identity,
and attention would never reach the logits). Prompts are drawn from ``--seed``
over the fixed lengths ``PROMPT_LENS``, so whole-prompt admission compiles
one prefill program per length and no more. Before the timed run one
warm-up request per prompt length compiles every program of the path; its
time is reported as set-up (``compile_s``), not as serving time.

The first line printed names the device (platform, kind, count). The last
is a ``[serve]`` JSON summary; ``main`` returns the same dict with the
finished requests attached, which is how ``chip_smoke.py`` drives this
module.
"""
from __future__ import annotations

import argparse
import json
import os
import time
from typing import Dict, List

import jax
import numpy as np

from repro.configs import get_config, get_reduced_config
from repro.engine.core import EngineConfig, make_engine
from repro.kernels import ops
from repro.models import transformer as tf

PROMPT_LENS = (64, 128, 256)
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing
    is overridden; otherwise the cache lives at the fixed path
    ``<repo>/.jax_cache`` (git-ignored), so later runs from this checkout
    find it again. Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def device_info() -> Dict[str, object]:
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def draw_prompts(rng: np.random.Generator, n: int,
                 vocab: int) -> List[np.ndarray]:
    """``n`` random prompts whose lengths come from ``PROMPT_LENS``."""
    lens = rng.choice(PROMPT_LENS, size=n)
    return [rng.integers(0, vocab, int(p), dtype=np.int32) for p in lens]


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--arch", default="gemma_2b")
    ap.add_argument("--reduced", action="store_true",
                    help="serve the arch's reduced (CPU-sized) config "
                         "instead of its published widths")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=1024)
    ap.add_argument("--chunk-size", type=int, default=0,
                    help="0 = whole-prompt admission; >0 = chunked "
                         "admission with chunks of this many tokens")
    ap.add_argument("--seed", type=int, default=0)
    return ap


def main(argv=None) -> Dict[str, object]:
    args = build_parser().parse_args(argv)
    use_compile_cache()
    ops.DISPATCH.clear()
    dev = device_info()
    print(json.dumps({"device": dev}), flush=True)

    cfg = (get_reduced_config(args.arch) if args.reduced
           else get_config(args.arch))
    if not cfg.supports_decode:
        raise SystemExit(f"{args.arch} is encoder-only; no serving path")
    t0 = time.monotonic()
    params = tf.init_params(cfg, jax.random.PRNGKey(args.seed), False)
    eng = make_engine(cfg, params=params, max_batch=args.max_batch,
                      max_len=args.max_len,
                      config=EngineConfig(chunk_size=args.chunk_size),
                      keep_logits=True)
    warm_rng = np.random.default_rng([args.seed, 1])
    for p in PROMPT_LENS:
        eng.submit(warm_rng.integers(0, cfg.vocab_size, p, dtype=np.int32), 2)
    eng.run()
    eng.finished = []
    warm_steps = eng.steps
    compile_s = time.monotonic() - t0

    rng = np.random.default_rng(args.seed)
    t0 = time.monotonic()
    for prompt in draw_prompts(rng, args.requests, cfg.vocab_size):
        eng.submit(prompt, args.max_new)
    done = sorted(eng.run(), key=lambda r: r.rid)
    wall_s = time.monotonic() - t0

    toks = sum(len(r.tokens) for r in done)
    summary = {
        "device": dev, "arch": cfg.name,
        "width": "reduced" if args.reduced else "published",
        "chunk_size": args.chunk_size, "requests": len(done),
        "tokens": toks, "engine_steps": eng.steps - warm_steps,
        "compile_s": compile_s, "wall_s": wall_s,
        "tokens_per_s": toks / wall_s,
        "ttft_mean_s": float(np.mean([r.ttft for r in done])),
        "tpot_mean_s": float(np.mean([r.tpot for r in done
                                      if r.tpot is not None] or [0.0])),
        "dispatch": ops.dispatch_record(),
    }
    print("[serve] " + json.dumps(summary), flush=True)
    return {**summary, "done": done}


if __name__ == "__main__":
    main()
