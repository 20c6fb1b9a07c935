#!/usr/bin/env python3
"""On-chip smoke test: gemma-2b at published width through the paged Engine.

    python chip_smoke.py               # one TPU chip
    python chip_smoke.py --four-chips  # DisaggEngine over four chips only

One process, no child processes. Phases run in order; the first failure
raises and the script exits non-zero without printing a result.

  (a) device: a TPU must be attached (JAX/libtpu versions are printed).
  (b) kernel parity: each Pallas kernel of the served path, compiled
      (``interpret=False``) at gemma-2b widths in bf16, against its
      ``kernels/ref.py`` oracle evaluated in float32 at highest matmul
      precision; max abs error must stay within ``KERNEL_ATOL``.
  (c) serve: ``repro.launch.serve`` at published width, once with
      whole-prompt admission and once chunked. Every request must finish
      with ``MAX_NEW`` tokens, no first-token logit may be NaN/inf, and the
      two paths' first-token logits must agree within ``LOGIT_RTOL``
      (relative L2 per request).
  (d) report: dispatch record (the served path must take the Pallas kernel
      for flash, paged decode and paged chunk attention), device peak
      bytes in use, compile and wall seconds. These are smoke numbers, not
      measurements.

``--four-chips`` runs only the disaggregated path: 2 prefill + 2 decode
workers, one per chip, against the single-chip ``oracle_engine``. Each
worker's params and pool must live on its own device, every KV handoff must
be device-to-device, and the token streams and the first- and last-token
logits must equal the oracle's bitwise.

The last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "gemma_2b"
REQUESTS = 8
MAX_NEW = 32
MAX_BATCH = 8
MAX_LEN = 1024
CHUNK = 128
KERNEL_ATOL = 3e-2      # bf16 inputs/outputs, O(1) attention outputs
# whole (flash kernel) vs chunked (paged chunk kernel) first-token logits:
# each of 18 layers adds a bf16 rounding difference of ~2**-8 relative, which
# random-walks to ~sqrt(18) * 2**-8 = 1.7e-2; allow three times that. A
# masking or layout fault moves the logits by O(1).
LOGIT_RTOL = 5e-2


def log(msg: str):
    print(f"[chip_smoke] {msg}", flush=True)


def check_device(jax, need: int):
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SystemExit(f"[chip_smoke] no TPU: JAX found {d.platform!r} "
                         f"devices; this smoke test runs only on a TPU")
    if len(devs) < need:
        raise SystemExit(f"[chip_smoke] need {need} TPU chips, found "
                         f"{len(devs)}")
    import jaxlib
    from importlib.metadata import PackageNotFoundError, version
    try:
        libtpu = version("libtpu")
    except PackageNotFoundError:
        libtpu = "unknown"
    info = {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}
    log(f"device {json.dumps(info)} jax={jax.__version__} "
        f"jaxlib={jaxlib.__version__} libtpu={libtpu}")
    return info


def kernel_parity(jax, jnp):
    """Phase (b): compiled kernels vs float32 oracles at gemma-2b widths."""
    from repro.configs import get_config
    from repro.kernels import ref
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.paged_attention import (paged_chunk_attention,
                                               paged_decode_attention,
                                               paged_verify_attention)
    cfg = get_config(ARCH)
    nh, kvh, d = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    b, bt, mb, s_ver, s_chunk = 8, 16, 64, 5, CHUNK
    nb = b * mb + 1
    key = jax.random.PRNGKey(0)

    def rnd(i, shape):
        return jax.random.normal(jax.random.fold_in(key, i), shape,
                                 jnp.float32).astype(jnp.bfloat16)

    kp, vp = rnd(1, (nb, bt, kvh, d)), rnd(2, (nb, bt, kvh, d))
    tab = jax.random.permutation(jax.random.fold_in(key, 3),
                                 nb)[:b * mb].reshape(b, mb).astype(jnp.int32)
    lens = jax.random.randint(jax.random.fold_in(key, 4), (b,), 1,
                              mb * bt - s_ver + 1).astype(jnp.int32)
    # chunk rows: every position valid, each extent inside the table
    chunk_lens = jnp.minimum(lens, mb * bt - s_chunk)
    full = jnp.full((b,), s_chunk, jnp.int32)
    # name -> (kernel, oracle, bf16 operands, int operands); flash is causal
    cases = {
        "paged_decode_attention": (
            paged_decode_attention, ref.paged_decode_attention,
            (rnd(5, (b, 1, nh, d)), kp, vp), (tab, lens)),
        "paged_verify_attention": (
            paged_verify_attention, ref.paged_verify_attention,
            (rnd(6, (b, s_ver, nh, d)), kp, vp), (tab, lens)),
        "paged_chunk_attention": (
            paged_chunk_attention,
            lambda q, k, v, t, n, _: ref.paged_chunk_attention(q, k, v, t, n),
            (rnd(10, (b, s_chunk, nh, d)), kp, vp), (tab, chunk_lens, full)),
    }
    for s in (256, 200):          # whole tiles, and a prompt padded to them
        cases[f"flash_attention_s{s}"] = (
            flash_attention, ref.flash_attention,
            (rnd(7, (1, s, nh, d)), rnd(8, (1, s, kvh, d)),
             rnd(9, (1, s, kvh, d))), ())
    errs = {}
    for name, (kernel, oracle, floats, ints) in cases.items():
        got = np.asarray(kernel(*floats, *ints).astype(jnp.float32))
        with jax.default_matmul_precision("highest"):
            want = oracle(*[x.astype(jnp.float32) for x in floats], *ints)
        assert np.isfinite(got).all(), f"{name}: non-finite kernel output"
        errs[name] = float(np.max(np.abs(got - np.asarray(want))))
    log(f"kernel parity max abs err (atol {KERNEL_ATOL}): "
        f"{json.dumps(errs)}")
    bad = {k: v for k, v in errs.items() if not v <= KERNEL_ATOL}
    assert not bad, f"kernel parity outside tolerance: {bad}"


def serve_both():
    """Phases (c) and (d): both admission paths through the entry point."""
    from repro.launch import serve
    base = ["--arch", ARCH, "--requests", str(REQUESTS), "--max-new",
            str(MAX_NEW), "--max-batch", str(MAX_BATCH), "--max-len",
            str(MAX_LEN)]
    runs = {"whole": serve.main(base),
            "chunked": serve.main(base + ["--chunk-size", str(CHUNK)])}
    for name, res in runs.items():
        done = res["done"]
        assert len(done) == REQUESTS, f"{name}: {len(done)} finished"
        for r in done:
            assert len(r.tokens) == MAX_NEW, \
                f"{name}: request {r.rid} has {len(r.tokens)} tokens"
            assert np.isfinite(r.first_logits).all(), \
                f"{name}: request {r.rid} first-token logits not finite"
        disp = res["dispatch"]
        fell_back = [op for op, impls in disp.items()
                     if "ref" in impls]
        assert not fell_back, f"{name}: ran the reference for {fell_back}"
        log(f"{name}: dispatch {json.dumps(disp)} compile_s "
            f"{res['compile_s']} wall_s {res['wall_s']} tokens "
            f"{res['tokens']}")
    assert "pallas" in runs["whole"]["dispatch"].get("flash_attention", {})
    for name in runs:
        assert "pallas" in runs[name]["dispatch"].get(
            "paged_decode_attention", {}), f"{name}: no paged decode kernel"
    assert "pallas" in runs["chunked"]["dispatch"].get(
        "paged_chunk_attention", {}), "chunked: no paged chunk kernel"
    rel_l2 = lambda x, y: float(np.linalg.norm(x - y)  # noqa: E731
                                / np.linalg.norm(x))
    rel, rel_last = [], []
    for a, c in zip(runs["whole"]["done"], runs["chunked"]["done"]):
        assert a.rid == c.rid and np.array_equal(a.prompt, c.prompt)
        rel.append(rel_l2(a.first_logits, c.first_logits))
        rel_last.append(rel_l2(a.last_logits, c.last_logits))
    same_first = sum(a.tokens[0] == c.tokens[0]
                     for a, c in zip(runs["whole"]["done"],
                                     runs["chunked"]["done"]))
    log(f"whole vs chunked first-token logits rel L2 (tol {LOGIT_RTOL}): "
        f"max {max(rel)} per request {rel}; same first token "
        f"{same_first}/{REQUESTS}; last-token logits rel L2 (reported, "
        f"not checked) max {max(rel_last)}")
    assert max(rel) <= LOGIT_RTOL, "whole and chunked logits disagree"


def four_chips(jax, cfg):
    """--four-chips: DisaggEngine (2 prefill + 2 decode, one device each)
    vs the single-device oracle engine, and nothing else."""
    from repro.engine.workers import DisaggEngine, oracle_engine
    from repro.launch import serve
    from repro.models import transformer as tf
    devs = jax.devices()[:4]
    t0 = time.monotonic()
    params = tf.init_params(cfg, jax.random.PRNGKey(0), False)
    prompts = serve.draw_prompts(np.random.default_rng(0), REQUESTS,
                                 cfg.vocab_size)
    geom = dict(max_batch=MAX_BATCH, max_len=MAX_LEN, keep_logits=True)

    oracle = oracle_engine(cfg, params, **geom)
    for p in prompts:
        oracle.submit(p, MAX_NEW)
    want = {r.rid: r for r in oracle.run()}
    del oracle
    oracle_s = time.monotonic() - t0

    t0 = time.monotonic()
    eng = DisaggEngine(cfg, params, n_prefill=2, n_decode=2,
                       devices=(devs[:2], devs[2:]), **geom)
    for p in prompts:
        eng.submit(p, MAX_NEW)
    got = {r.rid: r for r in eng.run()}
    disagg_s = time.monotonic() - t0

    placement = {}
    for role, workers in (("prefill", eng.prefill), ("decode", eng.decode)):
        for i, w in enumerate(workers):
            on = set()
            for leaf in jax.tree.leaves((w.params, w.caches)):
                on |= leaf.devices()
            assert on == {w.device}, f"{role}{i} arrays on {on}, " \
                f"expected {w.device}"
            placement[f"{role}{i}"] = str(w.device)
    recs = eng.transfers
    assert len(recs) == REQUESTS, f"{len(recs)} handoffs"
    assert all(r["staged"] == "device" for r in recs), \
        "a handoff was staged through the host"
    assert sorted(got) == sorted(want)
    for rid, r in got.items():
        # random-weight greedy streams mostly repeat the last prompt token,
        # so the logits are the real check: the first comes from a prefill
        # worker, the last from a decode worker reading handed-off KV
        assert r.tokens == want[rid].tokens, f"request {rid}: stream differs"
        for which in ("first_logits", "last_logits"):
            assert np.array_equal(getattr(r, which),
                                  getattr(want[rid], which)), \
                f"request {rid}: {which} differ from the oracle"
    ts = eng.transfer_stats()
    log(f"four-chips placement {json.dumps(placement)}")
    log(f"four-chips handoffs {ts['handoffs']} all device-to-device, "
        f"bytes {ts['bytes']} total_s {ts['total_s']}; streams and first/"
        f"last-token logits equal the oracle bitwise for {len(want)} "
        f"requests x {MAX_NEW} tokens; oracle wall "
        f"{oracle_s} s, disagg wall {disagg_s} s (compile included)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the disaggregated four-chip path")
    args = ap.parse_args(argv)
    t_start = time.monotonic()
    import jax
    import jax.numpy as jnp
    info = check_device(jax, 4 if args.four_chips else 1)
    from repro.launch import serve
    cache = serve.use_compile_cache()
    log(f"compile cache {cache}")
    if args.four_chips:
        from repro.configs import get_config
        four_chips(jax, get_config(ARCH))
    else:
        kernel_parity(jax, jnp)
        serve_both()
    stats = jax.devices()[0].memory_stats() or {}
    log(f"device0 peak_bytes_in_use {stats.get('peak_bytes_in_use')} of "
        f"bytes_limit {stats.get('bytes_limit')}; script wall "
        f"{time.monotonic() - t_start} s")
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
