"""Disaggregated prefill/decode serving over the shared ``EngineCore``.

The paper's §II-B claim — prefill and decode want different hardware and
batching regimes, so production serving splits them across workers and ships
the KV cache between them — has so far only been *priced* by the simulator
(``core/system.py`` "disaggregated" strategy, ``benchmarks/disaggregation``).
This module makes it real:

* ``PrefillWorker`` — an ``EngineCore`` that runs ONLY admission + prefill
  (whole-prompt or chunked). A request whose context is fully written
  becomes a handoff: the worker gathers its filled KV pages
  (``PagedKVStore.export_pages``), frees the table (registered prompt
  blocks park as evictable cache, so prefill-side prefix hits survive the
  handoff) and places ``(request, export, pages)`` in its outbox.
* ``DecodeWorker`` — an ``EngineCore`` that runs ONLY the decode pass.
  ``ingest`` queues a handoff FIFO-fairly; admission imports the pages into
  the worker's own pool (``PagedKVStore.import_pages`` — resident chain
  prefixes are aliased, and only unmatched pages are scattered) and decode
  continues from the streamed first token. Swap preemption stays local
  (host round-trip against this worker's pool); recompute preemption
  surfaces the victim in ``evicted`` — only a prefill worker can rebuild
  its KV, so the orchestrator routes it back (§II-B's "decode node cannot
  re-prefill" asymmetry, made concrete).
* ``DisaggEngine`` — the orchestrator: ``n_prefill`` x ``n_decode`` workers
  paired per the simulator's disaggregation modes ("local" = fixed
  prefill->decode pairing, "global" = any-to-any, deterministic
  least-loaded) with the KV handoff as a REAL page transfer:
  device-to-device ``jax.device_put`` when the host gives each role its own
  device (``launch.mesh.handoff_devices``), host-staged ``jax.device_get``
  otherwise. ``granularity="full"`` moves the whole table in one timed
  transfer; ``"layerwise"`` moves it layer by layer (paper §III-B2) — the
  exposed stall is then ~one layer (the rest overlaps pipelined compute),
  while total wire bytes are identical. Every handoff is timed;
  ``transfer_stats()`` feeds ``benchmarks/engine_disagg.py``, which fits
  ``LinkSpec`` constants from the samples and backfills the simulator's
  ``core/comm.py`` pricing (the measure->calibrate->replay loop).

Bit-equality oracle: under greedy decoding the disaggregated path must emit
token streams bit-identical to the single-device ``Engine`` — prefill
numerics, the handoff (pages move verbatim; aliased pages hold equal bits by
the hash-chain contract), and per-row decode numerics are all unchanged, and
every scheduling difference (worker pairing, admission order, preemption)
only reorders WHEN tokens are computed, never WHAT they are
(``tests/test_disagg_engine.py``).

Wire-dedup note: the transfer always moves the full filled page range; a
decode-resident chain prefix saves the pool *write* and is reported as
``import_dedup_blocks`` — the bytes a pinned-dedup wire protocol could have
skipped, which is exactly what the simulator's coordinator prices as
``kv_transfer_dedup_bytes``.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.engine.core import Engine, EngineConfig, EngineCore, EngineRequest
from repro.engine.paged_kv import PageExport
from repro.launch.mesh import handoff_devices
from repro.models import transformer as tf


@dataclass
class KVHandoff:
    """One prefill->decode KV handoff in flight: the request (stream and
    timing state ride along), its export snapshot fields, the staged page
    payload (on the decode worker's device, or host numpy when staged
    through the host), and the timed transfer record."""
    req: EngineRequest
    ctx: np.ndarray
    tokens: int
    chain: List[int]
    pages: Dict
    record: Dict


def _page_slice(pages, start: int):
    """Tail-slice a gathered page payload along the page axis (drop the
    leading ``start`` pages — the ones the importing store aliased)."""
    return {name: {"k": g["k"][:, start:], "v": g["v"][:, start:]}
            for name, g in pages.items()}


def move_pages(pages, device, granularity: str) -> Tuple[Dict, Dict]:
    """Physically move a gathered page payload to ``device`` (None =
    host-staged: ``jax.device_get`` to numpy), timing the transfer.

    ``full`` moves the whole payload as one transfer. ``layerwise`` moves
    one layer of one cache group per transfer (paper §III-B2): total wire
    bytes are identical, but the *exposed* stall is the slowest single
    layer — every other layer overlaps the consumer's layerwise compute,
    exactly how the simulator's ``Network._exposed`` prices it.

    Returns ``(staged_pages, record)`` where ``record`` carries
    ``bytes / pages / layers / granularity / total_s / exposed_s`` and the
    raw ``samples`` list of ``(bytes, seconds)`` per timed transfer — the
    points ``benchmarks/engine_disagg.py`` fits ``LinkSpec`` constants
    from."""
    assert granularity in ("full", "layerwise")
    leaves = jax.tree_util.tree_leaves(pages)
    for x in leaves:
        x.block_until_ready()              # exclude producer compute
    nbytes = int(sum(x.nbytes for x in leaves))
    n_pages = int(leaves[0].shape[1]) if leaves else 0
    n_layers = int(sum(g["k"].shape[0] for g in pages.values()))
    samples: List[Tuple[int, float]] = []
    if granularity == "full":
        t0 = time.perf_counter()
        if device is not None:
            staged = jax.device_put(pages, device)
            jax.block_until_ready(staged)
        else:
            staged = jax.device_get(pages)
        dt = time.perf_counter() - t0
        samples.append((nbytes, dt))
        total = exposed = dt
    else:
        staged = {}
        total, exposed = 0.0, 0.0
        for name, g in pages.items():
            ks, vs = [], []
            for layer in range(g["k"].shape[0]):
                sk, sv = g["k"][layer], g["v"][layer]
                sk.block_until_ready()
                sv.block_until_ready()
                lbytes = int(sk.nbytes + sv.nbytes)
                t0 = time.perf_counter()
                if device is not None:
                    ok = jax.device_put(sk, device)
                    ov = jax.device_put(sv, device)
                    jax.block_until_ready((ok, ov))
                else:
                    ok = jax.device_get(sk)
                    ov = jax.device_get(sv)
                dt = time.perf_counter() - t0
                samples.append((lbytes, dt))
                total += dt
                exposed = max(exposed, dt)
                ks.append(ok)
                vs.append(ov)
            # reassemble the layer axis on the destination side (pipeline
            # plumbing, not wire time — excluded from the samples)
            if device is not None:
                with jax.default_device(device):
                    staged[name] = {"k": jnp.stack(ks), "v": jnp.stack(vs)}
            else:
                staged[name] = {"k": np.stack(ks), "v": np.stack(vs)}
    record = {
        "bytes": nbytes,
        "pages": n_pages,
        "layers": n_layers,
        "granularity": granularity,
        "staged": "device" if device is not None else "host",
        "total_s": total,
        "exposed_s": exposed,
        "samples": samples,
    }
    return staged, record


class PrefillWorker(EngineCore):
    """Prefill-only role: admission + (whole or chunked) prefill, then
    export. Never decodes — a request whose context is fully in KV leaves
    through the outbox the same step it completes."""

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        assert not self.spec, \
            "speculative decoding is a single-engine feature (the draft " \
            "rides the decode pass, which this role never runs)"
        self.outbox: List[Tuple[EngineRequest, PageExport, Dict]] = []

    def step(self) -> bool:
        """One prefill iteration: admit (whole-prompt admission prefills
        inline), advance chunk-phase rows one chunked pass, export every
        row whose context completed. Returns True when any work happened."""
        with self._dev_scope():
            self._admit()
            worked = False
            if self.chunk_size and any(
                    r is not None and not self._is_decoding(r)
                    for r in self.active):
                self._chunk_pass()
                worked = True
            return bool(self._export_ready()) or worked

    def _export_ready(self) -> int:
        n = 0
        for slot in range(self.max_batch):
            r = self.active[slot]
            if r is None or not self._is_decoding(r):
                continue
            exp = self.store.export_pages(r.rid)
            ids = jnp.asarray(np.asarray(exp.blocks, np.int32))
            pages = self._gather_pages(self.caches, ids)
            jax.block_until_ready(pages)
            # free AFTER the gather: registered prompt blocks park as
            # evictable cache, so later prompts sharing the prefix still
            # alias them (prefill-side prefix hits survive the handoff)
            self.store.free(r.rid)
            del self._admit_order[r.rid]
            self.active[slot] = None
            self._clear_row(slot)
            r.slot = None
            r.state = "handoff"
            self.outbox.append((r, exp, pages))
            n += 1
        return n


class DecodeWorker(EngineCore):
    """Decode-only role: imports handed-off KV pages into its own pool and
    continues the stream. Swap preemption round-trips pages against THIS
    worker's pool; recompute preemption cannot be satisfied here (no
    prefill pass) — victims surface in ``evicted`` for the orchestrator to
    route back to a prefill worker."""

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        assert not self.spec, \
            "speculative decoding is a single-engine feature for now"
        self._handoffs: Dict[int, KVHandoff] = {}
        self.evicted: List[EngineRequest] = []

    def ingest(self, h: KVHandoff):
        """Queue a transferred handoff FIFO-fairly (by rid, merged with any
        swap victims awaiting re-admission). The staged pages wait with it;
        admission scatters them when a slot and pool capacity open up."""
        assert h.req.state == "handoff"
        self._handoffs[h.req.rid] = h
        self.enqueue(h.req)

    def _admit_one(self, slot: int, r: EngineRequest) -> bool:
        if r.state != "handoff":
            assert r.state == "swapped", \
                f"decode worker cannot admit a {r.state!r} request (only " \
                "handoffs and its own swap victims)"
            return super()._admit_one(slot, r)
        h = self._handoffs[r.rid]
        got = self.store.import_pages(r.rid, h.tokens, h.chain)
        if got is None:
            return False                   # head-of-line wait, like any path
        blocks, n_matched = got
        if n_matched < len(blocks):
            ids = jnp.asarray(np.asarray(blocks[n_matched:], np.int32))
            self.caches = self._scatter_pages(
                self.caches, _page_slice(h.pages, n_matched), ids)
        self._set_row(slot, blocks, h.tokens)
        r.ctx = h.ctx
        r.prefilled = h.tokens
        del self._handoffs[r.rid]
        self._place(slot, r)
        return True

    def step(self) -> bool:
        """One decode iteration: admit (imports + swap-ins), grow, decode.
        Returns True when a decode pass ran."""
        with self._dev_scope():
            self._admit()
            worked = False
            if any(a is not None for a in self.active):
                self._grow_active()
                self._decode_pass()
                self._trace_step()
                worked = True
            # recompute victims need a prefill worker to rebuild their KV
            out = [r for r in self.waiting if r.state == "preempted"]
            if out:
                self.waiting = [r for r in self.waiting
                                if r.state != "preempted"]
                self.evicted.extend(out)
            return worked


class DisaggEngine:
    """Disaggregated serving orchestrator: ``Engine``-compatible
    ``submit``/``run`` over prefill and decode worker fleets with a real
    KV-page handoff between them (see module docstring).

    * ``mode`` — "local" pins prefill worker ``i`` to decode worker
      ``i % n_decode`` (the simulator's fixed fast-pair wiring); "global"
      routes every handoff to the least-loaded decode worker (any-to-any,
      deterministic).
    * ``granularity`` — "full" | "layerwise" KV transfer (§III-B2).
    * ``prefill_blocks`` / ``decode_blocks`` — per-role pool sizes (None =
      pressure-free default); shrink them to exercise preemption on either
      side of the handoff.
    * ``keep_logits`` — passed to every worker (``EngineCore``).
    * ``devices`` — optional ``(prefill_devices, decode_devices)`` lists;
      default asks ``launch.mesh.handoff_devices`` (real cross-device
      ``jax.device_put`` when the host has >= 2 devices, host-staged
      otherwise).
    """

    def __init__(self, cfg: ModelConfig, params=None, *,
                 n_prefill: int = 1, n_decode: int = 1, mode: str = "local",
                 granularity: str = "full", max_batch: int = 4,
                 max_len: int = 512, seed: int = 0, block_tokens: int = 16,
                 prefill_blocks: Optional[int] = None,
                 decode_blocks: Optional[int] = None,
                 preemption: str = "swap",
                 config: Optional[EngineConfig] = None,
                 trace_occupancy: bool = False, devices=None,
                 keep_logits: bool = False):
        assert mode in ("local", "global")
        assert granularity in ("full", "layerwise")
        assert n_prefill >= 1 and n_decode >= 1
        self.cfg = cfg
        self.mode = mode
        self.granularity = granularity
        config = config or EngineConfig()
        assert config.draft_cfg is None and config.spec_k == 0, \
            "speculative decoding is a single-engine feature for now"
        if params is None:
            params = tf.init_params(cfg, jax.random.PRNGKey(seed))
        if devices is None:
            devices = handoff_devices(n_prefill, n_decode)
        pdevs, ddevs = devices
        kw = dict(max_batch=max_batch, max_len=max_len,
                  block_tokens=block_tokens, preemption=preemption,
                  config=config, trace_occupancy=trace_occupancy,
                  keep_logits=keep_logits)
        self.prefill = [PrefillWorker(cfg, params, num_blocks=prefill_blocks,
                                      device=pdevs[i], **kw)
                        for i in range(n_prefill)]
        self.decode = [DecodeWorker(cfg, params, num_blocks=decode_blocks,
                                    device=ddevs[j], **kw)
                       for j in range(n_decode)]
        self._next_rid = 0
        self._rr = 0
        self._home: Dict[int, int] = {}    # rid -> prefill worker index
        self.finished: List[EngineRequest] = []
        self.transfers: List[Dict] = []    # one timed record per handoff
        self.steps = 0

    # ------------------------------------------------------------------
    def submit(self, prompt: np.ndarray, max_new_tokens: int = 32,
               eos_id: Optional[int] = None) -> EngineRequest:
        prompt = np.asarray(prompt, np.int32)
        # a request must fit BOTH roles' geometry: it prefills (and may
        # re-prefill after a decode-side recompute) on a prefill worker and
        # decodes to its stop bound on a decode worker
        self.prefill[0]._validate_submit(prompt, max_new_tokens)
        self.decode[0]._validate_submit(prompt, max_new_tokens)
        r = EngineRequest(rid=self._next_rid, prompt=prompt,
                          max_new_tokens=max_new_tokens, eos_id=eos_id,
                          submit_time=time.monotonic())
        self._next_rid += 1
        idx = self._rr % len(self.prefill)
        self._rr += 1
        self._home[r.rid] = idx
        self.prefill[idx].waiting.append(r)
        return r

    def _route(self, src_idx: int) -> int:
        if self.mode == "local":
            return src_idx % len(self.decode)
        # global: deterministic least-loaded (queued + staged + active)
        return min(range(len(self.decode)),
                   key=lambda j: (len(self.decode[j].waiting)
                                  + len(self.decode[j]._handoffs)
                                  + sum(a is not None
                                        for a in self.decode[j].active)))

    def _pending(self) -> bool:
        for w in self.prefill:
            if w.waiting or w.outbox or any(a is not None for a in w.active):
                return True
        for w in self.decode:
            if (w.waiting or w._handoffs or w.evicted
                    or any(a is not None for a in w.active)):
                return True
        return False

    def run(self, max_steps: int = 100_000) -> List[EngineRequest]:
        while self._pending() and self.steps < max_steps:
            self.steps += 1
            progress = False
            for i, pw in enumerate(self.prefill):
                if pw.step():
                    progress = True
                while pw.outbox:
                    r, exp, pages = pw.outbox.pop(0)
                    j = self._route(i)
                    dw = self.decode[j]
                    staged, rec = move_pages(pages, dw.device,
                                             self.granularity)
                    rec.update(rid=r.rid, src=f"prefill{i}",
                               dst=f"decode{j}")
                    self.transfers.append(rec)
                    dw.ingest(KVHandoff(req=r, ctx=r.ctx, tokens=exp.tokens,
                                        chain=exp.chain, pages=staged,
                                        record=rec))
                    progress = True
            for j, dw in enumerate(self.decode):
                if dw.step():
                    progress = True
                if dw.finished:
                    self.finished.extend(dw.finished)
                    dw.finished = []
                while dw.evicted:
                    r = dw.evicted.pop(0)
                    self.prefill[self._home[r.rid]].enqueue(r)
                    progress = True
            if not progress and self._pending():
                raise RuntimeError(
                    "disaggregated engine stalled: a queued request cannot "
                    "be admitted on any worker (pool too small for the "
                    "handoff?)")
        return self.finished

    # ------------------------------------------------------------------
    def transfer_stats(self) -> Dict[str, object]:
        """Aggregated handoff telemetry: wire bytes/pages moved, total and
        exposed transfer seconds, the raw ``(bytes, seconds)`` fit samples,
        and decode-side dedup (pool writes skipped for resident prefixes)."""
        recs = self.transfers
        return {
            "granularity": self.granularity,
            "mode": self.mode,
            "handoffs": len(recs),
            "bytes": int(sum(r["bytes"] for r in recs)),
            "pages": int(sum(r["pages"] for r in recs)),
            "total_s": float(sum(r["total_s"] for r in recs)),
            "exposed_s": float(sum(r["exposed_s"] for r in recs)),
            "samples": [s for r in recs for s in r["samples"]],
            "dedup_blocks": int(sum(w.store.import_dedup_blocks
                                    for w in self.decode)),
            "cross_device": any(r["staged"] == "device" for r in recs),
        }

    def kv_stats(self) -> Dict[str, Dict[str, float]]:
        return {
            **{f"prefill{i}": w.kv_stats()
               for i, w in enumerate(self.prefill)},
            **{f"decode{j}": w.kv_stats()
               for j, w in enumerate(self.decode)},
        }


def oracle_engine(cfg: ModelConfig, params=None, **kw) -> Engine:
    """The single-device ``Engine`` with the same geometry kwargs
    ``DisaggEngine`` takes — convenience for parity harnesses that build
    both sides from one kwarg dict."""
    kw.pop("n_prefill", None)
    kw.pop("n_decode", None)
    kw.pop("mode", None)
    kw.pop("granularity", None)
    kw.pop("devices", None)
    kw.pop("prefill_blocks", None)
    kw.pop("decode_blocks", None)
    return Engine(cfg, params, **kw)
