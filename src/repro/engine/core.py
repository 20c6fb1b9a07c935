"""Core machinery of the real-execution serving engine.

Historically ``engine/runner.py`` held one monolithic ``Engine`` class —
admission, chunked prefill, decode, speculative decoding, preemption and
block-table bookkeeping all interleaved. This module is the refactor of that
class into composable layers:

* ``EngineCore`` — everything role-agnostic: request/record types, the paged
  ``PagedKVStore`` + physical cache pool, block-table row maintenance,
  admission paths (whole, chunked, swap-in), preemption (swap/recompute),
  growth, and the two forward passes as separately callable units —
  ``_decode_pass`` (one ``(b, 1)`` decode over decode-phase rows) and
  ``_chunk_pass`` (one ``(b, chunk_size)`` chunked-prefill advance).
* ``Engine(EngineCore)`` — the single-device engine: ``run()`` drives mixed
  iterations (decode + chunk), legacy whole-prefill iterations, or
  speculative iterations over the shared core. Public behavior is unchanged;
  ``engine/runner.py`` re-exports it so existing imports keep working.
* ``PrefillWorker`` / ``DecodeWorker`` / ``DisaggEngine``
  (``engine/workers.py``) — disaggregated serving: each worker is an
  ``EngineCore`` that runs ONLY its role's pass; finished prefills hand
  their KV pages to a decode worker through a real transfer path
  (``PagedKVStore.export_pages`` / ``import_pages``).
* ``SlotEngine`` — the original dense per-slot engine, kept verbatim as the
  bit-exactness oracle (``tests/test_paged_engine.py``).

Interface contract (paged ``Engine``)
-------------------------------------
* Geometry: ``max_len`` must be a multiple of ``block_tokens``;
  ``max_blocks = max_len // block_tokens``; the physical pool holds
  ``num_blocks`` allocatable pages plus one *trash page* (index
  ``num_blocks``). ``num_blocks`` defaults to ``max_batch * max_blocks``
  (no memory pressure); shrink it to exercise preemption for real.
* Block-table layout: row ``i`` of the ``(max_batch, max_blocks)`` table
  maps logical token position ``p`` to physical page
  ``table[i, p // block_tokens]``, slot ``p % block_tokens``. Dead rows
  (no active request) point every entry at the trash page with length 0 —
  their decode output is garbage the engine ignores, exactly like the dense
  engine's stale slots, and their masked writes land in the trash page so
  they can never corrupt a live page.
* Length-masking: the model sees ``lengths`` per row and masks
  ``pos >= length`` to probability exactly 0, so stale page content (prior
  occupants, trash) cannot leak into live rows.
* Admission reserves ``ceil(context / block_tokens)`` pages; full
  block-aligned *prompt* blocks register in the store's radix index, and a
  later admission whose prompt shares the block-aligned prefix maps the same
  physical pages (refcount bump — real dedup, visible in
  ``Engine.kv_stats()``).
* Speculative decoding (``EngineConfig(draft_cfg=..., spec_k=...)``): each
  iteration drafts up to ``spec_k`` greedy tokens per row with a small draft
  model (its own paged pool), COW-forks the target block tables
  (``PagedKVStore.fork_table``), scores draft + bonus positions in ONE
  target pass (``paged_verify_attention``), and commits the longest
  agreeing prefix — rejected KV rolls back via ``abort``/trim, so greedy
  streams stay bit-identical to plain decode while emitting up to
  ``spec_k + 1`` tokens per target pass.
* Preemption (``preemption="swap" | "recompute"``) is *real*:
  swap moves the victim's pages device -> host (``jax.device_get`` of the
  gathered pages; ``jax.device_put`` scatters them back on resume) and
  recompute drops the pages and re-prefills ``prompt + generated[:-1]`` on
  re-admission. Both keep every token generated so far. Victims requeue
  FIFO-fairly (by original submit order), and a shared-page victim degrades
  from swap to recompute — the same composition rule the simulator uses.

Cross-link: ``docs/architecture.md`` ("Paged real-execution engine" and
"Disaggregated engine") maps this module against the simulator stack layer
by layer.
"""
from __future__ import annotations

import bisect
import contextlib
import functools
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.engine.paged_kv import PagedKVStore, prefix_chain
from repro.models import steps
from repro.models import transformer as tf


@dataclass
class EngineConfig:
    """Scheduling policy for the paged ``Engine`` — the TTFT-vs-ITL knob.

    ``chunk_size == 0`` keeps the legacy whole-prompt admission path (one
    blocking prefill per admission). With ``chunk_size > 0`` every scheduler
    iteration becomes a MIXED iteration: running decodes take their normal
    ``(b, 1)`` step AND waiting/partial prefills advance by up to one
    ``(b, chunk_size)`` chunked-prefill pass in the same iteration, so a
    long prompt never stalls running decodes for its whole length.

    * ``chunk_size`` — prompt tokens per request per iteration. Smaller
      chunks bound the per-iteration prefill work (better ITL for running
      decodes), larger chunks finish prompts in fewer passes (better TTFT).
    * ``token_budget`` — total forward tokens an iteration may spend across
      both passes; 0 defaults to ``max_batch + chunk_size`` (all decodes
      plus one full chunk).
    * ``decode_share`` — fraction of ``token_budget`` reserved for decode
      rows while any are running; the leftover is the chunk budget. 0 keeps
      the default reservation (exactly the running decodes); 1.0 starves
      prefill completely until every running decode finishes (max-ITL
      extreme of the knob).
    * ``max_context`` — logical KV tokens a single request may span; 0
      defaults to ``max_len``. Raising it (multiple of ``block_tokens``)
      lets the chunked engine serve prompts far beyond ``max_len`` — the
      per-pass working set stays ``chunk_size`` wide regardless.

    Speculative decoding (``draft_cfg`` + ``spec_k``, requires
    ``chunk_size == 0``): every iteration runs a small draft model for up
    to ``spec_k`` greedy tokens per row, verifies them in ONE target pass
    (``paged_verify_attention``), and commits the longest matching prefix
    plus the bonus token — up to ``spec_k + 1`` tokens per target pass
    instead of 1, with greedy streams bit-identical to plain decode.

    * ``draft_cfg`` — ModelConfig of the draft model (gqa-family, same
      vocab as the target). None disables speculation.
    * ``spec_k`` — draft tokens proposed per iteration (0 disables).
    * ``draft_seed`` — init seed for the draft params when the engine is
      not handed ``draft_params`` explicitly.
    """
    chunk_size: int = 0
    token_budget: int = 0
    decode_share: float = 0.0
    max_context: int = 0
    draft_cfg: Optional[ModelConfig] = None
    spec_k: int = 0
    draft_seed: int = 1


@dataclass
class EngineRequest:
    rid: int
    prompt: np.ndarray                       # (p,) int32
    max_new_tokens: int = 32
    eos_id: Optional[int] = None
    submit_time: float = 0.0
    first_token_time: Optional[float] = None
    finish_time: Optional[float] = None
    tokens: List[int] = field(default_factory=list)
    token_times: List[float] = field(default_factory=list)
    # logits behind tokens[0] and tokens[-1], kept only by an engine built
    # with keep_logits=True (numerics checks across admission paths and
    # devices; greedy streams of random-weight models cannot show them)
    first_logits: Optional[np.ndarray] = None
    last_logits: Optional[np.ndarray] = None
    slot: Optional[int] = None
    # new | running | swapped | preempted | handoff | done
    # ("handoff": prefill complete, KV pages in flight to a decode worker —
    # disaggregated serving only, see engine/workers.py)
    state: str = "new"
    preemptions: int = 0
    # chunked-prefill continuation state: ``ctx`` is the full context this
    # admission must write to KV (prompt, or prompt + generated[:-1] on a
    # recompute resume) and ``prefilled`` counts how much of it is written.
    # ``prefilled == len(ctx)`` marks the request decode-phase.
    ctx: Optional[np.ndarray] = None
    prefilled: int = 0

    @property
    def itl(self) -> List[float]:
        """Inter-token latencies (seconds) between consecutive streamed
        tokens — the per-request tail-latency surface the chunked scheduler
        is tuned against."""
        return [b - a for a, b in zip(self.token_times, self.token_times[1:])]

    @property
    def ttft(self):
        return (self.first_token_time - self.submit_time
                if self.first_token_time else None)

    @property
    def tpot(self):
        if self.finish_time is None or self.first_token_time is None:
            return None
        return ((self.finish_time - self.first_token_time)
                / max(1, len(self.tokens) - 1))


class EngineCore:
    """Role-agnostic core of the paged engine: store + cache pool + block
    tables + admission/preemption/growth + the decode and chunk passes as
    separately callable units. ``Engine`` composes every pass on one
    device; the disaggregated workers (``engine/workers.py``) each run only
    their role's pass. ``device`` pins this core's pool (and every pass it
    runs) to one jax device — None keeps the default device, which is also
    the host-staged fallback for single-device hosts.
    ``keep_logits`` copies the logits behind each request's first and
    latest token to the host (``EngineRequest.first_logits`` /
    ``last_logits``; not kept by speculative iterations)."""

    def __init__(self, cfg: ModelConfig, params=None, max_batch: int = 4,
                 max_len: int = 512, seed: int = 0, block_tokens: int = 16,
                 num_blocks: Optional[int] = None, preemption: str = "swap",
                 trace_occupancy: bool = False,
                 config: Optional[EngineConfig] = None, draft_params=None,
                 device=None, keep_logits: bool = False):
        assert max_len % block_tokens == 0, \
            "max_len must be a multiple of block_tokens (bit-exact parity " \
            "with the dense engine needs identical logical cache length)"
        assert preemption in ("swap", "recompute")
        self.config = config or EngineConfig()
        self.chunk_size = self.config.chunk_size
        assert self.chunk_size >= 0
        max_context = self.config.max_context or max_len
        assert self.chunk_size or max_context == max_len, \
            "max_context > max_len needs chunked prefill (chunk_size > 0): " \
            "the whole-prompt path prefills through a (1, max_len) cache"
        assert max_context % block_tokens == 0 and max_context >= max_len, \
            "max_context must be a multiple of block_tokens and >= max_len"
        self.cfg = cfg
        self.max_batch = max_batch
        self.max_len = max_len
        self.max_context = max_context
        # generation stop bound AND eager-validation bound for submit():
        # chunked rows may span max_context, whole-prefill rows cap at
        # max_len exactly like the dense oracle
        self._len_limit = max_context if self.chunk_size else max_len
        self.block_tokens = block_tokens
        self.max_blocks = max_context // block_tokens
        self.num_blocks = (max_batch * self.max_blocks if num_blocks is None
                           else num_blocks)
        self.preemption = preemption
        self.device = device
        self.keep_logits = keep_logits
        with self._dev_scope():
            if params is None:
                params = tf.init_params(cfg, jax.random.PRNGKey(seed))
            elif device is not None:
                params = jax.device_put(params, device)
            self.params = params
            self.store = PagedKVStore(self.num_blocks, block_tokens)
            self.caches = tf.init_paged_cache(cfg, max_batch, self.num_blocks,
                                              block_tokens, self.max_blocks)
        trash = self.store.trash_block
        self._tables_np = np.full((max_batch, self.max_blocks), trash,
                                  np.int32)
        self._lengths_np = np.zeros((max_batch,), np.int32)
        self.active: List[Optional[EngineRequest]] = [None] * max_batch
        self.waiting: List[EngineRequest] = []
        self.finished: List[EngineRequest] = []
        self.steps = 0
        self._next_rid = 0
        self._admit_seq = 0
        self._admit_order: Dict[int, int] = {}   # rid -> admit seq
        self.trace_occupancy = trace_occupancy
        self.occupancy: List[Dict] = []          # per-step block occupancy

        bt, mb = self.block_tokens, self.max_blocks

        @jax.jit
        def _prefill_one(params, tokens):
            return steps.prefill_step(params, {"tokens": tokens}, cfg, max_len)

        @jax.jit
        def _decode(params, tokens, caches):
            return steps.serve_step(params, tokens, caches, cfg)

        @jax.jit
        def _chunk(params, tokens, q_valid, caches):
            return steps.chunk_step(params, tokens, q_valid, caches, cfg)

        self._prefill_one = _prefill_one
        self._decode = _decode
        self._chunk = _chunk
        # pure page-movement kernels live in models/steps.py so the single
        # engine, the disaggregated workers and the spec-decode path all
        # share one implementation
        self._write_prefill = jax.jit(functools.partial(
            steps.write_prefill_pages, max_blocks=mb, block_tokens=bt))
        self._gather_pages = jax.jit(steps.gather_pages)
        self._scatter_pages = jax.jit(steps.scatter_pages)

        # -- speculative decoding (draft model + verify pass) ----------
        self.spec_k = self.config.spec_k
        self.draft_cfg = self.config.draft_cfg
        self.spec = self.draft_cfg is not None and self.spec_k > 0
        if self.spec:
            assert self.chunk_size == 0, \
                "speculative decoding needs the whole-prefill path " \
                "(EngineConfig.chunk_size == 0)"
            assert paged_supported(self.draft_cfg), \
                "draft model must serve through the paged cache path"
            assert self.draft_cfg.vocab_size == cfg.vocab_size, \
                "draft and target must share a vocabulary"
            dcfg = self.draft_cfg
            with self._dev_scope():
                if draft_params is None:
                    draft_params = tf.init_params(
                        dcfg, jax.random.PRNGKey(self.config.draft_seed))
                self.draft_params = draft_params
                # the draft pool is sized so it can NEVER hit pressure:
                # capacity planning stays a target-pool problem and draft
                # admission is infallible (a draft page is kvh*hd of a tiny
                # model — cheap)
                self.draft_store = PagedKVStore(max_batch * self.max_blocks,
                                                block_tokens)
                self.draft_caches = tf.init_paged_cache(
                    dcfg, max_batch, self.draft_store.num_blocks, block_tokens,
                    self.max_blocks)
            self._draft_tables_np = np.full(
                (max_batch, self.max_blocks), self.draft_store.trash_block,
                np.int32)
            self._draft_lengths_np = np.zeros((max_batch,), np.int32)
            # rid -> number of leading draft-cache positions whose KV matches
            # the request's true token stream (rewind point for re-drafting)
            self._draft_valid: Dict[int, int] = {}
            # acceptance accounting for calibration (spec_stats())
            self.spec_iters = 0
            self.spec_row_steps = 0
            self.spec_emitted = 0
            self._spec_pos_proposed = np.zeros((self.spec_k,), np.int64)
            self._spec_pos_accepted = np.zeros((self.spec_k,), np.int64)

            @jax.jit
            def _draft_prefill(params, tokens):
                return steps.prefill_step(params, {"tokens": tokens}, dcfg,
                                          max_len)

            @jax.jit
            def _draft_decode(params, tokens, caches):
                return steps.serve_step(params, tokens, caches, dcfg)

            @jax.jit
            def _verify(params, tokens, q_valid, caches):
                return steps.verify_step(params, tokens, q_valid, caches, cfg)

            self._draft_prefill = _draft_prefill
            self._draft_decode = _draft_decode
            self._verify = _verify
            self._copy_pages = jax.jit(steps.copy_pages)

    def _dev_scope(self):
        """Ambient-device context for this core's array work: a no-op on the
        default device, ``jax.default_device(self.device)`` when the core is
        pinned (disaggregated workers) so freshly created arrays colocate
        with the pool."""
        return (jax.default_device(self.device) if self.device is not None
                else contextlib.nullcontext())

    # ------------------------------------------------------------------
    def _validate_submit(self, prompt: np.ndarray, max_new_tokens: int):
        """Eager admission validation: a prompt must leave room for at least
        one generated token under the stop bound (p + t >= limit - 1), else
        it would only fail deep inside prefill/table maintenance."""
        limit = self._len_limit
        if len(prompt) > limit - 2:
            if self.chunk_size:
                raise ValueError(
                    f"prompt of {len(prompt)} tokens exceeds max_context - 2 "
                    f"= {limit - 2}; raise EngineConfig.max_context")
            raise ValueError(
                f"prompt of {len(prompt)} tokens exceeds max_len - 2 = "
                f"{limit - 2}; enable chunked prefill "
                f"(EngineConfig(chunk_size=..., max_context=...)) to serve "
                f"prompts past max_len")
        need = self.store.blocks_for_tokens(
            min(len(prompt) + max_new_tokens, limit - 1))
        if need > self.num_blocks:
            raise ValueError(
                f"request needs {need} blocks but the pool holds only "
                f"{self.num_blocks}; raise num_blocks or shrink the request")

    def submit(self, prompt: np.ndarray, max_new_tokens: int = 32,
               eos_id: Optional[int] = None) -> EngineRequest:
        prompt = np.asarray(prompt, np.int32)
        self._validate_submit(prompt, max_new_tokens)
        r = EngineRequest(rid=self._next_rid, prompt=prompt,
                          max_new_tokens=max_new_tokens, eos_id=eos_id,
                          submit_time=time.monotonic())
        self._next_rid += 1
        self.waiting.append(r)
        return r

    def enqueue(self, r: EngineRequest):
        """Queue an externally constructed request FIFO-fairly (by rid).
        Disaggregated serving routes requests between workers with this —
        the orchestrator owns rid assignment, so worker-local ``submit`` is
        bypassed."""
        rids = [w.rid for w in self.waiting]
        self.waiting.insert(bisect.bisect_left(rids, r.rid), r)

    # -- block-table row maintenance -----------------------------------
    def _pad_ids(self, blocks: List[int]) -> np.ndarray:
        ids = np.full((self.max_blocks,), self.store.trash_block, np.int32)
        ids[:len(blocks)] = blocks
        return ids

    def _set_row(self, slot: int, blocks: List[int], length: int):
        self._tables_np[slot] = self._pad_ids(blocks)
        self._lengths_np[slot] = length

    def _clear_row(self, slot: int):
        self._tables_np[slot] = self.store.trash_block
        self._lengths_np[slot] = 0

    def _push_rows(self, tables: Optional[np.ndarray] = None,
                   lengths: Optional[np.ndarray] = None):
        """Sync block-table/length rows into every cache group (identical
        across layers — the indirection is per-request). Defaults to the
        host mirrors; mixed iterations push per-pass VIEWS instead (chunk
        rows appear as trash/0 to the decode pass so its structural write
        at position ``length`` can never land in a live page)."""
        tabs = jnp.asarray(self._tables_np if tables is None else tables)
        lens = jnp.asarray(self._lengths_np if lengths is None else lengths)
        for g in self.caches.values():
            L = g["block_tables"].shape[0]
            g["block_tables"] = jnp.broadcast_to(tabs[None], (L, *tabs.shape))
            g["length"] = jnp.broadcast_to(lens[None], (L, *lens.shape))

    def _push_draft_rows(self, tables: Optional[np.ndarray] = None,
                         lengths: Optional[np.ndarray] = None):
        """Same as ``_push_rows`` for the draft model's cache groups."""
        tabs = jnp.asarray(self._draft_tables_np if tables is None else tables)
        lens = jnp.asarray(self._draft_lengths_np if lengths is None
                           else lengths)
        for g in self.draft_caches.values():
            L = g["block_tables"].shape[0]
            g["block_tables"] = jnp.broadcast_to(tabs[None], (L, *tabs.shape))
            g["length"] = jnp.broadcast_to(lens[None], (L, *lens.shape))

    # -- admission ------------------------------------------------------
    def _resume_ctx(self, r: EngineRequest) -> np.ndarray:
        """Context a (re-)admission must cover in KV: the prompt plus every
        token generated so far but the last — the cache then spans positions
        [0, p + t - 1) and decode continues by feeding tokens[-1]. Nothing
        generated is lost."""
        return np.concatenate([r.prompt, np.asarray(r.tokens[:-1], np.int32)]) \
            if r.tokens else r.prompt

    def _place(self, slot: int, r: EngineRequest):
        """Admission tail shared by every path (including the decode
        worker's page-import path): bind request to slot, stamp the admit
        order, (re-)prefill the draft model when speculating."""
        r.slot = slot
        r.state = "running"
        self._admit_order[r.rid] = self._admit_seq
        self._admit_seq += 1
        self.active[slot] = r
        if self.spec:
            self._admit_draft(r)

    def _admit_one(self, slot: int, r: EngineRequest) -> bool:
        """Try to place ``r`` in ``slot``; False when KV capacity blocks it
        (head-of-line: the caller stops admitting, keeping FIFO order)."""
        if r.state == "swapped":
            blocks = self.store.swap_in(r.rid)
            if blocks is None:
                return False
            t = self.store.tables[r.rid]
            ids = jnp.asarray(np.asarray(blocks, np.int32))
            self.caches = self._scatter_pages(
                self.caches,
                jax.device_put(t.host_pages, self.device), ids)
            t.host_pages = None
            self._set_row(slot, blocks, t.tokens)
            # mid-prefill swap victims resume chunking where the fill front
            # stopped; mid-decode victims have prefilled == len(ctx)
            r.ctx = self._resume_ctx(r)
            r.prefilled = t.tokens
        elif self.chunk_size:
            # chunked admission: reserve KV for the FIRST chunk only (plus
            # any resident matched prefix — free dedup); the mixed step
            # prefills chunk by chunk, growing the table at the fill front.
            # No forward pass happens here, so admission never stalls
            # running decodes.
            ctx = self._resume_ctx(r)
            chain = prefix_chain(r.prompt, self.block_tokens)
            got = self.store.allocate(r.rid, min(self.chunk_size, len(ctx)),
                                      chain, filled=0,
                                      context_tokens=len(ctx))
            if got is None:
                return False
            blocks, _ = got
            r.ctx = ctx
            r.prefilled = 0
            self._set_row(slot, blocks, 0)
        else:
            ctx = self._resume_ctx(r)
            chain = prefix_chain(r.prompt, self.block_tokens)
            got = self.store.allocate(r.rid, len(ctx), chain)
            if got is None:
                return False
            blocks, _ = got
            logits, dense = self._prefill_one(self.params, ctx[None, :])
            ids = jnp.asarray(self._pad_ids(blocks))
            # matched prefix blocks are rewritten with bit-identical content
            # (same tokens at same positions => same K/V); only the table
            # aliasing dedups memory, not the prefill compute
            self.caches = self._write_prefill(self.caches, dense, ids)
            if r.state == "new":
                tok = int(jnp.argmax(logits, -1)[0])
                if self.keep_logits:
                    r.first_logits = r.last_logits = np.asarray(logits[0])
                r.first_token_time = time.monotonic()
                r.tokens.append(tok)
                r.token_times.append(r.first_token_time)
            self._set_row(slot, blocks, len(ctx))
            r.ctx = ctx
            r.prefilled = len(ctx)
        self._place(slot, r)
        return True

    def _admit_draft(self, r: EngineRequest):
        """(Re-)prefill the DRAFT model over ``r``'s resume context. Runs at
        every admission path — fresh, recompute resume, swap-in — because
        draft KV is never swapped: it is dropped at preemption and rebuilt
        here (a small-model prefill is cheaper than round-tripping its
        pages, and it keeps host memory accounting target-only)."""
        ctx = r.ctx
        got = self.draft_store.allocate(r.rid, len(ctx), ())
        assert got is not None, "draft pool is sized to never run out"
        blocks, _ = got
        _, dense = self._draft_prefill(self.draft_params,
                                       jnp.asarray(ctx[None, :]))
        dids = np.full((self.max_blocks,), self.draft_store.trash_block,
                       np.int32)
        dids[:len(blocks)] = blocks
        self.draft_caches = self._write_prefill(self.draft_caches, dense,
                                                jnp.asarray(dids))
        self._draft_tables_np[r.slot] = dids
        self._draft_lengths_np[r.slot] = len(ctx)
        self._draft_valid[r.rid] = len(ctx)

    def _admit(self):
        for slot in range(self.max_batch):
            if self.active[slot] is not None or not self.waiting:
                continue
            if not self._admit_one(slot, self.waiting[0]):
                break
            self.waiting.pop(0)

    # -- preemption -----------------------------------------------------
    def preempt_slot(self, slot: int, policy: Optional[str] = None):
        """Evict the request in ``slot`` and requeue it FIFO-fairly (ordered
        by original submit rid, not pushed to the queue head). ``swap``
        moves its pages to host memory; ``recompute`` drops them. Either
        way the tokens generated so far are kept."""
        r = self.active[slot]
        if r is None:
            return
        policy = policy or self.preemption
        rid = r.rid
        if self.spec:
            # a mid-step victim may hold a speculative fork: roll the target
            # table back to its committed base before swap/drop, and drop the
            # draft KV outright (rebuilt by _admit_draft on resume)
            if rid in self.store.forks:
                self.store.abort_fork(rid)
            if rid in self.draft_store.tables:
                self.draft_store.free(rid)
            self._draft_valid.pop(rid, None)
            self._draft_tables_np[slot] = self.draft_store.trash_block
            self._draft_lengths_np[slot] = 0
        if policy == "swap":
            blocks = self.store.swap_out(rid)
            if blocks is None:                 # shared pages: degrade
                policy = "recompute"
            else:
                # gather exactly the victim's pages (not the trash-padded
                # table): host memory and the device->host transfer scale
                # with the request, not with max_blocks
                ids = jnp.asarray(np.asarray(blocks, np.int32))
                pages = self._gather_pages(self.caches, ids)
                self.store.tables[rid].host_pages = jax.device_get(pages)
                r.state = "swapped"
        if policy == "recompute":
            self.store.drop(rid)
            r.state = "preempted"
        r.preemptions += 1
        self.active[slot] = None
        r.slot = None
        self._clear_row(slot)
        self.enqueue(r)

    def _make_room(self, for_rid: int) -> bool:
        """Free blocks by preempting the most-recently-admitted other active
        request (the simulator's coldest-victim rule)."""
        victims = [r for r in self.active
                   if r is not None and r.rid != for_rid]
        if not victims:
            return False
        v = max(victims, key=lambda r: self._admit_order[r.rid])
        self.preempt_slot(v.slot)
        return True

    # -- decode ---------------------------------------------------------
    def _is_decoding(self, r: EngineRequest) -> bool:
        """Decode-phase rows have their whole context in KV; chunk-phase
        rows are still filling it (chunked mode only)."""
        return r.prefilled >= len(r.ctx)

    def _grow_active(self):
        """Fault in pages so every active DECODE row's table covers the KV
        slot its next decode write lands in; exhaustion preempts victims."""
        for slot in range(self.max_batch):
            r = self.active[slot]      # re-read: _make_room may evict slots
            if r is None or not self._is_decoding(r) \
                    or not self.store.needs_block(r.rid):
                continue
            while True:
                b = self.store.grow(r.rid)
                if b is not None:
                    self._tables_np[r.slot,
                                    len(self.store.tables[r.rid].blocks) - 1] = b
                    break
                if not self._make_room(r.rid):
                    raise RuntimeError(
                        "KV pool exhausted with no preemptable victim")

    def _grow_to(self, r: EngineRequest, target_tokens: int):
        """Fault pages until ``r``'s table covers ``target_tokens`` KV slots
        (chunk-phase growth at the fill front); exhaustion preempts victims
        — never ``r`` itself."""
        t = self.store.tables[r.rid]
        while len(t.blocks) * self.block_tokens < target_tokens:
            b = self.store.grow(r.rid)
            if b is not None:
                self._tables_np[r.slot, len(t.blocks) - 1] = b
                continue
            if not self._make_room(r.rid):
                raise RuntimeError(
                    "KV pool exhausted with no preemptable victim")

    def _finish(self, r: EngineRequest, now: float):
        r.finish_time = now
        r.state = "done"
        if self.spec:
            if r.rid in self.draft_store.tables:
                self.draft_store.free(r.rid)
            self._draft_valid.pop(r.rid, None)
            self._draft_tables_np[r.slot] = self.draft_store.trash_block
            self._draft_lengths_np[r.slot] = 0
        self.store.free(r.rid)
        del self._admit_order[r.rid]       # rids never reuse: don't leak
        self.finished.append(r)
        self.active[r.slot] = None
        self._clear_row(r.slot)
        r.slot = None

    def _trace_step(self):
        self.steps += 1
        if self.trace_occupancy:
            st = self.store
            self.occupancy.append({
                "step": self.steps, "used_blocks": st.used_blocks,
                "free_blocks": st.free_blocks,
                "cached_blocks": st.cached_blocks,
                "active": sum(a is not None for a in self.active),
            })

    def _decode_bookkeeping(self, new_tok: np.ndarray, logits):
        """Per-row accounting after a decode pass: stream the token, advance
        the store, finish rows that hit a stop condition."""
        now = time.monotonic()
        if self.keep_logits:
            logits = np.asarray(logits)
        for s, r in enumerate(self.active):
            if r is None or not self._is_decoding(r):
                continue
            self.store.advance(r.rid)
            self._lengths_np[s] = min(self._lengths_np[s] + 1,
                                      self._len_limit - 1)
            t = int(new_tok[s])
            r.tokens.append(t)
            r.token_times.append(now)
            if self.keep_logits:
                r.last_logits = logits[s]
            done = (len(r.tokens) >= r.max_new_tokens
                    or (r.eos_id is not None and t == r.eos_id)
                    or len(r.prompt) + len(r.tokens) >= self._len_limit - 1)
            if done:
                self._finish(r, now)

    def _decode_pass(self):
        """One ``(b, 1)`` decode pass over the decode-phase rows, with
        chunk-phase rows viewed as trash/0 so the pass's structural KV write
        at position ``length`` can never land in a live page. No-op when no
        row is decode-phase."""
        dec = [r for r in self.active
               if r is not None and self._is_decoding(r)]
        if not dec:
            return
        tabs = self._tables_np.copy()
        lens = self._lengths_np.copy()
        for r in self.active:
            if r is not None and not self._is_decoding(r):
                tabs[r.slot] = self.store.trash_block
                lens[r.slot] = 0
        last = np.zeros((self.max_batch, 1), np.int32)
        for r in dec:
            last[r.slot, 0] = r.tokens[-1]
        self._push_rows(tabs, lens)
        new_tok, logits, self.caches = self._decode(
            self.params, jnp.asarray(last), self.caches)
        self._decode_bookkeeping(np.asarray(new_tok), logits)

    # -- chunked prefill pass -------------------------------------------
    def _chunk_budget(self, n_dec: int) -> int:
        """Chunk tokens this iteration may spend, after the decode
        reservation (the TTFT-vs-ITL split of the token budget)."""
        budget = self.config.token_budget or (self.max_batch + self.chunk_size)
        if n_dec == 0:
            return max(budget, 1)
        reserved = max(n_dec,
                       int(np.ceil(self.config.decode_share * budget)))
        return max(0, budget - reserved)

    def _chunk_pass(self):
        """One ``(b, chunk_size)`` chunked-prefill pass advancing each
        chunk-phase row's fill front by up to ``chunk_size`` tokens within
        the iteration's token budget. A prompt completing its last chunk
        streams its first token from that pass (bit-identical to whole
        prefill's last-position logits).

        Chunk scheduling: admit-order fairness, shared token budget.
        ``_grow_to`` may preempt victims (most-recently-admitted), including
        rows already scheduled this pass — takes are re-validated after."""
        chunkers = sorted(
            (r for r in self.active
             if r is not None and not self._is_decoding(r)),
            key=lambda r: self._admit_order[r.rid])
        budget = self._chunk_budget(sum(1 for r in self.active
                                        if r is not None
                                        and self._is_decoding(r)))
        takes: Dict[int, int] = {}
        for r in chunkers:
            if r.slot is None or self.active[r.slot] is not r:
                continue                       # evicted by a peer's growth
            take = min(self.chunk_size, len(r.ctx) - r.prefilled, budget)
            if take <= 0:
                continue
            self._grow_to(r, r.prefilled + take)
            takes[r.rid] = take
            budget -= take
        alive = {r.rid for r in self.active if r is not None}
        takes = {rid: tk for rid, tk in takes.items() if rid in alive}
        if takes:
            toks = np.zeros((self.max_batch, self.chunk_size), np.int32)
            q_valid = np.zeros((self.max_batch,), np.int32)
            rows = [r for r in self.active
                    if r is not None and r.rid in takes]
            for r in rows:
                tk = takes[r.rid]
                toks[r.slot, :tk] = r.ctx[r.prefilled:r.prefilled + tk]
                q_valid[r.slot] = tk
            self._push_rows()                  # real tables for every row
            new_tok, logits, self.caches = self._chunk(
                self.params, jnp.asarray(toks), jnp.asarray(q_valid),
                self.caches)
            new_tok = np.asarray(new_tok)
            now = time.monotonic()
            for r in rows:
                tk = takes[r.rid]
                self.store.advance(r.rid, tk)
                r.prefilled += tk
                self._lengths_np[r.slot] = r.prefilled
                if r.prefilled == len(r.ctx) and not r.tokens:
                    # prompt complete: stream the first token (resumes keep
                    # their stream and re-enter decode by feeding tokens[-1])
                    tok = int(new_tok[r.slot])
                    if self.keep_logits:
                        r.first_logits = r.last_logits = np.asarray(
                            logits[r.slot])
                    r.first_token_time = now
                    r.tokens.append(tok)
                    r.token_times.append(now)
        self._trace_step()

    def kv_stats(self) -> Dict[str, float]:
        return self.store.stats()


class Engine(EngineCore):
    """Continuous-batching engine over paged KV: every serving pass on one
    device (see module docstring). ``engine/workers.py`` builds the
    disaggregated prefill/decode split from the same ``EngineCore``."""

    def _step_decode(self):
        """Legacy whole-prefill iteration: one (b, 1) decode pass."""
        self._grow_active()
        self._decode_pass()
        self._trace_step()

    def _step_mixed(self):
        """One mixed iteration: (a) the decode pass for decode-phase rows —
        identical in shape and numerics to the legacy iteration — then (b)
        the chunked-prefill pass for chunk-phase rows, sharing the
        iteration's token budget."""
        self._grow_active()
        self._decode_pass()
        self._chunk_pass()

    # -- speculative iteration (draft k, verify in one target pass) -----
    def _step_spec(self):
        """One speculative iteration over the active (decode-phase) rows:

        1. DRAFT — rewind each row's draft cache to its last
           stream-consistent position, catch it up on the true stream, then
           roll the draft forward for up to ``k_eff`` greedy tokens (batched
           ``(b, 1)`` passes; rows done drafting sit out as trash/0).
        2. FORK — COW-fork each row's target block table
           (``PagedKVStore.fork_table``) so the verify pass may write KV at
           positions ``L .. L + k_eff`` without touching committed pages;
           capacity faults preempt peers exactly like ``_grow_active``.
        3. VERIFY — one ``(b, spec_k + 1)`` target pass feeds the last
           committed token plus the draft tokens; ``greedy[:, j]`` is
           bit-identical to what sequential decode would emit at that
           position (``paged_verify_attention`` contract).
        4. ACCEPT — per row, emit greedy tokens while they confirm the
           draft, plus the bonus token, applying the stop conditions
           token-by-token; ``commit_fork`` keeps KV for what was emitted and
           rolls back the rest.

        Streams are bit-identical to ``_step_decode`` because verify
        reproduces sequential numerics exactly and acceptance only decides
        how MANY of those tokens commit per pass (1..k_eff+1, never 0)."""
        live = [r for r in self.active if r is not None]
        limit = self._len_limit
        k_eff: Dict[int, int] = {}
        for r in live:
            # k_eff caps so the verify feed never proposes past the stop
            # bounds: at most max_new - 1 further tokens ride behind the
            # guaranteed bonus token, and writes stay inside the table
            L = int(self._lengths_np[r.slot])
            k_eff[r.rid] = max(0, min(self.spec_k,
                                      r.max_new_tokens - len(r.tokens) - 1,
                                      limit - 1 - L))

        # -- 1. draft phase --------------------------------------------
        drafts: Dict[int, List[int]] = {r.rid: [] for r in live}
        queues: Dict[int, List[int]] = {}
        part = [r for r in live if k_eff[r.rid] > 0]
        for r in part:
            dv = self._draft_valid[r.rid]
            L = int(self._lengths_np[r.slot])
            stream = np.concatenate([r.ctx, np.asarray(r.tokens, np.int32)])
            # feeding stream[dv..L] rewrites draft KV at positions dv..L
            # (overwriting any rejected-draft garbage) and the LAST feed's
            # output is the first draft token
            queues[r.rid] = [int(t) for t in stream[dv:L + 1]]
            self._draft_lengths_np[r.slot] = dv
        while part:
            feed = np.zeros((self.max_batch, 1), np.int32)
            tabs = np.full_like(self._draft_tables_np,
                                self.draft_store.trash_block)
            lens = np.zeros_like(self._draft_lengths_np)
            for r in part:
                q = queues[r.rid]
                feed[r.slot, 0] = q.pop(0) if q else drafts[r.rid][-1]
                D = int(self._draft_lengths_np[r.slot])
                dt = self.draft_store.tables[r.rid]
                while len(dt.blocks) * self.block_tokens <= D:
                    b = self.draft_store.grow(r.rid)
                    assert b is not None, "draft pool sized to never run out"
                    self._draft_tables_np[r.slot, len(dt.blocks) - 1] = b
                tabs[r.slot] = self._draft_tables_np[r.slot]
                lens[r.slot] = D
            self._push_draft_rows(tabs, lens)
            out, _, self.draft_caches = self._draft_decode(
                self.draft_params, jnp.asarray(feed), self.draft_caches)
            out = np.asarray(out)
            nxt = []
            for r in part:
                D = int(self._draft_lengths_np[r.slot])
                dt = self.draft_store.tables[r.rid]
                if D + 1 > dt.tokens:      # store tracks the high-water mark
                    self.draft_store.advance(r.rid, D + 1 - dt.tokens)
                self._draft_lengths_np[r.slot] = D + 1
                if not queues[r.rid]:
                    drafts[r.rid].append(int(out[r.slot]))
                if queues[r.rid] or len(drafts[r.rid]) < k_eff[r.rid]:
                    nxt.append(r)
            part = nxt

        # -- 2. fork target tables -------------------------------------
        for r in live:
            if r.slot is None or self.active[r.slot] is not r:
                continue                   # evicted by a peer's fork below
            while True:
                f = self.store.fork_table(r.rid, k_eff[r.rid] + 1)
                if f is not None:
                    break
                if not self._make_room(r.rid):
                    raise RuntimeError(
                        "KV pool exhausted with no preemptable victim")
            self._tables_np[r.slot] = self._pad_ids(
                self.store.tables[r.rid].blocks)
            if f.cow:
                # device-copy the COW'd pages so the fork's private copies
                # hold the shared prefix content the verify pass reads
                src = jnp.asarray(np.asarray([o for _, o, _ in f.cow],
                                             np.int32))
                dst = jnp.asarray(np.asarray([n for _, _, n in f.cow],
                                             np.int32))
                self.caches = self._copy_pages(self.caches, src, dst)

        # -- 3. verify pass --------------------------------------------
        live = [r for r in live
                if r.slot is not None and self.active[r.slot] is r]
        if not live:
            self._trace_step()
            return
        toks = np.zeros((self.max_batch, self.spec_k + 1), np.int32)
        q_valid = np.zeros((self.max_batch,), np.int32)
        for r in live:
            k = k_eff[r.rid]
            toks[r.slot, 0] = r.tokens[-1]
            toks[r.slot, 1:1 + k] = drafts[r.rid][:k]
            q_valid[r.slot] = k + 1
        self._push_rows()
        greedy, _, self.caches = self._verify(
            self.params, jnp.asarray(toks), jnp.asarray(q_valid), self.caches)
        greedy = np.asarray(greedy)

        # -- 4. accept, emit, commit -----------------------------------
        now = time.monotonic()
        for r in live:
            k = k_eff[r.rid]
            d = drafts[r.rid]
            a = 0
            while a < k and d[a] == int(greedy[r.slot, a]):
                a += 1
            self._spec_pos_proposed[:k] += 1
            self._spec_pos_accepted[:a] += 1
            L = int(self._lengths_np[r.slot])
            m, done = 0, False
            for j in range(a + 1):
                t = int(greedy[r.slot, j])
                r.tokens.append(t)
                r.token_times.append(now)
                m += 1
                if (len(r.tokens) >= r.max_new_tokens
                        or (r.eos_id is not None and t == r.eos_id)
                        or len(r.prompt) + len(r.tokens) >= limit - 1):
                    done = True
                    break
            self.store.commit_fork(r.rid, m)
            self._tables_np[r.slot] = self._pad_ids(
                self.store.tables[r.rid].blocks)
            self._lengths_np[r.slot] = min(L + m, limit - 1)
            self.spec_emitted += m
            self.spec_row_steps += 1
            if done:
                self._finish(r, now)
            elif k:
                # draft KV is valid through the accepted prefix (positions
                # L+1..L+min(k-1, a, m) hold confirmed draft tokens), capped
                # at L+m so the next catch-up re-feeds at least the newest
                # token
                self._draft_valid[r.rid] = min(L + m,
                                               L + 1 + min(k - 1, a, m))
        self.spec_iters += 1
        self._trace_step()

    def spec_stats(self) -> Dict[str, object]:
        """Acceptance telemetry for calibration: the measured per-position
        CONDITIONAL acceptance distribution feeds
        ``perfmodel.speculative_decode_step`` and the simulator's SPEC_DECODE
        pricing instead of an assumed geometric alpha
        (``benchmarks/spec_decode.py`` closes the loop).

        ``acceptance_per_position[i]`` is the *marginal* P(draft positions
        0..i all accepted) — acceptance stops at the first rejection, so the
        raw accepted/proposed ratio is already a cumulative product.
        ``conditional_acceptance_per_position[i]`` divides out the previous
        position's marginal to recover P(accept i | accepted 0..i-1) — the
        alpha_i sequence ``expected_accepted_tokens`` compounds."""
        prop = self._spec_pos_proposed
        acc = self._spec_pos_accepted
        marginal = [float(a) / p if p else 0.0 for a, p in zip(acc, prop)]
        cond, prev = [], 1.0
        for m in marginal:
            cond.append(min(1.0, m / prev) if prev > 0 else 0.0)
            prev = m
        return {
            "spec_k": self.spec_k,
            "iterations": self.spec_iters,
            "row_steps": self.spec_row_steps,
            "emitted": self.spec_emitted,
            # mean tokens a row commits per target pass it takes part in —
            # the direct analogue of 1.0 for plain decode
            "tokens_per_step": (self.spec_emitted / self.spec_row_steps
                                if self.spec_row_steps else 0.0),
            "proposed_per_position": [int(x) for x in prop],
            "accepted_per_position": [int(x) for x in acc],
            "acceptance_per_position": marginal,
            "conditional_acceptance_per_position": cond,
        }

    def run(self, max_steps: int = 100_000) -> List[EngineRequest]:
        if self.spec:
            step = self._step_spec
        else:
            step = self._step_mixed if self.chunk_size else self._step_decode
        while (self.waiting or any(a is not None for a in self.active)) \
                and self.steps < max_steps:
            self._admit()
            if any(a is not None for a in self.active):
                step()
        return self.finished


def paged_supported(cfg: ModelConfig) -> bool:
    """Can this config serve through the paged ``Engine``? Paging covers
    attention KV only: MLA's latent cache and hybrid/ssm recurrent state are
    not paged yet (see ROADMAP open items)."""
    return (cfg.family in ("dense", "vlm", "audio", "moe")
            and cfg.attn_type != "mla")


def make_engine(cfg: ModelConfig, **kw):
    """Engine factory: the paged ``Engine`` when the config supports paged
    attention caches, else the dense ``SlotEngine`` (which serves every
    decode-capable family). Paged-only kwargs are dropped for the dense
    fallback."""
    if paged_supported(cfg):
        return Engine(cfg, **kw)
    for k in ("block_tokens", "num_blocks", "preemption", "trace_occupancy",
              "config", "draft_params", "device", "keep_logits"):
        kw.pop(k, None)
    return SlotEngine(cfg, **kw)


# ---------------------------------------------------------------------------
# dense slot engine (the parity oracle)
# ---------------------------------------------------------------------------

class SlotEngine:
    """The original dense-KV engine: one contiguous ``(max_len, kvh, hd)``
    cache row per decode slot, no paging. Kept as the bit-exactness oracle
    for the paged ``Engine`` (same admission policy, same greedy decode, so
    token streams must match) and as the simplest reference driver. Its
    preemption keeps the seed behavior — it *discards* progress past the
    first streamed token — which is exactly the deficiency the paged engine
    removes; don't use it for preemption studies."""

    def __init__(self, cfg: ModelConfig, params=None, max_batch: int = 4,
                 max_len: int = 512, seed: int = 0):
        self.cfg = cfg
        self.max_batch = max_batch
        self.max_len = max_len
        if params is None:
            params = tf.init_params(cfg, jax.random.PRNGKey(seed))
        self.params = params
        self.caches = tf.init_cache(cfg, max_batch, max_len)
        self.active = [None] * max_batch        # slot -> EngineRequest
        self.waiting: List[EngineRequest] = []
        self.finished: List[EngineRequest] = []
        self.steps = 0
        self._next_rid = 0

        @jax.jit
        def _prefill_one(params, tokens):
            return steps.prefill_step(params, {"tokens": tokens}, cfg, max_len)

        @jax.jit
        def _decode(params, tokens, caches):
            return steps.serve_step(params, tokens, caches, cfg)

        self._prefill_one = _prefill_one
        self._decode = _decode

    # ------------------------------------------------------------------
    def submit(self, prompt: np.ndarray, max_new_tokens: int = 32,
               eos_id: Optional[int] = None) -> EngineRequest:
        r = EngineRequest(rid=self._next_rid,
                          prompt=np.asarray(prompt, np.int32),
                          max_new_tokens=max_new_tokens, eos_id=eos_id,
                          submit_time=time.monotonic())
        self._next_rid += 1
        self.waiting.append(r)
        return r

    def _write_slot(self, slot: int, req_cache):
        """Copy a single-request cache into batch slot ``slot``."""
        def put(full, one):
            return full.at[:, slot].set(one[:, 0].astype(full.dtype)) \
                if full.ndim >= 2 else full
        self.caches = jax.tree.map(put, self.caches, req_cache)

    def _admit(self):
        for slot in range(self.max_batch):
            if self.active[slot] is not None or not self.waiting:
                continue
            r = self.waiting.pop(0)
            logits, cache1 = self._prefill_one(self.params, r.prompt[None, :])
            tok = int(jnp.argmax(logits, -1)[0])
            now = time.monotonic()
            r.first_token_time = now
            r.tokens.append(tok)
            r.token_times.append(now)
            r.slot = slot
            self._write_slot(slot, cache1)
            self.active[slot] = r

    def _step_decode(self):
        last = np.zeros((self.max_batch, 1), np.int32)
        for s, r in enumerate(self.active):
            if r is not None:
                last[s, 0] = r.tokens[-1]
        new_tok, _, self.caches = self._decode(self.params,
                                               jnp.asarray(last), self.caches)
        new_tok = np.asarray(new_tok)
        now = time.monotonic()
        for s, r in enumerate(self.active):
            if r is None:
                continue
            t = int(new_tok[s])
            r.tokens.append(t)
            r.token_times.append(now)
            done = (len(r.tokens) >= r.max_new_tokens
                    or (r.eos_id is not None and t == r.eos_id)
                    or len(r.prompt) + len(r.tokens) >= self.max_len - 1)
            if done:
                r.finish_time = now
                self.finished.append(r)
                self.active[s] = None
        self.steps += 1

    def run(self, max_steps: int = 100_000) -> List[EngineRequest]:
        while (self.waiting or any(a is not None for a in self.active)) \
                and self.steps < max_steps:
            self._admit()
            if any(a is not None for a in self.active):
                self._step_decode()
        return self.finished

    # --- fault tolerance: preempt & requeue (client-failure analogue) ----
    def preempt_slot(self, slot: int):
        r = self.active[slot]
        if r is None:
            return
        r.tokens = r.tokens[:1]           # keep the streamed first token
        r.token_times = r.token_times[:1]
        self.active[slot] = None
        self.waiting.insert(0, r)
