"""Chunked prefill & continuous batching: bit-exact parity of the mixed-
iteration engine against whole-prefill oracles.

Three layers of evidence, mirroring the engine's layering:

* Model layer — a prompt prefilled chunk-by-chunk through the paged pool
  (``mode="chunk"`` / ``gqa_prefill_paged``) must produce bitwise-identical
  last-position logits AND pool K/V to a single whole-prompt prefill.
* Store layer — chunked allocation (first-chunk reservation + fill-front
  growth + mid-chunk swap with tail trim) keeps every PagedKVStore
  invariant, and its prefix/accounting counters equal the whole-prompt
  path's when unpressured.
* Engine layer — greedy token streams from the chunked ``Engine`` equal the
  dense ``SlotEngine`` oracle across chunk size x prompt length x prefix
  sharing x preemption (swap and recompute, including mid-chunk), and a
  prompt far beyond ``max_len`` completes bit-identically against an oracle
  sized to ``max_context`` while the whole-prefill engine rejects it
  eagerly.
"""
import jax
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import get_reduced_config
from repro.engine.paged_kv import PagedKVStore, prefix_chain
from repro.engine.runner import Engine, EngineConfig, SlotEngine
from repro.models import steps
from repro.models import transformer as tf

MAX_LEN = 96
BT = 16


@pytest.fixture(scope="module")
def cfg():
    return get_reduced_config("gemma_2b")


@pytest.fixture(scope="module")
def params(cfg):
    # live output projections: under the training init every block is the
    # identity, so streams would not depend on the KV cache at all
    p, _ = tf.init_model(cfg, jax.random.PRNGKey(0), zero_out=False)
    return p


# oracle streams are deterministic: cache them across hypothesis examples so
# repeated prompt sets don't re-run (and re-jit) the dense engine
_ORACLE: dict = {}


def _oracle_streams(cfg, params, prompts, max_new, max_len=MAX_LEN):
    key = (tuple(tuple(p.tolist()) for p in prompts), max_new, max_len)
    if key not in _ORACLE:
        slot = SlotEngine(cfg, params=params, max_batch=2, max_len=max_len)
        for p in prompts:
            slot.submit(p, max_new_tokens=max_new)
        _ORACLE[key] = {tuple(r.prompt.tolist()): list(r.tokens)
                        for r in slot.run()}
    return _ORACLE[key]


# ---------------------------------------------------------------------------
# model layer: chunked == whole prefill, bitwise
# ---------------------------------------------------------------------------

def test_chunk_passes_match_whole_prefill_bitwise(cfg, params):
    """Drive chunk_step manually over a paged cache and compare against one
    whole-prompt prefill: last-position logits and every written K/V slot
    must be bit-identical (the foundation the engine parity rests on)."""
    rng = np.random.default_rng(0)
    P = 40
    prompt = rng.integers(1, cfg.vocab_size, P).astype(np.int32)
    logits_w, dense = steps.prefill_step(
        params, {"tokens": jax.numpy.asarray(prompt[None])}, cfg, MAX_LEN)
    logits_w = np.asarray(logits_w)
    mb, num_blocks = MAX_LEN // BT, 2 * (MAX_LEN // BT)
    for chunk in (8, 13, 40):                  # unaligned + whole-in-one
        caches = tf.init_paged_cache(cfg, 2, num_blocks, BT, mb)
        tables = np.full((2, mb), num_blocks, np.int32)
        tables[0] = np.arange(mb)
        for g in caches.values():
            L = g["block_tables"].shape[0]
            g["block_tables"] = jax.numpy.broadcast_to(
                jax.numpy.asarray(tables)[None], (L, 2, mb))
        got = 0
        while got < P:
            take = min(chunk, P - got)
            toks = np.zeros((2, chunk), np.int32)
            toks[0, :take] = prompt[got:got + take]
            qv = np.array([take, 0], np.int32)
            _, logits_c, caches = steps.chunk_step(
                params, jax.numpy.asarray(toks), jax.numpy.asarray(qv),
                caches, cfg)
            got += take
        assert np.array_equal(np.asarray(logits_c)[0], logits_w[0]), chunk
        kp = np.asarray(caches["attn"]["k_pool"])
        kd = np.asarray(dense["attn"]["k"])
        kg = kp[:, tables[0]].reshape(kp.shape[0], mb * BT, *kp.shape[3:])
        assert np.array_equal(kg[:, :P], kd[:, 0, :P]), chunk


# ---------------------------------------------------------------------------
# store layer: chunked allocation semantics
# ---------------------------------------------------------------------------

def test_store_chunked_allocate_grow_advance():
    st_ = PagedKVStore(num_blocks=8, block_tokens=4)
    chain = prefix_chain(list(range(16)), 4)       # 4 full blocks
    blocks, m = st_.allocate(0, 4, chain, filled=0, context_tokens=16)
    assert m == 0 and len(blocks) == 1             # first chunk only
    assert st_.tables[0].tokens == 0
    st_.advance(0, 4)                              # chunk 1 written
    for _ in range(3):                             # fill front growth
        b = st_.grow(0)
        assert b is not None
        st_.advance(0, 4)
    assert st_.tables[0].tokens == 16
    assert st_.tables[0].hashes == chain           # registered as it filled
    st_.check_invariants()
    # a second chunked admission of the same prompt aliases all 4 blocks up
    # front (matched prefix claimed to the full context, not just chunk 1)
    blocks2, m2 = st_.allocate(1, 4, chain, filled=0, context_tokens=16)
    assert m2 == 4 and blocks2 == st_.tables[0].blocks
    st_.free(0)
    st_.free(1)
    st_.check_invariants()


def test_store_grow_aliases_chain_registered_after_admission():
    """Concurrent chunked prefills of a shared prefix: the later request's
    fill-front growth must alias blocks the earlier one registered AFTER
    the later one was admitted."""
    st_ = PagedKVStore(num_blocks=8, block_tokens=4)
    chain = prefix_chain(list(range(12)), 4)
    st_.allocate(0, 4, chain, filled=0, context_tokens=12)   # A: chunk 1
    st_.allocate(1, 4, chain[:1], filled=0, context_tokens=12)
    # B admitted seeing only A's first registration; A fills onward
    st_.tables[1].chain = list(chain)              # same prompt, full chain
    st_.advance(0, 4)
    st_.grow(0)
    st_.advance(0, 4)                              # A registered chain[1]
    st_.advance(1, 4)
    b = st_.grow(1)                                # B's fill front at block 1
    assert b == st_.tables[0].blocks[1]            # aliased, not fresh
    assert st_.refcount[b] == 2
    st_.free(0)
    st_.free(1)
    st_.check_invariants()


def test_store_swap_out_trims_unfilled_tail():
    st_ = PagedKVStore(num_blocks=8, block_tokens=4)
    chain = prefix_chain(list(range(16)), 4)
    st_.allocate(0, 4, chain, filled=0, context_tokens=16)
    st_.advance(0, 4)
    st_.grow(0)                                    # reserved ahead of fill
    st_.advance(0, 2)                              # mid-chunk: 6 filled
    st_.grow(0)                                    # one fully unfilled block
    assert len(st_.tables[0].blocks) == 3
    kept = st_.swap_out(0)
    assert kept is not None and len(kept) == 2     # blocks_for(6) == 2
    st_.check_invariants()
    back = st_.swap_in(0)
    assert len(back) == 2 and st_.tables[0].tokens == 6
    st_.free(0)
    st_.check_invariants()


@settings(max_examples=15, deadline=None)
@given(ops=st.lists(st.tuples(st.integers(0, 4), st.integers(1, 30)),
                    min_size=1, max_size=40),
       nb=st.integers(4, 12), bt=st.sampled_from([2, 4]),
       chunk=st.integers(1, 6))
def test_store_invariants_random_walk_chunked(ops, nb, bt, chunk):
    """The allocator random walk of test_paged_engine, rerun through the
    CHUNKED admission path (first-chunk reservation, fill-front growth in
    chunk-sized strides, mid-fill swap with tail trim)."""
    st_ = PagedKVStore(num_blocks=nb, block_tokens=bt)
    live, goal, rid = [], {}, 0
    for op, arg in ops:
        if op == 0:                                # chunked admission
            toks = arg
            chain = prefix_chain(list(range(min(toks, 3 * bt))), bt)
            if st_.allocate(rid, min(chunk * bt, toks), chain, filled=0,
                            context_tokens=toks) is not None:
                live.append(rid)
                goal[rid] = toks
            rid += 1
        elif op == 1 and live:                     # advance the fill front
            r = live[arg % len(live)]
            t = st_.tables[r]
            if t.on_device and t.tokens < goal[r]:
                take = min(chunk, goal[r] - t.tokens)
                ok = True
                while len(t.blocks) * bt < t.tokens + take:
                    if st_.grow(r) is None:
                        ok = False
                        break
                if ok:
                    st_.advance(r, take)
        elif op == 2 and live:                     # free
            st_.free(live.pop(arg % len(live)))
        elif op == 3 and live:                     # swap out (maybe degrade)
            r = live[arg % len(live)]
            if st_.tables[r].on_device:
                if st_.swap_out(r) is None:
                    live.remove(r)
                    st_.drop(r)
        elif op == 4 and live:                     # swap in
            r = live[arg % len(live)]
            if not st_.tables[r].on_device:
                st_.swap_in(r)
        st_.check_invariants()
    for r in live:
        st_.free(r)
    st_.check_invariants()
    assert st_.used_blocks == 0


# ---------------------------------------------------------------------------
# engine layer: stream parity across the scheduling space
# ---------------------------------------------------------------------------

def _prompts(lengths, share, vocab, seed=3):
    rng = np.random.default_rng(seed)
    shared = rng.integers(1, vocab, 2 * BT).astype(np.int32)
    out = []
    for n in lengths:
        body = rng.integers(1, vocab, n).astype(np.int32)
        if share and n > 2 * BT:
            body[:2 * BT] = shared
        out.append(body)
    return out


@settings(max_examples=8, deadline=None)
@given(chunk=st.sampled_from([4, 16, 32, 96]),
       lengths=st.lists(st.sampled_from([12, 33, 50]), min_size=2,
                        max_size=4),
       share=st.booleans(),
       policy=st.sampled_from(["swap", "recompute"]),
       tight=st.booleans())
def test_chunked_stream_parity_sweep(cfg, params, chunk, lengths, share,
                                     policy, tight):
    """chunk size x prompt length x prefix sharing x preemption: greedy
    streams from the chunked engine must be bit-identical to the dense
    whole-prefill oracle. ``tight`` shrinks the pool so growth preempts
    victims mid-stream (and mid-chunk) for real."""
    prompts = _prompts(lengths, share, cfg.vocab_size)
    want = _oracle_streams(cfg, params, prompts, max_new=8)
    nb = 7 if tight else None
    eng = Engine(cfg, params=params, max_batch=2, max_len=MAX_LEN,
                 block_tokens=BT, num_blocks=nb, preemption=policy,
                 config=EngineConfig(chunk_size=chunk))
    for p in prompts:
        eng.submit(p, max_new_tokens=8)
    done = eng.run(max_steps=5000)
    got = {tuple(r.prompt.tolist()): list(r.tokens) for r in done}
    assert got == want
    eng.store.check_invariants()
    assert eng.store.used_blocks == 0              # everything released


def test_mid_chunk_preemption_swap_and_recompute(cfg, params):
    """Preempt a request whose prefill is mid-flight (0 < prefilled < ctx):
    swap must round-trip the partial fill front through host memory,
    recompute must restart it — both without perturbing the stream."""
    rng = np.random.default_rng(21)
    long_p = rng.integers(1, cfg.vocab_size, 60).astype(np.int32)
    want = _oracle_streams(cfg, params, [long_p], max_new=6)
    for policy in ("swap", "recompute"):
        eng = Engine(cfg, params=params, max_batch=2, max_len=MAX_LEN,
                     block_tokens=BT, preemption=policy,
                     config=EngineConfig(chunk_size=12))
        r = eng.submit(long_p, max_new_tokens=6)
        eng._admit()
        eng._step_mixed()
        eng._step_mixed()
        assert r.prefilled == 24                   # mid-prefill, mid-BLOCK
        eng.preempt_slot(r.slot)
        assert r.state == ("swapped" if policy == "swap" else "preempted")
        done = eng.run()
        assert {tuple(q.prompt.tolist()): list(q.tokens)
                for q in done} == want, policy
        assert r.preemptions == 1
        eng.store.check_invariants()


def test_chunked_accounting_matches_whole_path(cfg, params):
    """Unpressured + prefix-shared: the chunked engine's dedup/allocation
    counters must equal the whole-prefill engine's (same prompts, same
    physical sharing — chunking changes the schedule, not the memory
    story), and chunked peak occupancy can only be lower."""
    prompts = _prompts([50, 50, 33, 40], share=True, vocab=cfg.vocab_size)
    stats = {}
    for mode, kw in (("whole", {}),
                     ("chunk", {"config": EngineConfig(chunk_size=16)})):
        eng = Engine(cfg, params=params, max_batch=2, max_len=MAX_LEN,
                     block_tokens=BT, **kw)
        for p in prompts:
            eng.submit(p, max_new_tokens=6)
        eng.run()
        stats[mode] = eng.kv_stats()
        eng.store.check_invariants()
    for k in ("prefix_hit_blocks", "prefix_hit_tokens",
              "blocks_allocated_total"):
        assert stats["chunk"][k] == stats["whole"][k], k
    assert stats["chunk"]["prefix_hit_blocks"] > 0  # sharing actually fired
    assert stats["chunk"]["peak_blocks"] <= stats["whole"]["peak_blocks"]


def test_long_context_prompt_beyond_max_len(cfg, params):
    """A prompt ~3x max_len completes through the chunked engine with
    bit-identical greedy tokens to a dense oracle sized to max_context;
    the whole-prefill engine rejects the same prompt eagerly."""
    rng = np.random.default_rng(7)
    prompt = rng.integers(1, cfg.vocab_size, 300).astype(np.int32)
    want = _oracle_streams(cfg, params, [prompt], max_new=6, max_len=384)
    eng = Engine(cfg, params=params, max_batch=2, max_len=MAX_LEN,
                 block_tokens=BT,
                 config=EngineConfig(chunk_size=32, max_context=384))
    eng.submit(prompt, max_new_tokens=6)
    done = eng.run()
    assert len(done) == 1
    assert list(done[0].tokens) == want[tuple(prompt.tolist())]
    whole = Engine(cfg, params=params, max_batch=2, max_len=MAX_LEN,
                   block_tokens=BT)
    with pytest.raises(ValueError, match="chunked prefill"):
        whole.submit(prompt)


def test_submit_validates_eagerly(cfg, params):
    eng = Engine(cfg, params=params, max_batch=1, max_len=MAX_LEN,
                 block_tokens=BT)
    eng.submit(np.arange(MAX_LEN - 2, dtype=np.int32))     # boundary: fits
    with pytest.raises(ValueError, match="max_len"):
        eng.submit(np.arange(MAX_LEN - 1, dtype=np.int32))
    chunked = Engine(cfg, params=params, max_batch=1, max_len=MAX_LEN,
                     block_tokens=BT,
                     config=EngineConfig(chunk_size=16, max_context=192))
    chunked.submit(np.arange(MAX_LEN + 10, dtype=np.int32))  # past max_len ok
    with pytest.raises(ValueError, match="max_context"):
        chunked.submit(np.arange(191, dtype=np.int32))
    # max_context without chunking is a config error, caught at construction
    with pytest.raises(AssertionError):
        Engine(cfg, params=params, max_batch=1, max_len=MAX_LEN,
               block_tokens=BT, config=EngineConfig(max_context=192))


def test_decode_share_knob_starves_or_feeds_prefill(cfg, params):
    """decode_share is the ITL extreme of the knob: at 1.0 a running decode
    monopolizes the budget and a waiting prompt makes no prefill progress;
    at 0.0 the same iteration advances the prompt by a full chunk."""
    rng = np.random.default_rng(31)
    short = rng.integers(1, cfg.vocab_size, 12).astype(np.int32)
    long_p = rng.integers(1, cfg.vocab_size, 60).astype(np.int32)
    for share, expect_progress in ((1.0, 0), (0.0, 16)):
        eng = Engine(cfg, params=params, max_batch=2, max_len=MAX_LEN,
                     block_tokens=BT,
                     config=EngineConfig(chunk_size=16, decode_share=share))
        a = eng.submit(short, max_new_tokens=30)
        eng._admit()
        while not eng._is_decoding(a):             # finish a's prefill
            eng._step_mixed()
        b = eng.submit(long_p, max_new_tokens=4)
        eng._admit()
        n_tok = len(a.tokens)
        eng._step_mixed()
        assert len(a.tokens) == n_tok + 1          # decode always advances
        assert b.prefilled == expect_progress, share
