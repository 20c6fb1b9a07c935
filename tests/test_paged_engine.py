"""Paged real-execution engine: kernel parity (paged vs dense decode
attention in interpret mode), PagedKVStore allocator semantics, and
paged-Engine-vs-seed-SlotEngine token-stream equality under greedy decoding
— including preemption mid-stream (swap and recompute both keep every
generated token and must not change the stream)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import get_reduced_config
from repro.engine.paged_kv import PagedKVStore, prefix_chain
from repro.engine.runner import Engine, SlotEngine, make_engine
from repro.kernels import ref
from repro.kernels.paged_attention import paged_decode_attention
from repro.models import transformer as tf

KEY = jax.random.PRNGKey(7)


def _pool_case(rnd_key, b, kvh, g, d, dv, bt, mb):
    """Random pool + a permutation block table (every row's pages scattered
    arbitrarily through the pool) + ragged lengths >= 1."""
    n_pages = b * mb + 3
    q = jax.random.normal(jax.random.fold_in(rnd_key, 0), (b, 1, kvh * g, d))
    kp = jax.random.normal(jax.random.fold_in(rnd_key, 1), (n_pages, bt, kvh, d))
    vp = jax.random.normal(jax.random.fold_in(rnd_key, 2), (n_pages, bt, kvh, dv))
    tab = jax.random.permutation(jax.random.fold_in(rnd_key, 3),
                                 n_pages)[:b * mb].reshape(b, mb)
    lens = jax.random.randint(jax.random.fold_in(rnd_key, 4), (b,), 1,
                              mb * bt + 1)
    return q, kp, vp, tab.astype(jnp.int32), lens.astype(jnp.int32)


# ---------------------------------------------------------------------------
# kernel parity: paged (interpret) vs dense oracle
# ---------------------------------------------------------------------------

@settings(max_examples=12, deadline=None)
@given(b=st.integers(1, 3), kvh=st.sampled_from([1, 2]),
       g=st.sampled_from([1, 2, 4]), d=st.sampled_from([16, 32, 64]),
       bt=st.sampled_from([8, 16, 32]), mb=st.integers(1, 6),
       seed=st.integers(0, 2 ** 16))
def test_paged_kernel_matches_dense_ref(b, kvh, g, d, bt, mb, seed):
    """Hypothesis sweep over (batch, lengths, block_tokens, table layout):
    the Pallas paged kernel (interpret mode) must match the dense jnp oracle
    evaluated on the gathered logical cache to fp32 tolerance."""
    q, kp, vp, tab, lens = _pool_case(jax.random.fold_in(KEY, seed),
                                      b, kvh, g, d, d, bt, mb)
    out = paged_decode_attention(q, kp, vp, tab, lens, interpret=True)
    dense_k = ref.gather_paged_kv(kp, tab)
    dense_v = ref.gather_paged_kv(vp, tab)
    want = ref.decode_attention(q, dense_k, dense_v, lens)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("kvh", [1, 4])
@pytest.mark.parametrize("verify", [False, True])
def test_paged_kernels_lane_width_heads(kvh, verify):
    """Head dims of 128 lanes, as the chip runs them, over one kv head (MQA)
    and several (GQA): each (page, kv head) tile of the head-sliced pool
    view must reach its own query group."""
    from repro.kernels.paged_attention import paged_verify_attention
    q, kp, vp, tab, lens = _pool_case(jax.random.fold_in(KEY, 40 + kvh),
                                      3, kvh, 2, 128, 128, 16, 4)
    if verify:
        q = jnp.concatenate([q, 0.5 * q, -q], axis=1)    # s = 3 positions
        lens = jnp.minimum(lens, 4 * 16 - 3)
        out = paged_verify_attention(q, kp, vp, tab, lens, interpret=True)
        want = ref.paged_verify_attention(q, kp, vp, tab, lens)
    else:
        out = paged_decode_attention(q, kp, vp, tab, lens, interpret=True)
        want = ref.paged_decode_attention(q, kp, vp, tab, lens)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)


def test_paged_kernel_asymmetric_dv():
    q, kp, vp, tab, lens = _pool_case(jax.random.fold_in(KEY, 99),
                                      2, 2, 2, 32, 16, 8, 4)
    out = paged_decode_attention(q, kp, vp, tab, lens, interpret=True)
    want = ref.paged_decode_attention(q, kp, vp, tab, lens)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)


def test_paged_ref_ignores_dead_table_entries():
    """Garbage in pages referenced only by masked (beyond-length) table
    entries must not leak into the output — the trash-page contract."""
    q, kp, vp, tab, lens = _pool_case(jax.random.fold_in(KEY, 5),
                                      2, 1, 4, 32, 32, 8, 4)
    lens = jnp.array([9, 17], jnp.int32)          # partial coverage
    out1 = ref.paged_decode_attention(q, kp, vp, tab, lens)
    # scribble every page, then restore only the live slots' content
    live_k = ref.gather_paged_kv(kp, tab)
    live_v = ref.gather_paged_kv(vp, tab)
    kp2 = kp.at[...].set(1e4)
    vp2 = vp.at[...].set(-1e4)
    bt = kp.shape[1]
    for i in range(2):
        for p in range(int(lens[i])):
            blk, off = int(tab[i, p // bt]), p % bt
            kp2 = kp2.at[blk, off].set(live_k[i, p])
            vp2 = vp2.at[blk, off].set(live_v[i, p])
    out2 = ref.paged_decode_attention(q, kp2, vp2, tab, lens)
    np.testing.assert_allclose(out1, out2, atol=1e-6)


# ---------------------------------------------------------------------------
# PagedKVStore allocator semantics
# ---------------------------------------------------------------------------

def test_store_prefix_dedup_and_cached_reclaim():
    st_ = PagedKVStore(num_blocks=8, block_tokens=4)
    prompt = list(range(12))                       # 3 full blocks
    chain = prefix_chain(prompt, 4)
    b0, m0 = st_.allocate(0, 12, chain)
    assert m0 == 0 and len(b0) == 3
    b1, m1 = st_.allocate(1, 14, chain)            # same prefix + tail
    assert m1 == 3 and b1[:3] == b0[:3]            # physical aliasing
    assert st_.refcount[b0[0]] == 2
    st_.free(0)
    st_.free(1)
    # registered blocks stay resident as cache and are reclaimed on demand
    assert st_.cached_blocks == 3 and st_.used_blocks == 0
    b2, m2 = st_.allocate(2, 12, chain)
    assert m2 == 3                                 # hit the cached chain
    st_.free(2)
    got = st_.allocate(3, 8 * 4)                   # whole pool: evicts cache
    assert got is not None and st_.radix_evictions == 3
    st_.check_invariants()


def test_store_swap_roundtrip_and_shared_degrade():
    st_ = PagedKVStore(num_blocks=6, block_tokens=4)
    chain = prefix_chain(list(range(8)), 4)
    st_.allocate(0, 8, chain)
    st_.allocate(1, 8, chain)                      # shares both blocks
    assert st_.swap_out(0) is None                 # shared pages: degrade
    st_.free(1)
    blocks = st_.swap_out(0)                       # now refcount-1
    assert blocks is not None and not st_.tables[0].on_device
    assert st_.used_blocks == 0                    # device side released
    back = st_.swap_in(0)
    assert back is not None and st_.tables[0].on_device
    assert st_.tables[0].tokens == 8
    st_.check_invariants()


@settings(max_examples=15, deadline=None)
@given(ops=st.lists(st.tuples(st.integers(0, 4), st.integers(1, 30)),
                    min_size=1, max_size=40),
       nb=st.integers(4, 12), bt=st.sampled_from([2, 4, 8]))
def test_store_invariants_random_walk(ops, nb, bt):
    st_ = PagedKVStore(num_blocks=nb, block_tokens=bt)
    live = []
    rid = 0
    for op, arg in ops:
        if op == 0:                                # allocate
            toks = arg
            chain = prefix_chain(list(range(min(toks, 3 * bt))), bt)
            if st_.allocate(rid, toks, chain) is not None:
                live.append(rid)
            rid += 1
        elif op == 1 and live:                     # grow/advance one token
            r = live[arg % len(live)]
            if st_.tables[r].on_device:
                if st_.needs_block(r):
                    if st_.grow(r) is None:
                        continue
                st_.advance(r)
        elif op == 2 and live:                     # free
            r = live.pop(arg % len(live))
            st_.free(r)
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
# engine parity vs the seed slot engine
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def cfg():
    return get_reduced_config("gemma_2b")


@pytest.fixture(scope="module")
def prompts(cfg):
    rng = np.random.default_rng(3)
    # two distinct lengths only: every fresh prompt length retraces the
    # prefill jit, and parity doesn't need a length sweep here (the kernel
    # sweep above covers raggedness)
    return [rng.integers(0, cfg.vocab_size, n) for n in (12, 17, 12, 17, 12)]


@pytest.fixture(scope="module")
def params(cfg):
    # live output projections: under the training init every block is the
    # identity, so streams would not depend on the KV cache at all
    return tf.init_params(cfg, jax.random.PRNGKey(3), False)


def test_paged_engine_matches_slot_engine(cfg, params, prompts):
    slot = SlotEngine(cfg, params, max_batch=2, max_len=64)
    paged = Engine(cfg, params, max_batch=2, max_len=64, block_tokens=16)
    for p in prompts:
        slot.submit(p, max_new_tokens=5)
        paged.submit(p, max_new_tokens=5)
    want = {tuple(r.prompt.tolist()): r.tokens for r in slot.run()}
    got = {tuple(r.prompt.tolist()): r.tokens for r in paged.run()}
    assert got == want
    paged.store.check_invariants()
    assert paged.store.used_blocks == 0            # everything released


@pytest.mark.parametrize("policy", ["swap", "recompute"])
def test_pressured_engine_stream_parity(cfg, params, prompts, policy):
    """A pool too small for both requests forces real mid-stream preemption
    (device->host page movement for swap; drop + re-prefill for recompute);
    the token streams must still equal the unpressured engine's."""
    ample = Engine(cfg, params, max_batch=2, max_len=64, block_tokens=8)
    tight = Engine(cfg, params, max_batch=2, max_len=64, block_tokens=8,
                   num_blocks=5, preemption=policy)
    for p in prompts[:2]:
        ample.submit(p, max_new_tokens=12)
        tight.submit(p, max_new_tokens=12)
    want = {tuple(r.prompt.tolist()): r.tokens for r in ample.run()}
    got = {tuple(r.prompt.tolist()): r.tokens for r in tight.run()}
    assert got == want
    st_ = tight.kv_stats()
    assert st_["page_faults"] >= 1                 # pressure actually fired
    if policy == "swap":
        assert st_["swap_outs"] >= 1 and st_["swap_ins"] >= 1
    else:
        assert st_["recompute_drops"] >= 1
    assert any(r.preemptions for r in tight.finished)
    tight.store.check_invariants()


def test_manual_preempt_keeps_tokens_and_requeues_fifo(cfg):
    rng = np.random.default_rng(9)
    eng = Engine(cfg, max_batch=1, max_len=64, seed=0, block_tokens=16)
    first = eng.submit(rng.integers(0, cfg.vocab_size, 8), max_new_tokens=6)
    eng._admit()
    eng._step_decode()
    eng._step_decode()
    generated = list(first.tokens)
    assert len(generated) == 3
    later = eng.submit(rng.integers(0, cfg.vocab_size, 8), max_new_tokens=4)
    eng.preempt_slot(0)
    # FIFO-fair: the preempted request resumes BEFORE the later submission
    # (seed engine would also put it first here, but by unconditional
    # insert(0) — the distinction is covered below)
    assert [r.rid for r in eng.waiting] == [first.rid, later.rid]
    done = eng.run()
    assert len(done) == 2
    assert done[0] is first
    assert first.tokens[:len(generated)] == generated   # nothing discarded
    assert len(first.tokens) == 6


def test_preempt_requeue_is_fifo_fair_not_queue_head(cfg):
    """A preempted LATER request must not jump ahead of earlier waiters."""
    rng = np.random.default_rng(11)
    eng = Engine(cfg, max_batch=2, max_len=64, seed=0, block_tokens=16)
    a = eng.submit(rng.integers(0, cfg.vocab_size, 8), max_new_tokens=4)
    b = eng.submit(rng.integers(0, cfg.vocab_size, 8), max_new_tokens=4)
    c = eng.submit(rng.integers(0, cfg.vocab_size, 8), max_new_tokens=4)
    eng._admit()                                   # a, b running; c waiting
    eng._step_decode()
    eng.preempt_slot(b.slot)
    assert [r.rid for r in eng.waiting] == [b.rid, c.rid]
    eng.preempt_slot(a.slot)
    assert [r.rid for r in eng.waiting] == [a.rid, b.rid, c.rid]
    done = eng.run()
    assert len(done) == 3 and all(len(r.tokens) == 4 for r in done)


def test_submit_rids_unique_after_completion(cfg):
    """Seed bug: rids were recomputed from queue sizes, so they collided
    after requests finished. They must be unique for the life of the
    engine (the store keys tables by rid)."""
    rng = np.random.default_rng(13)
    eng = Engine(cfg, max_batch=2, max_len=64, seed=0, block_tokens=16)
    r1 = eng.submit(rng.integers(0, cfg.vocab_size, 8), max_new_tokens=3)
    eng.run()
    r2 = eng.submit(rng.integers(0, cfg.vocab_size, 8), max_new_tokens=3)
    r3 = eng.submit(rng.integers(0, cfg.vocab_size, 8), max_new_tokens=3)
    eng.run()
    rids = [r1.rid, r2.rid, r3.rid]
    assert len(set(rids)) == 3
    slot = SlotEngine(cfg, max_batch=1, max_len=64)
    s1 = slot.submit(rng.integers(0, cfg.vocab_size, 8), max_new_tokens=3)
    slot.run()
    s2 = slot.submit(rng.integers(0, cfg.vocab_size, 8), max_new_tokens=3)
    assert s1.rid != s2.rid


def test_engine_prefix_sharing_dedups_physical_blocks(cfg):
    rng = np.random.default_rng(17)
    sysp = rng.integers(0, cfg.vocab_size, 32)     # 2 full blocks of 16
    eng = Engine(cfg, max_batch=4, max_len=64, seed=2, block_tokens=16)
    for _ in range(4):
        eng.submit(np.concatenate([sysp, rng.integers(0, cfg.vocab_size, 5)]),
                   max_new_tokens=3)
    eng.run()
    st_ = eng.kv_stats()
    assert st_["prefix_hit_blocks"] >= 6           # 3 sharers x 2 blocks
    assert st_["dedup_ratio"] > 1.0
    eng.store.check_invariants()


def test_make_engine_falls_back_for_unpaged_families(cfg):
    """MLA (latent cache) and recurrent families are not paged yet; the
    factory must hand them the dense SlotEngine instead of crashing."""
    assert isinstance(make_engine(cfg, max_batch=1, max_len=64,
                                  block_tokens=16), Engine)
    mla = get_reduced_config("deepseek_v2_lite_16b")
    eng = make_engine(mla, max_batch=1, max_len=64, block_tokens=16)
    assert isinstance(eng, SlotEngine)
    ssm = get_reduced_config("xlstm_1_3b")
    assert isinstance(make_engine(ssm, max_batch=1, max_len=64), SlotEngine)


def test_init_paged_cache_lengths_zero_when_batch_equals_max_blocks(cfg):
    """Regression: the block-table leaf was picked by *shape*, so a (batch,)
    length array with batch == max_blocks got initialized to the trash id."""
    from repro.models import transformer as tf
    caches = tf.init_paged_cache(cfg, batch=4, num_blocks=16,
                                 block_tokens=16, max_blocks=4)
    g = caches["attn"]
    assert np.all(np.asarray(g["length"]) == 0)
    assert np.all(np.asarray(g["block_tables"]) == 16)


def test_engine_geometry_guards(cfg):
    with pytest.raises(AssertionError):
        Engine(cfg, max_batch=1, max_len=60, block_tokens=16)  # not divisible
    eng = Engine(cfg, max_batch=1, max_len=64, block_tokens=16, num_blocks=2)
    with pytest.raises(ValueError):
        eng.submit(np.arange(30, dtype=np.int32), max_new_tokens=30)
