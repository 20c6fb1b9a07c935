"""Speculative decoding end-to-end: verify-kernel parity (Pallas interpret
vs per-position decode oracle), COW fork/rollback random walks in
`PagedKVStore`, verify-mode model parity, speculative-Engine-vs-plain-Engine
greedy stream equality (spec_k x prompt length x prefix sharing x
preemption), and the per-position acceptance distribution in
`perfmodel.speculative_decode_step` pinned against Monte-Carlo."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import get_reduced_config
from repro.engine.paged_kv import PagedKVStore, prefix_chain
from repro.kernels import ref
from repro.kernels.paged_attention import paged_verify_attention
from repro.models import steps
from repro.models import transformer as tf

KEY = jax.random.PRNGKey(11)


@pytest.fixture(scope="module")
def cfg():
    return get_reduced_config("gemma_2b")


@pytest.fixture(scope="module")
def params(cfg):
    # live output projections: under the training init every block is the
    # identity, so streams would not depend on the KV cache at all
    p, _ = tf.init_model(cfg, jax.random.PRNGKey(0), zero_out=False)
    return p


def _pool_case(rnd_key, b, s, kvh, g, d, dv, bt, mb):
    """Random pool + permutation block table; lengths leave >= s slots of
    headroom so every draft position lands inside the table's coverage."""
    nb = b * mb
    q = jax.random.normal(jax.random.fold_in(rnd_key, 0), (b, s, kvh * g, d))
    kp = jax.random.normal(jax.random.fold_in(rnd_key, 1), (nb, bt, kvh, d))
    vp = jax.random.normal(jax.random.fold_in(rnd_key, 2), (nb, bt, kvh, dv))
    tab = jax.random.permutation(jax.random.fold_in(rnd_key, 3),
                                 nb).reshape(b, mb)
    lens = jax.random.randint(jax.random.fold_in(rnd_key, 4), (b,), 1,
                              mb * bt - s + 1)
    return q, kp, vp, tab.astype(jnp.int32), lens.astype(jnp.int32)


# ---------------------------------------------------------------------------
# verify kernel: ref oracle vs sequential decode, Pallas interpret vs ref
# ---------------------------------------------------------------------------

def test_verify_ref_positions_bitwise_equal_sequential_decode():
    """Position j of the verify oracle must be BIT-identical to a one-token
    paged decode at the same position — the numeric foundation of the
    engine's spec-vs-plain stream-equality contract."""
    q, kp, vp, tab, lens = _pool_case(jax.random.fold_in(KEY, 0),
                                      3, 4, 2, 2, 32, 32, 8, 6)
    out = ref.paged_verify_attention(q, kp, vp, tab, lens)
    for j in range(q.shape[1]):
        want = ref.paged_decode_attention(q[:, j:j + 1], kp, vp, tab,
                                          lens + j + 1)
        np.testing.assert_array_equal(np.asarray(out[:, j:j + 1]),
                                      np.asarray(want))


@settings(max_examples=10, deadline=None)
@given(b=st.integers(1, 3), s=st.integers(1, 5), kvh=st.sampled_from([1, 2]),
       g=st.sampled_from([1, 2, 4]), d=st.sampled_from([16, 32]),
       bt=st.sampled_from([8, 16]), mb=st.integers(2, 6),
       seed=st.integers(0, 2 ** 16))
def test_verify_kernel_matches_ref(b, s, kvh, g, d, bt, mb, seed):
    """Hypothesis sweep: the one-pass Pallas verify kernel (interpret mode)
    must match the per-position unrolled oracle to fp32 tolerance across
    (batch, draft width, lengths, block size, table layout)."""
    q, kp, vp, tab, lens = _pool_case(jax.random.fold_in(KEY, seed),
                                      b, s, kvh, g, d, d, bt, mb)
    out = paged_verify_attention(q, kp, vp, tab, lens, interpret=True)
    want = ref.paged_verify_attention(q, kp, vp, tab, lens)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)


def test_verify_kernel_asymmetric_dv():
    q, kp, vp, tab, lens = _pool_case(jax.random.fold_in(KEY, 99),
                                      2, 3, 2, 2, 32, 16, 8, 4)
    out = paged_verify_attention(q, kp, vp, tab, lens, interpret=True)
    want = ref.paged_verify_attention(q, kp, vp, tab, lens)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)


def test_verify_ref_ignores_garbage_beyond_span():
    """Pool content past a row's causal span (draft positions not yet
    written, trash page, rejected writes from earlier iterations) must not
    perturb any verify output — masked lanes carry probability exactly 0."""
    q, kp, vp, tab, lens = _pool_case(jax.random.fold_in(KEY, 7),
                                      2, 3, 1, 4, 32, 32, 8, 6)
    s = q.shape[1]
    out1 = ref.paged_verify_attention(q, kp, vp, tab, lens)
    live_k = ref.gather_paged_kv(kp, tab)
    live_v = ref.gather_paged_kv(vp, tab)
    kp2 = kp.at[...].set(1e4)
    vp2 = vp.at[...].set(-1e4)
    bt = kp.shape[1]
    for i in range(2):
        for p in range(int(lens[i]) + s):       # position s-1 reads slots
            blk, off = int(tab[i, p // bt]), p % bt      # 0 .. lens+s-1
            kp2 = kp2.at[blk, off].set(live_k[i, p])
            vp2 = vp2.at[blk, off].set(live_v[i, p])
    out2 = ref.paged_verify_attention(q, kp2, vp2, tab, lens)
    np.testing.assert_allclose(out1, out2, atol=1e-6)


# ---------------------------------------------------------------------------
# model layer: verify_step == sequential decode, bitwise
# ---------------------------------------------------------------------------

def test_verify_step_matches_sequential_decode_bitwise(cfg, params):
    """Feed an arbitrary (not necessarily greedy) draft continuation through
    one verify pass and through s sequential one-token decode steps: the
    per-position logits and argmaxes must be bit-identical — the model-layer
    foundation of the engine's spec-vs-plain stream equality."""
    rng = np.random.default_rng(2)
    P, s, bt, max_len = 40, 4, 16, 96
    mb, num_blocks = max_len // bt, 2 * (max_len // bt)
    prompt = rng.integers(1, cfg.vocab_size, P).astype(np.int32)
    draft = rng.integers(1, cfg.vocab_size, s).astype(np.int32)

    def fresh_caches():
        caches = tf.init_paged_cache(cfg, 2, num_blocks, bt, mb)
        tables = np.full((2, mb), num_blocks, np.int32)
        tables[0] = np.arange(mb)             # row 0 live, row 1 dead/trash
        for g in caches.values():
            L = g["block_tables"].shape[0]
            g["block_tables"] = jnp.broadcast_to(
                jnp.asarray(tables)[None], (L, 2, mb))
        toks = np.zeros((2, P), np.int32)
        toks[0] = prompt
        qv = jnp.asarray(np.array([P, 0], np.int32))
        _, _, caches = steps.chunk_step(params, jnp.asarray(toks), qv,
                                        caches, cfg)
        return caches

    # sequential arm: one-token decodes, collecting per-position logits
    caches = fresh_caches()
    seq_logits = []
    for j in range(s):
        t = np.zeros((2, 1), np.int32)
        t[0, 0] = draft[j]
        _, logits, caches = steps.serve_step(params, jnp.asarray(t),
                                             caches, cfg)
        seq_logits.append(np.asarray(logits))

    # verify arm: all s positions in one pass
    caches = fresh_caches()
    feed = np.zeros((2, s), np.int32)
    feed[0] = draft
    qv = jnp.asarray(np.array([s, 0], np.int32))
    greedy, logits, _ = steps.verify_step(params, jnp.asarray(feed), qv,
                                          caches, cfg)
    greedy, logits = np.asarray(greedy), np.asarray(logits)
    for j in range(s):
        assert np.array_equal(logits[0, j], seq_logits[j][0]), j
        assert greedy[0, j] == int(np.argmax(seq_logits[j][0])), j


# ---------------------------------------------------------------------------
# PagedKVStore: COW fork/commit/abort random walk
# ---------------------------------------------------------------------------

def test_fork_cow_protects_shared_registered_block():
    """Two tables share a registered block; one forks with its fill front
    midway into it (the chunked-admission shape). The fork must COW the
    shared page out of the write range, commit must release the original to
    its other owner, and an abort must restore the exact pre-fork state."""
    bt = 4
    st_ = PagedKVStore(num_blocks=12, block_tokens=bt)
    chain = prefix_chain(list(range(2 * bt)), bt)
    a, _ = st_.allocate(1, 2 * bt, chain)
    b, n_matched = st_.allocate(2, 2 * bt, chain, filled=bt + 1,
                                context_tokens=2 * bt)
    assert n_matched == 2 and b == a          # fully shared
    base = (list(st_.tables[2].blocks), st_.tables[2].tokens,
            list(st_.tables[2].hashes), dict(st_.refcount))
    f = st_.fork_table(2, extra_tokens=bt)    # write range starts in blk 1
    assert f is not None and len(f.cow) == 1
    idx, old, new = f.cow[0]
    assert idx == 1 and old == a[1] and st_.tables[2].blocks[1] == new
    t = st_.tables[2]
    for i in range(t.tokens // bt, len(t.blocks)):
        blk = t.blocks[i]
        assert st_.refcount[blk] == 1 and blk not in st_.by_block
    st_.check_invariants()
    st_.abort_fork(2)
    assert (list(t.blocks), t.tokens, list(t.hashes),
            dict(st_.refcount)) == base
    st_.check_invariants()
    # fork again and commit: rid 1 must still own the original page
    f = st_.fork_table(2, extra_tokens=bt)
    st_.commit_fork(2, 3)
    assert t.tokens == bt + 1 + 3
    assert st_.tables[1].blocks == a and st_.refcount[a[1]] == 1
    st_.check_invariants()
    st_.free(1)
    st_.free(2)
    st_.check_invariants()
    assert st_.used_blocks == 0


@settings(max_examples=30, deadline=None)
@given(ops=st.lists(st.tuples(st.integers(0, 7), st.integers(0, 30)),
                    min_size=1, max_size=50),
       nb=st.integers(4, 14), bt=st.sampled_from([2, 4]))
def test_fork_random_walk_invariants(ops, nb, bt):
    """fork/commit/abort interleaved with admission, fill-front growth,
    swap_out/swap_in/free and cache reclaim: store invariants hold after
    every op, every fork's write range is private (refcount-1,
    unregistered), and an aborted fork restores table + refcounts exactly."""
    st_ = PagedKVStore(num_blocks=nb, block_tokens=bt)
    live, goal, rid = [], {}, 0
    snaps = {}                                 # rid -> (pre-fork state, extra)
    for op, arg in ops:
        if op == 0:                            # admission, shared prefixes
            toks = 1 + arg % (4 * bt)
            fill = max(1, arg % (toks + 1))
            chain = prefix_chain(list(range(min(toks, 3 * bt))), bt)
            if st_.allocate(rid, toks, chain, filled=fill,
                            context_tokens=toks) is not None:
                live.append(rid)
                goal[rid] = toks
            rid += 1
        elif op == 1 and live:                 # open a fork
            r = live[arg % len(live)]
            t = st_.tables[r]
            if t.on_device and r not in st_.forks:
                extra = 1 + arg % (2 * bt)
                snap = (list(t.blocks), t.tokens, list(t.hashes),
                        dict(st_.refcount))
                if st_.fork_table(r, extra) is not None:
                    snaps[r] = (snap, extra)
                    for i in range(t.tokens // bt, len(t.blocks)):
                        blk = t.blocks[i]
                        assert st_.refcount[blk] == 1
                        assert blk not in st_.by_block
        elif op == 2 and st_.forks:            # commit
            r = sorted(st_.forks)[arg % len(st_.forks)]
            _, extra = snaps.pop(r)
            base_tokens = st_.forks[r].base_tokens
            n = arg % (extra + 1)
            st_.commit_fork(r, n)
            t = st_.tables[r]
            assert t.tokens == base_tokens + n
            assert len(t.blocks) * bt >= t.tokens
        elif op == 3 and st_.forks:            # abort: exact restore
            r = sorted(st_.forks)[arg % len(st_.forks)]
            (blocks, tokens, hashes, _), _ = snaps.pop(r)
            f = st_.forks[r]
            released = [new for _, _, new in f.cow] + list(f.grown)
            st_.abort_fork(r)
            t = st_.tables[r]
            assert (list(t.blocks), t.tokens, list(t.hashes)) \
                == (blocks, tokens, hashes)
            for blk in released:               # fork-private pages all gone
                assert blk not in st_.refcount
        elif op == 4 and live:                 # plain fill-front growth
            r = live[arg % len(live)]
            t = st_.tables[r]
            if t.on_device and r not in st_.forks and t.tokens < goal[r]:
                ok = True
                while len(t.blocks) * bt < t.tokens + 1:
                    if st_.grow(r) is None:
                        ok = False
                        break
                if ok:
                    st_.advance(r, 1)
        elif op == 5 and live:                 # free (forks resolve first)
            r = live.pop(arg % len(live))
            if r in st_.forks:
                st_.abort_fork(r)
                snaps.pop(r)
            st_.free(r)
        elif op == 6 and live:                 # swap out (maybe degrade)
            r = live[arg % len(live)]
            t = st_.tables[r]
            if t.on_device:
                if r in st_.forks:
                    st_.abort_fork(r)
                    snaps.pop(r)
                if st_.swap_out(r) is None:
                    live.remove(r)
                    st_.drop(r)
        elif op == 7 and live:                 # swap in
            r = live[arg % len(live)]
            if not st_.tables[r].on_device:
                st_.swap_in(r)
        st_.check_invariants()
    for r in live:
        if r in st_.forks:
            st_.abort_fork(r)
        st_.free(r)
    st_.check_invariants()
    assert st_.used_blocks == 0


# ---------------------------------------------------------------------------
# engine: speculative streams bit-identical to plain decode
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def draft_cfg():
    return get_reduced_config("guard_2b")


@pytest.fixture(scope="module")
def draft_params(draft_cfg):
    p, _ = tf.init_model(draft_cfg, jax.random.PRNGKey(5), zero_out=False)
    return p


_STREAMS = {}


def _engine_streams(cfg, params, prompts, *, spec_k=0, draft_cfg=None,
                    draft_params=None, num_blocks=None, preemption="swap",
                    max_new=10, key=None):
    """Run an Engine over ``prompts`` and return {rid: tokens}. Non-spec
    baselines memoize on ``key`` (the oracle never changes across cases)."""
    from repro.engine.runner import Engine, EngineConfig
    if key is not None and key in _STREAMS:
        return _STREAMS[key]
    conf = EngineConfig(draft_cfg=draft_cfg, spec_k=spec_k)
    eng = Engine(cfg, params=params, max_batch=3, max_len=64, block_tokens=8,
                 num_blocks=num_blocks, preemption=preemption, config=conf,
                 draft_params=draft_params)
    for p in prompts:
        eng.submit(p, max_new_tokens=max_new)
    fin = eng.run()
    assert len(fin) == len(prompts)
    eng.store.check_invariants()
    assert not eng.store.forks          # every fork committed or aborted
    out = {r.rid: list(r.tokens) for r in fin}
    if key is not None:
        _STREAMS[key] = out
    return eng if key is None else out


def _case_prompts(share, lens):
    rng = np.random.default_rng(10_000 * share + sum(lens))
    shared = rng.integers(1, 512, size=16).astype(np.int32)
    out = []
    for n in lens:
        tail = rng.integers(1, 512, size=n).astype(np.int32)
        out.append(np.concatenate([shared, tail]) if share else tail)
    return out


@settings(max_examples=8, deadline=None)
@given(spec_k=st.integers(1, 5), share=st.booleans(),
       lens=st.lists(st.integers(1, 40), min_size=2, max_size=5),
       preemption=st.sampled_from(["swap", "recompute"]),
       tight=st.booleans())
def test_spec_engine_stream_parity(cfg, params, draft_cfg, draft_params,
                                   spec_k, share, lens, preemption, tight):
    """The tentpole invariant: for every (spec_k, prompt-length mix, prefix
    sharing, pool pressure, preemption policy) the speculative engine's
    greedy streams are BIT-IDENTICAL to the plain paged engine's. A tight
    pool forces mid-speculation preemption (fork aborts, draft rebuilds);
    shared prefixes force real COW forks over registered pages."""
    prompts = _case_prompts(share, lens)
    nb = 12 if tight else None
    base = _engine_streams(cfg, params, prompts, num_blocks=nb,
                           preemption=preemption,
                           key=("base", share, tuple(lens), preemption, nb))
    eng = _engine_streams(cfg, params, prompts, spec_k=spec_k,
                          draft_cfg=draft_cfg, draft_params=draft_params,
                          num_blocks=nb, preemption=preemption)
    got = {r.rid: list(r.tokens) for r in eng.finished}
    assert got == base
    st_ = eng.spec_stats()
    assert st_["emitted"] == sum(len(t) - 1 for t in base.values())


def test_spec_engine_perfect_draft_accepts_everything(cfg, params):
    """Draft == target: every draft token must be accepted (acceptance 1.0
    per position) and rows commit k+1 tokens per step away from stop
    boundaries — the mechanism's upper bound, and a direct check that
    acceptance logic compares the right positions."""
    prompts = _case_prompts(0, [5, 17, 9])
    eng = _engine_streams(cfg, params, prompts, spec_k=3, draft_cfg=cfg,
                          draft_params=params, max_new=13)
    base = _engine_streams(cfg, params, prompts, max_new=13,
                           key=("perfect-base",))
    assert {r.rid: list(r.tokens) for r in eng.finished} == base
    st_ = eng.spec_stats()
    assert st_["acceptance_per_position"] == [1.0, 1.0, 1.0]
    assert st_["conditional_acceptance_per_position"] == [1.0, 1.0, 1.0]
    assert st_["tokens_per_step"] > 2.0


def test_spec_engine_partial_acceptance_telemetry(cfg, params):
    """Draft = target weights + noise: acceptance is strictly partial, and
    the telemetry must be self-consistent. ``acceptance_per_position`` is a
    MARGINAL (accept stops at the first rejection, so accepted/proposed is
    already a cumulative product); the conditional sequence divides that
    out, so compounding it back (``expected_accepted_tokens``) must equal
    1 + sum(marginals) — the identity E[accepted] = sum_i P(accept through
    i). Feeding the marginals instead would double-compound (the bug this
    test pins)."""
    import math

    from repro.perfmodel.analytical import expected_accepted_tokens

    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(3), len(leaves))
    noisy = jax.tree.unflatten(tree, [
        l + 0.1 * jax.random.normal(k, l.shape, l.dtype)
        if jnp.issubdtype(l.dtype, jnp.floating) else l
        for l, k in zip(leaves, keys)])
    prompts = _case_prompts(0, [5, 17, 9])
    eng = _engine_streams(cfg, params, prompts, spec_k=4, draft_cfg=cfg,
                          draft_params=noisy, max_new=13)
    base = _engine_streams(cfg, params, prompts, max_new=13,
                           key=("partial-base",))
    assert {r.rid: list(r.tokens) for r in eng.finished} == base
    st_ = eng.spec_stats()
    marg = st_["acceptance_per_position"]
    cond = st_["conditional_acceptance_per_position"]
    assert all(m <= c + 1e-12 for m, c in zip(marg, cond))
    # the identity is exact when marginals decay monotonically (k_eff
    # clamping can wiggle the tail, hence the small tolerance); the
    # double-compounding bug would miss by ~sum(marg) - sum(cumprods)
    pred = expected_accepted_tokens(4, cond)
    assert math.isclose(pred, 1.0 + sum(marg), rel_tol=0.05)
    # measured tokens/step only deviates from the prediction through stop
    # boundaries (rows finishing mid-run), so it stays in a loose band
    assert abs(pred - st_["tokens_per_step"]) / pred < 0.5


def test_spec_engine_eos_mid_acceptance(cfg, params, draft_cfg, draft_params):
    """EOS inside an accepted run must truncate the stream exactly where
    sequential decode would stop."""
    prompts = _case_prompts(0, [7, 21])
    base = _engine_streams(cfg, params, prompts, max_new=16,
                           key=("eos-base",))
    eos = base[0][min(3, len(base[0]) - 1)]     # a token the stream emits
    from repro.engine.runner import Engine, EngineConfig
    outs = []
    for k in (0, 4):
        conf = EngineConfig(draft_cfg=draft_cfg if k else None, spec_k=k)
        eng = Engine(cfg, params=params, max_batch=3, max_len=64,
                     block_tokens=8, config=conf,
                     draft_params=draft_params if k else None)
        for p in prompts:
            eng.submit(p, max_new_tokens=16, eos_id=int(eos))
        fin = eng.run()
        outs.append({r.rid: list(r.tokens) for r in fin})
    assert outs[0] == outs[1]


# ---------------------------------------------------------------------------
# analytical model: expected accepted tokens
# ---------------------------------------------------------------------------

def test_expected_accepted_tokens_matches_monte_carlo():
    """The per-position closed form E = 1 + sum_j prod_{i<=j} a_i must match
    a direct Monte-Carlo of the acceptance process (accept position j iff
    every earlier position accepted and its own coin lands)."""
    from repro.perfmodel.analytical import expected_accepted_tokens
    rng = np.random.default_rng(0)
    dist = [0.9, 0.7, 0.5, 0.2]
    k = len(dist)
    runs = np.cumprod(rng.random((200_000, k)) < np.asarray(dist), axis=1)
    mc = float((1 + runs.sum(axis=1)).mean())
    assert abs(expected_accepted_tokens(k, dist) - mc) < 0.01
    # scalar alpha keeps the geometric closed form
    assert np.isclose(expected_accepted_tokens(4, 0.8),
                      (1 - 0.8 ** 5) / (1 - 0.8))
    # a short distribution extends with its last value
    assert np.isclose(expected_accepted_tokens(4, [0.5]),
                      expected_accepted_tokens(4, 0.5))
    # degenerate bounds: never-accept -> bonus token only; always -> k+1
    assert expected_accepted_tokens(4, 0.0) == 1.0
    assert expected_accepted_tokens(4, [1.0, 1.0, 1.0, 1.0]) == 5.0


def test_sim_spec_decode_stage():
    """SPEC_DECODE in the simulator: speculative decode steps commit
    multiple tokens per iteration, so decode-bound TPOT must drop vs the
    plain scheduler; a measured per-position distribution prices between
    its geometric envelopes."""
    from repro.core import (SystemSpec, WorkloadConfig, build_system,
                            generate)
    from repro.core.llm_scheduler import SchedulerLimits
    from repro.core.workload import AZURE_CODE

    def tpot(limits):
        spec = SystemSpec(n_llm_clients=2, strategy="continuous",
                          limits=limits, with_pre_post=False)
        coord = build_system(spec)
        wl = WorkloadConfig(trace=AZURE_CODE, rate=2.0, n_requests=30,
                            postprocess=False, seed=41)
        coord.submit(generate(wl))
        return coord.run().summary()["tpot_p50"]

    base = tpot(SchedulerLimits())
    spec = tpot(SchedulerLimits(spec_k=4, spec_acceptance=0.8))
    dist = tpot(SchedulerLimits(spec_k=4,
                                spec_acceptance=(0.9, 0.8, 0.5, 0.3)))
    assert spec < base
    assert dist < base
