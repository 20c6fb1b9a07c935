"""Disaggregated prefill/decode engine (paper §II-B made real): worker-role
split over the shared ``EngineCore``, the KV-page export/import handoff, the
single-engine bit-equality oracle (across transfer granularities, pairing
modes, chunked prefill, and preemption on either side of the handoff), plus
the simulator-side pricing this PR calibrates: ``Network`` estimate/transfer
consistency on multi-link paths, layerwise swap granularity in
``PagedKVAllocator``, and the measured-link alpha-beta fit."""
import jax
import numpy as np
import pytest

from repro.configs import get_reduced_config
from repro.core.comm import Network
from repro.core.llm_scheduler import LLMScheduler, SchedulerLimits
from repro.core.memory import PagedKVAllocator, tier_transfer_time
from repro.core.request import LLM, Request, Stage
from repro.engine.core import EngineConfig, EngineCore
from repro.engine.paged_kv import PagedKVStore, prefix_chain
from repro.engine.workers import DisaggEngine, move_pages, oracle_engine
from repro.launch.mesh import handoff_devices
from repro.models import transformer as tf
from repro.perfmodel.hardware import (ClusterSpec, H100, LinkSpec,
                                      TIER_HOST_DRAM)
from repro.perfmodel.regression import fit_link_spec

BLOCK_TOKENS = 16
OUT_TOKENS = 8
GEOM = dict(max_batch=2, max_len=96, block_tokens=BLOCK_TOKENS)


@pytest.fixture(scope="module")
def cfg():
    return get_reduced_config("gemma_2b")


@pytest.fixture(scope="module")
def params(cfg):
    # live output projections: under the training init every block is the
    # identity, so streams would not depend on the KV cache at all
    return tf.init_model(cfg, jax.random.PRNGKey(3), zero_out=False)[0]


@pytest.fixture(scope="module")
def prompts(cfg):
    """Shared 32-token (2-block) system prefix + short unique tails, two
    distinct total lengths to bound jit retraces."""
    rng = np.random.default_rng(5)
    sysp = rng.integers(0, cfg.vocab_size, 32)
    return [np.concatenate([sysp, rng.integers(0, cfg.vocab_size, n)])
            .astype(np.int32) for n in (6, 11, 6, 11)]


@pytest.fixture(scope="module")
def oracle_streams(cfg, params, prompts):
    eng = oracle_engine(cfg, params, **GEOM)
    hs = [eng.submit(p, max_new_tokens=OUT_TOKENS) for p in prompts]
    eng.run()
    return [h.tokens for h in hs]


def _disagg_streams(cfg, params, prompts, **kw):
    eng = DisaggEngine(cfg, params, **{**GEOM, **kw})
    hs = [eng.submit(p, max_new_tokens=OUT_TOKENS) for p in prompts]
    eng.run()
    for w in eng.prefill + eng.decode:
        w.store.check_invariants()
    assert all(h.state == "done" for h in hs)
    return [h.tokens for h in hs], eng


# ---------------------------------------------------------------------------
# bit-equality oracle: granularity x pairing mode x chunking x preemption
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["local", "global"])
@pytest.mark.parametrize("gran", ["full", "layerwise"])
def test_disagg_streams_match_oracle(cfg, params, prompts, oracle_streams,
                                     mode, gran):
    got, eng = _disagg_streams(cfg, params, prompts, n_prefill=1, n_decode=2,
                               mode=mode, granularity=gran)
    assert got == oracle_streams
    ts = eng.transfer_stats()
    assert ts["handoffs"] == len(prompts)
    assert ts["bytes"] > 0 and ts["total_s"] > 0
    assert ts["exposed_s"] <= ts["total_s"] + 1e-12


def test_disagg_chunked_prefill_parity(cfg, params, prompts, oracle_streams):
    """Chunked prefill on the prefill workers (budgeted passes, first token
    streamed from the final chunk) must not change any stream."""
    got, eng = _disagg_streams(cfg, params, prompts, n_prefill=2, n_decode=1,
                               mode="global", granularity="layerwise",
                               config=EngineConfig(chunk_size=8))
    assert got == oracle_streams
    assert eng.transfer_stats()["handoffs"] == len(prompts)


@pytest.fixture(scope="module")
def pressure_prompts(cfg):
    """No shared prefix (so swap preemption is never degraded by shared
    pages) and lengths that cross a block boundary mid-decode — two rows
    together overflow a 6-block decode pool exactly when one grows."""
    rng = np.random.default_rng(23)
    return [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
            for n in (44, 46, 44, 46)]


@pytest.fixture(scope="module")
def pressure_oracle(cfg, params, pressure_prompts):
    eng = oracle_engine(cfg, params, **GEOM)
    hs = [eng.submit(p, max_new_tokens=OUT_TOKENS) for p in pressure_prompts]
    eng.run()
    return [h.tokens for h in hs]


@pytest.mark.parametrize("policy", ["swap", "recompute"])
def test_disagg_preemption_parity(cfg, params, pressure_prompts,
                                  pressure_oracle, policy):
    """Pools too small for the full working set force preemption on the
    decode side of the handoff; streams stay bit-identical. Recompute
    victims on a decode worker cannot re-prefill there — they must
    round-trip through their home prefill worker and hand off again; swap
    victims round-trip against the decode worker's own pool."""
    got, eng = _disagg_streams(cfg, params, pressure_prompts,
                               n_prefill=1, n_decode=1,
                               mode="local", granularity="full",
                               preemption=policy, decode_blocks=6)
    assert got == pressure_oracle
    kv = eng.kv_stats()
    faults = sum(w["page_faults"] for w in kv.values())
    assert faults >= 1                        # pressure actually fired
    if policy == "swap":
        assert any(w["swap_outs"] >= 1 for w in kv.values())
        assert eng.transfer_stats()["handoffs"] == len(pressure_prompts)
    else:
        assert any(w["recompute_drops"] >= 1 for w in kv.values())
        # at least one victim re-prefilled and handed off a second time
        assert eng.transfer_stats()["handoffs"] > len(pressure_prompts)


def test_disagg_prefix_dedup_on_decode_side(cfg, params, prompts):
    """Same-prefix handoffs into one decode worker alias the resident chain:
    the import skips the pool write for matched pages and reports them as
    wire bytes a pinned-dedup protocol could have saved."""
    _, eng = _disagg_streams(cfg, params, prompts, n_prefill=1, n_decode=1,
                             mode="local", granularity="full")
    ts = eng.transfer_stats()
    assert ts["dedup_blocks"] >= 2            # the 2-block shared prefix
    # wire dedup, not a prefix-cache hit (count_hits=False convention)
    assert eng.decode[0].store.prefix_hit_blocks == 0


# ---------------------------------------------------------------------------
# store export/import handoff contract
# ---------------------------------------------------------------------------

def test_export_import_roundtrip_and_dedup():
    src = PagedKVStore(num_blocks=8, block_tokens=4)
    toks = list(range(12))
    chain = prefix_chain(toks, 4)
    src.allocate(1, 12, chain)
    exp = src.export_pages(1)
    assert exp.tokens == 12 and len(exp.blocks) == 3
    assert list(exp.chain) == list(chain)
    assert src.exports == 1 and src.exported_blocks == 3

    dst = PagedKVStore(num_blocks=8, block_tokens=4)
    blocks, matched = dst.import_pages(2, exp.tokens, exp.chain)
    assert len(blocks) == 3 and matched == 0  # cold pool: scatter everything
    dst.free(2)                               # registered blocks park cached
    blocks2, matched2 = dst.import_pages(3, exp.tokens, exp.chain)
    assert matched2 == 3                      # resident chain fully aliased
    assert dst.import_dedup_blocks == 3
    assert dst.prefix_hit_blocks == 0         # count_hits=False convention
    dst.check_invariants()


def test_export_refuses_forked_tables():
    st_ = PagedKVStore(num_blocks=8, block_tokens=4)
    st_.allocate(1, 8)
    st_.fork_table(1, 4)
    with pytest.raises(AssertionError):
        st_.export_pages(1)


def test_move_pages_host_staged_counts_bytes(cfg, params):
    from repro.models import steps
    import jax.numpy as jnp
    caches = tf.init_paged_cache(cfg, batch=1, num_blocks=4,
                                 block_tokens=BLOCK_TOKENS, max_blocks=4)
    pages = steps.gather_pages(caches, jnp.asarray([0, 2], jnp.int32))
    for gran in ("full", "layerwise"):
        staged, rec = move_pages(pages, None, gran)
        want = sum(x.nbytes for x in jax.tree_util.tree_leaves(pages))
        assert rec["bytes"] == want and rec["pages"] == 2
        assert rec["staged"] == "host"
        assert rec["exposed_s"] <= rec["total_s"] + 1e-12
        assert sum(b for b, _ in rec["samples"]) == want
        for name, g in staged.items():
            np.testing.assert_array_equal(np.asarray(g["k"]),
                                          np.asarray(pages[name]["k"]))


# ---------------------------------------------------------------------------
# runner facade: the public API survives the core/workers split
# ---------------------------------------------------------------------------

def test_runner_facade_reexports(cfg):
    from repro.engine import runner
    assert runner.Engine is not None and runner.SlotEngine is not None
    assert issubclass(runner.Engine, EngineCore)
    assert runner.EngineConfig is EngineConfig
    eng = runner.make_engine(cfg, max_batch=1, max_len=32,
                             block_tokens=16, device=None)
    assert isinstance(eng, runner.Engine)


# ---------------------------------------------------------------------------
# device assignment helper
# ---------------------------------------------------------------------------

def test_handoff_devices_roles_partition():
    pd, dd = handoff_devices(2, 3)
    assert len(pd) == 2 and len(dd) == 3
    if len(jax.devices()) < 2:
        assert all(d is None for d in pd + dd)
    else:
        assert not (set(pd) & set(dd))        # roles never share a device


# ---------------------------------------------------------------------------
# simulator pricing: estimate/transfer consistency + layerwise swap
# ---------------------------------------------------------------------------

def _two_hop_net():
    net = Network()
    net.add_link("a", LinkSpec("a", 1e9, 1e-5))
    net.add_link("b", LinkSpec("b", 4e8, 3e-5))
    net.connect("src", "dst", ["a", "b"])
    return net


@pytest.mark.parametrize("gran", ["full", "layerwise"])
def test_network_estimate_matches_transfer_under_contention(gran):
    """On a multi-link path, ``estimate`` must price a would-be ``transfer``
    exactly (same contention state) and in particular never under-price it —
    a router that trusts the estimate can never be surprised by the move."""
    net = _two_hop_net()
    rng = np.random.default_rng(17)
    now = 0.0
    for _ in range(25):
        nbytes = float(rng.integers(1, 1 << 22))
        est = net.estimate("src", "dst", nbytes, now, gran, n_layers=6)
        arrive = net.transfer("src", "dst", nbytes, now, gran, n_layers=6)
        assert arrive - now <= est + 1e-9
        assert arrive - now == pytest.approx(est, abs=1e-12)
        now += float(rng.random()) * 1e-3


def test_layerwise_occupies_full_bytes_despite_small_exposure():
    """Layerwise exposes ~one layer of latency but the link still carries
    every byte: a second transfer right behind it queues on the full
    occupancy, and estimate sees that contention too."""
    net = _two_hop_net()
    nbytes = 8e6
    t1 = net.transfer("src", "dst", nbytes, 0.0, "layerwise", n_layers=8)
    assert t1 - 0.0 < nbytes / 1e9            # exposed: far less than full
    est2 = net.estimate("src", "dst", nbytes, 0.0, "layerwise", n_layers=8)
    t2 = net.transfer("src", "dst", nbytes, 0.0, "layerwise", n_layers=8)
    assert est2 == pytest.approx(t2)
    assert t2 > nbytes / 1e9                  # queued behind full occupancy


def test_override_link_repices_in_place():
    net = _two_hop_net()
    net.transfer("src", "dst", 1e6, 0.0)
    moved = net.links["a"].bytes_moved
    busy = net.links["a"].busy_until
    net.override_link("a", LinkSpec("measured", 2e9, 0.0))
    assert net.links["a"].bytes_moved == moved     # counters survive
    assert net.links["a"].busy_until == busy       # contention survives
    est = net.estimate("src", "dst", 2e9, busy)
    assert est == pytest.approx(2e9 / 2e9 + 2e9 / 4e8 + 3e-5)


def test_tier_transfer_time_layerwise_prices_one_group():
    tier = TIER_HOST_DRAM
    nb = 1e8
    full = tier_transfer_time(nb, tier)
    lw = tier_transfer_time(nb, tier, "layerwise", 8)
    assert lw == pytest.approx(tier.transfer_time(nb / 8))
    assert lw < full
    assert tier_transfer_time(nb, tier, "layerwise", 1) == pytest.approx(full)


def test_allocator_layerwise_swap_same_bytes_smaller_stall():
    kv = PagedKVAllocator(capacity_bytes=64.0, bytes_per_token=1.0,
                          block_tokens=4, swap_tiers=(TIER_HOST_DRAM,))
    kv.allocate(1, 16)
    nb_full, t_full = kv.swap_out(1)
    nb_lw, t_lw = kv.swap_in(1, "layerwise", 8)
    assert nb_lw == nb_full                   # the wire carries it all
    assert t_lw < t_full                      # only one group is exposed
    kv.check_invariants()


def test_scheduler_layerwise_swap_cuts_stall_keeps_bytes():
    """End-to-end through ``SchedulerLimits``: the same pressured schedule
    swaps the same bytes under both granularities, but layerwise exposes a
    strictly smaller total stall (and every request still finishes)."""
    from repro.configs import get_config
    cfg = get_config("llama3_70b")
    cluster = ClusterSpec(H100, n_chips=2, tp=2)
    totals = {}
    for gran in ("full", "layerwise"):
        sched = LLMScheduler(
            "continuous", cfg, cluster,
            limits=SchedulerLimits(max_batch=8, kv_capacity_frac=0.0125,
                                   preemption="swap", swap_granularity=gran))
        reqs = [Request(arrival=0.0, input_tokens=400, output_tokens=120,
                        stages=[Stage(LLM)]) for _ in range(6)]
        for r in reqs:
            sched.add(r)
        now, finished, swap_t, swap_b = 0.0, [], 0.0, 0.0
        while sched.has_work():
            step = sched.plan_step()
            assert step is not None
            now += step.duration
            finished += sched.finish_step(step, now)
            swap_t += step.swap_time
            swap_b += step.swap_bytes
        assert len(finished) == 6
        assert sched.kv.swap_bytes_out > 0    # pressure actually swapped
        totals[gran] = (swap_b, swap_t)
    assert totals["layerwise"][0] == pytest.approx(totals["full"][0])
    assert totals["layerwise"][1] < totals["full"][1]


# ---------------------------------------------------------------------------
# measured-link fit (the calibration half of the loop)
# ---------------------------------------------------------------------------

def test_fit_link_spec_recovers_alpha_beta():
    alpha, bw = 2e-4, 5e8
    samples = [(b, alpha + b / bw) for b in (1e4, 1e5, 1e6, 4e6)]
    spec = fit_link_spec(samples)
    assert spec.latency == pytest.approx(alpha, rel=1e-6)
    assert spec.bandwidth == pytest.approx(bw, rel=1e-6)


def test_fit_link_spec_degenerate_cases():
    one = fit_link_spec([(1e6, 1e-3)])
    assert one.latency == 0.0
    assert one.bandwidth == pytest.approx(1e9)
    neg = fit_link_spec([(1e4, 5e-3), (1e6, 1e-3)])   # noisy negative slope
    assert neg.bandwidth > 0 and np.isfinite(neg.bandwidth)
    assert neg.latency >= 0.0
    with pytest.raises(ValueError):
        fit_link_spec([])
