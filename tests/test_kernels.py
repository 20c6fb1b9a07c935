"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp oracle across
shape/dtype sweeps, and the ``ops`` dispatch rules and record."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.paged_attention import paged_chunk_attention
from repro.kernels.pq_scan import pq_scan

KEY = jax.random.PRNGKey(0)


def _qkv(b, s, nh, kvh, d, dv=None, dtype=jnp.float32, t=None):
    t = t or s
    dv = dv or d
    q = jax.random.normal(jax.random.fold_in(KEY, 1), (b, s, nh, d), dtype)
    k = jax.random.normal(jax.random.fold_in(KEY, 2), (b, t, kvh, d), dtype)
    v = jax.random.normal(jax.random.fold_in(KEY, 3), (b, t, kvh, dv), dtype)
    return q, k, v


@pytest.mark.parametrize("b,s,nh,kvh,d", [
    (1, 128, 4, 4, 64),      # MHA
    (2, 256, 8, 2, 64),      # GQA
    (2, 192, 8, 1, 32),      # MQA, non-pow2 seq
    (1, 512, 16, 4, 128),    # larger head_dim
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_matches_ref(b, s, nh, kvh, d, causal):
    q, k, v = _qkv(b, s, nh, kvh, d)
    out = flash_attention(q, k, v, causal=causal, interpret=True,
                          block_q=64, block_k=64)
    want = ref.flash_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)


def test_flash_attention_bf16():
    q, k, v = _qkv(2, 128, 8, 2, 64, dtype=jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True, interpret=True,
                          block_q=64, block_k=64)
    want = ref.flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(out.astype(np.float32),
                               want.astype(np.float32), atol=3e-2, rtol=3e-2)


@pytest.mark.parametrize("b,S,nh,kvh,d,block", [
    (2, 300, 8, 2, 64, 128),
    (1, 1024, 4, 1, 32, 256),
    (3, 257, 16, 16, 64, 64),
])
def test_decode_attention_matches_ref(b, S, nh, kvh, d, block):
    q, k, v = _qkv(b, 1, nh, kvh, d, t=S)
    lengths = jax.random.randint(jax.random.fold_in(KEY, 9), (b,), 1, S)
    out = decode_attention(q, k, v, lengths, interpret=True, block_s=block)
    want = ref.decode_attention(q, k, v, lengths)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)


def test_decode_attention_masks_beyond_length():
    """Garbage in the cache past `length` must not affect the output."""
    b, S, nh, kvh, d = 1, 128, 4, 4, 32
    q, k, v = _qkv(b, 1, nh, kvh, d, t=S)
    lengths = jnp.array([40], jnp.int32)
    k2 = k.at[:, 40:].set(1e4)
    v2 = v.at[:, 40:].set(-1e4)
    o1 = decode_attention(q, k, v, lengths, interpret=True, block_s=64)
    o2 = decode_attention(q, k2, v2, lengths, interpret=True, block_s=64)
    np.testing.assert_allclose(o1, o2, atol=1e-6)


@pytest.mark.parametrize("N,M,K,block", [
    (1000, 16, 256, 256),
    (4096, 8, 256, 1024),
    (513, 32, 64, 128),
])
def test_pq_scan_matches_ref(N, M, K, block):
    codes = jax.random.randint(jax.random.fold_in(KEY, 4), (N, M), 0, K)
    lut = jax.random.normal(jax.random.fold_in(KEY, 5), (M, K), jnp.float32)
    out = pq_scan(codes, lut, interpret=True, block_n=block)
    want = ref.pq_scan(codes, lut)
    np.testing.assert_allclose(out, want, atol=1e-4, rtol=1e-5)


@pytest.mark.parametrize("bq,bk", [(64, 64), (128, 96), (1024, 1024)])
def test_chunked_flash_matches_ref(bq, bk):
    q, k, v = _qkv(2, 333, 8, 2, 32, dv=16)
    for causal in (True, False):
        o1 = ref.chunked_flash_attention(q, k, v, causal=causal,
                                         block_q=bq, block_k=bk)
        o2 = ref.flash_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(o1, o2, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("kvh", [1, 8])
def test_kernels_lane_width_heads(kvh):
    """128-lane head dims, as the chip runs them, for MQA and GQA: flash
    (with a ragged prompt that pads to whole tiles) and dense decode."""
    q, k, v = _qkv(2, 200, 8, kvh, 128, dtype=jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    want = ref.flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(out.astype(np.float32),
                               want.astype(np.float32), atol=3e-2, rtol=3e-2)
    lengths = jnp.array([1, 200], jnp.int32)
    out = decode_attention(q[:, :1], k, v, lengths, interpret=True,
                           block_s=64)
    want = ref.decode_attention(q[:, :1], k, v, lengths)
    np.testing.assert_allclose(out.astype(np.float32),
                               want.astype(np.float32), atol=3e-2, rtol=3e-2)


# Chunk rows as (length, q_valid) on a 24-page table of 16-token pages
# (384 positions), 64 chunk positions a row.
CHUNK_ROWS = {
    # a first chunk, rows riding along with nothing to write, a short chunk
    "fresh_idle_short": [(0, 64), (200, 0), (0, 5), (37, 0)],
    # starts mid-page and on a page edge; extents of 4 to 20 pages
    "mid_page_and_edge": [(7, 64), (32, 40), (120, 33), (300, 10)],
    # extents that end exactly at the table's end
    "table_end": [(320, 64), (383, 1), (352, 32), (0, 0)],
}


@pytest.mark.parametrize("rows", sorted(CHUNK_ROWS))
@pytest.mark.parametrize("nh,kvh,d", [(8, 1, 128), (8, 1, 256),
                                      (48, 8, 128), (48, 8, 256)])
def test_paged_chunk_attention_matches_ref(nh, kvh, d, rows):
    """The chunk kernel equals the reference at every valid position of
    every live row, with NaN in the trash page, in every page no live
    extent covers and past each extent in its last page: it reads only
    what the live rows' extents hold."""
    b, s, bt, mb = 4, 64, 16, 24
    nb = b * mb + 1                                 # the last is the trash
    lengths = jnp.array([n for n, _ in CHUNK_ROWS[rows]], jnp.int32)
    q_valid = jnp.array([v for _, v in CHUNK_ROWS[rows]], jnp.int32)
    q, _, _ = _qkv(b, s, nh, kvh, d)
    kp = jax.random.normal(jax.random.fold_in(KEY, 7), (nb, bt, kvh, d))
    vp = jax.random.normal(jax.random.fold_in(KEY, 8), (nb, bt, kvh, d))
    tab = jax.random.permutation(jax.random.fold_in(KEY, 9),
                                 nb - 1).reshape(b, mb).astype(jnp.int32)
    want = ref.paged_chunk_attention(q, kp, vp, tab, lengths)

    seen = np.zeros((nb, bt), bool)                 # positions live rows own
    for (length, valid), pages in zip(CHUNK_ROWS[rows], np.asarray(tab)):
        for p in range(length + valid if valid else 0):
            seen[pages[p // bt], p % bt] = True
    dirty = jnp.asarray(~seen)[:, :, None, None]
    got = paged_chunk_attention(q, jnp.where(dirty, jnp.nan, kp),
                                jnp.where(dirty, jnp.nan, vp), tab, lengths,
                                q_valid, interpret=True)
    for r in range(b):
        n = int(q_valid[r])
        np.testing.assert_allclose(got[r, :n], want[r, :n], atol=2e-5,
                                   rtol=2e-5)


def test_ops_dispatch_rules_and_record(monkeypatch):
    """Off the TPU every op takes the reference; on it (steered here) the
    kernel runs exactly when the head dims are whole 128-lane tiles. Choices
    are counted per trace (fresh lambdas, so no trace is served from a
    cache)."""
    def trace_all():
        ops.DISPATCH.clear()
        for d in (64, 128):
            q, k, v = _qkv(1, 16, 4, 1, d)
            jax.eval_shape(lambda *a: ops.flash_attention(*a), q, k, v)
            pool = jnp.zeros((3, 8, 1, d))
            tab = jnp.zeros((1, 2), jnp.int32)
            lens = jnp.ones((1,), jnp.int32)
            for fn in (ops.paged_decode_attention, ops.paged_chunk_attention):
                jax.eval_shape(lambda *a, fn=fn: fn(*a), q[:, :1], pool,
                               pool, tab, lens)
        return ops.dispatch_record()

    assert trace_all() == {"flash_attention": {"ref": 2},
                           "paged_chunk_attention": {"ref": 2},
                           "paged_decode_attention": {"ref": 2}}
    monkeypatch.setattr(ops, "_platform", lambda: "tpu")
    assert trace_all() == {
        "flash_attention": {"pallas": 1, "ref": 1},
        "paged_chunk_attention": {"pallas": 1, "ref": 1},
        "paged_decode_attention": {"pallas": 1, "ref": 1}}
