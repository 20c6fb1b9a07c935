"""Pallas TPU paged decode-attention kernel (block-table-indexed KV pool).

The dense decode kernel (``decode_attention.py``) streams a *contiguous*
``(b, S)`` cache; this one gathers K/V through a page table instead, so a
request's KV can live in scattered fixed-size physical blocks — the
real-execution twin of the simulator's ``PagedKVAllocator`` layout.

Interface contract
------------------
``paged_decode_attention(q, k_pool, v_pool, block_tables, lengths)``

* ``q``            — ``(b, 1, nh, d)`` one new query token per request.
* ``k_pool``       — ``(num_blocks, block_tokens, kvh, d)`` pooled key pages.
* ``v_pool``       — ``(num_blocks, block_tokens, kvh, dv)`` pooled value
                     pages (``dv`` may differ from ``d``).
* ``block_tables`` — ``(b, max_blocks) int32``; row ``i``'s logical cache is
                     the concatenation ``k_pool[block_tables[i, 0]],
                     k_pool[block_tables[i, 1]], ...`` — i.e. logical token
                     position ``p`` lives at ``(block_tables[i, p // bt],
                     p % bt)``. **Every** entry must be a valid pool index
                     (``0 <= e < num_blocks``): entries past the live length
                     are never *read into the softmax* (masked) but are still
                     *gathered*, so engines pad dead entries with a dedicated
                     trash/zero block, never with ``-1``.
* ``lengths``      — ``(b,) int32`` valid cache tokens per request; the mask
                     is ``pos < lengths``. Must be ``>= 1`` per row (a
                     zero-length row's output is an unspecified garbage row —
                     the engine masks dead slots the same way the dense
                     engine does) and ``<= max_blocks * block_tokens``.

Returns ``(b, 1, nh, dv)`` in ``q.dtype``.

Kernel structure
----------------
Grid ``(batch, kv_heads, max_blocks)`` with the block dimension minor so the
fp32 online-softmax scratch (m, l, acc) carries across a request's pages —
identical to the dense kernel's structure; the only difference is that the
K/V BlockSpec index maps read the physical page id from the scalar-prefetched
block table (``pltpu.PrefetchScalarGridSpec``) instead of slicing a
contiguous cache. The wrapper views each pool as ``(num_blocks,
block_tokens, kvh * d)`` (a free reshape of the token-major layout), so one
(page, kv head) tile is the block ``(1, block_tokens, d)``: its last two
dims equal the page length and a multiple of 128 whenever ``d % 128 == 0``,
which satisfies the TPU's (8, 128) tiling rule for every ``kvh`` and every
``block_tokens``. Pages whose first token is past ``lengths`` skip compute
entirely (``pl.when``); partial tail pages mask per-position. The reference
oracle (``ref.paged_decode_attention``) gathers the pool into a dense cache
and reuses the dense oracle, which makes paged-vs-dense parity exact.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _head_view(k_pool, v_pool):
    """``(nb, bt, kvh, d)`` pools as ``(nb, bt, kvh * d)``: kv head ``h`` of
    a page is then the ``(bt, d)`` tile at block index ``(page, 0, h)``."""
    nb, bt = k_pool.shape[:2]
    return k_pool.reshape(nb, bt, -1), v_pool.reshape(nb, bt, -1)


def _paged_decode_kernel(tab_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                         m_ref, l_ref, acc_ref, *, scale: float,
                         block_tokens: int):
    bi = pl.program_id(0)
    si = pl.program_id(2)
    ns = pl.num_programs(2)

    @pl.when(si == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[bi]
    s_start = si * block_tokens

    @pl.when(s_start < length)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)                 # (g, d)
        k = k_ref[0].astype(jnp.float32)                    # (bt, d)
        v = v_ref[0].astype(jnp.float32)                    # (bt, dv)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        span = s_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(span < length, s, NEG_INF)
        m_prev, l_prev = m_ref[...], l_ref[...]             # (g, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(si == ns - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_decode_attention(q, k_pool, v_pool, block_tables, lengths, *,
                           scale: float | None = None,
                           interpret: bool = False):
    """Block-table decode attention; see the module docstring for the full
    shape/masking contract. ``block_tokens`` is implied by ``k_pool.shape[1]``
    and ``max_blocks`` by ``block_tables.shape[1]``."""
    b, _, nh, d = q.shape
    bt, kvh = k_pool.shape[1], k_pool.shape[2]
    g = nh // kvh
    dv = v_pool.shape[-1]
    max_blocks = block_tables.shape[1]
    scale = d ** -0.5 if scale is None else scale

    qr = q.reshape(b, kvh, g, d)
    grid = (b, kvh, max_blocks)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,          # block_tables, lengths
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, g, d),
                         lambda bi, hi, si, tab, lens: (bi, hi, 0, 0)),
            pl.BlockSpec((1, bt, d),
                         lambda bi, hi, si, tab, lens: (tab[bi, si], 0, hi)),
            pl.BlockSpec((1, bt, dv),
                         lambda bi, hi, si, tab, lens: (tab[bi, si], 0, hi)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, dv),
                               lambda bi, hi, si, tab, lens: (bi, hi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, dv), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, scale=scale, block_tokens=bt),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32),
      qr, *_head_view(k_pool, v_pool))
    return out.reshape(b, 1, nh, dv)


def _paged_verify_kernel(tab_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                         m_ref, l_ref, acc_ref, *, scale: float,
                         block_tokens: int, s: int, g: int):
    """Speculative-verify analogue of ``_paged_decode_kernel``.

    Per (batch row, kv head) the query block holds all ``s = k + 1`` draft
    positions flattened with their query-head group into ``s * g`` rows; row
    ``r`` is draft position ``r // g``, which attends causally over pooled
    positions ``<= length + r // g``. One pass over the page axis scores
    every draft position — the online-softmax scratch simply carries
    ``s * g`` lanes instead of ``g``.
    """
    bi = pl.program_id(0)
    si = pl.program_id(2)
    ns = pl.num_programs(2)

    @pl.when(si == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[bi]
    s_start = si * block_tokens

    # The furthest-ahead draft position attends through pooled position
    # length + s - 1; later pages hold nothing any query row may read.
    @pl.when(s_start < length + s)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)                 # (s*g, d)
        k = k_ref[0].astype(jnp.float32)                    # (bt, d)
        v = v_ref[0].astype(jnp.float32)                    # (bt, dv)
        sc = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * scale
        span = s_start + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
        qpos = length + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 0) // g
        sc = jnp.where(span <= qpos, sc, NEG_INF)
        m_prev, l_prev = m_ref[...], l_ref[...]             # (s*g, 1)
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(sc - m_new)
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(si == ns - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_verify_attention(q, k_pool, v_pool, block_tables, lengths, *,
                           scale: float | None = None,
                           interpret: bool = False):
    """Score ``s = k + 1`` draft positions per row in ONE pass over the block
    table: query ``j`` of row ``i`` sits at logical position
    ``lengths[i] + j`` and attends over pooled positions
    ``<= lengths[i] + j``. The draft tokens' K/V must already be scattered
    into the pools at those positions (caller writes before attending).
    Layout/trash-page conventions are identical to ``paged_decode_attention``;
    the table must cover ``lengths[i] + s`` logical positions per live row.
    Returns ``(b, s, nh, dv)``."""
    b, s, nh, d = q.shape
    bt, kvh = k_pool.shape[1], k_pool.shape[2]
    g = nh // kvh
    dv = v_pool.shape[-1]
    max_blocks = block_tables.shape[1]
    scale = d ** -0.5 if scale is None else scale

    # (b, s, nh, d) -> (b, kvh, s*g, d): draft position major, group minor,
    # so kernel row r maps to (position r // g, group r % g).
    qr = q.reshape(b, s, kvh, g, d).transpose(0, 2, 1, 3, 4)
    qr = qr.reshape(b, kvh, s * g, d)
    grid = (b, kvh, max_blocks)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,          # block_tables, lengths
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, s * g, d),
                         lambda bi, hi, si, tab, lens: (bi, hi, 0, 0)),
            pl.BlockSpec((1, bt, d),
                         lambda bi, hi, si, tab, lens: (tab[bi, si], 0, hi)),
            pl.BlockSpec((1, bt, dv),
                         lambda bi, hi, si, tab, lens: (tab[bi, si], 0, hi)),
        ],
        out_specs=pl.BlockSpec((1, 1, s * g, dv),
                               lambda bi, hi, si, tab, lens: (bi, hi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((s * g, 1), jnp.float32),
            pltpu.VMEM((s * g, 1), jnp.float32),
            pltpu.VMEM((s * g, dv), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_verify_kernel, scale=scale, block_tokens=bt,
                          s=s, g=g),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, s * g, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32),
      qr, *_head_view(k_pool, v_pool))
    return out.reshape(b, kvh, s, g, dv).transpose(0, 2, 1, 3, 4) \
              .reshape(b, s, nh, dv)
