"""Pallas TPU paged attention kernels (block-table-indexed KV pool): decode,
speculative verify and chunked prefill.

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

Chunked prefill
---------------
``paged_chunk_attention(q, k_pool, v_pool, block_tables, lengths, q_valid)``

* ``q``       — ``(b, s, nh, d)``; query ``j`` of row ``r`` sits at logical
                position ``lengths[r] + j`` and attends over pooled
                positions ``<= lengths[r] + j`` (the contract of
                ``ref.paged_chunk_attention``). The chunk's own K/V must
                already be written into the pools.
* ``q_valid`` — ``(b,) int32`` valid chunk positions per row. A row with
                ``q_valid == 0`` issues no DMA and no compute; outputs at
                positions ``>= q_valid`` are unspecified (zeros where a
                whole query block is past ``q_valid``).
* pools, tables and trash-page conventions as above; a live row's table
  must cover ``lengths + q_valid`` positions.

Grid ``(batch, query blocks)``; each step holds ``tq`` chunk positions of
every kv head (flattened with the query-head group into ``tq * g`` rows, as
the verify kernel lays them out) and walks only the pages ``0 ..
ceil((lengths + min(block end, q_valid)) / block_tokens) - 1``, several
pages per step, copied from the pools (``memory_space=pl.ANY``) with manual
async copies into a double buffer. One page of all kv heads is a
contiguous ``(block_tokens, kvh * d)`` tile of ``_head_view``. Later table
entries are neither copied nor scored. Scores, the softmax statistics and
the accumulator are float32, operands cast as the decode kernel casts them;
masked keys add exactly 0 (their values are zeroed, so stale or NaN bytes
in a buffer slot past the extent cannot reach a live output). ``tq`` and
pages per step come from the shapes (``_chunk_tiles``) so that a step fits
the default scoped VMEM.
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


# Chunked-prefill attention: query rows per kv head and key tokens per grid
# step, and the VMEM the step's tiles may take (under the 16 MiB default
# scoped limit of v5e, with room for the compiler's own scratch).
_CHUNK_ROWS = 512
_CHUNK_KEYS = 256
_CHUNK_VMEM = 12 << 20


def _chunk_vmem(kvh, rows, d, dv, tk, q_bytes, kv_bytes):
    """Bytes of VMEM one grid step of ``paged_chunk_attention`` holds."""
    io = 2 * kvh * rows * (d + dv) * q_bytes        # q and out, two buffers
    stats = kvh * rows * (2 * 128 + d + dv) * 4     # m, l (lane-padded), q, acc
    pages = 2 * tk * kvh * (d + dv) * kv_bytes      # K and V, two slots
    temps = 3 * rows * tk * 4 + tk * (d + dv) * 4   # one head's scores, f32 K/V
    return io + stats + pages + temps


def _chunk_tiles(s, g, kvh, d, dv, bt, max_blocks, q_bytes, kv_bytes):
    """``(tq, pages)``: query positions per block and table entries per
    grid step, from the shapes alone. A query block holds ``tq * g`` rows
    per kv head (the whole chunk when it is small, else a power of two of
    positions); a step covers ``pages`` whole pages. Until the step fits
    ``_CHUNK_VMEM``, pages halve down to 128 tokens a step, then the query
    block, then pages again."""
    tq = s
    if s * g > _CHUNK_ROWS:
        tq = 8
        while 2 * tq * g <= _CHUNK_ROWS:
            tq *= 2
    pages = max(1, min(max_blocks, _CHUNK_KEYS // bt))

    def fits():
        return _chunk_vmem(kvh, tq * g, d, dv, pages * bt, q_bytes,
                           kv_bytes) <= _CHUNK_VMEM

    while not fits():
        if pages > 1 and (pages * bt > 128 or tq <= 8 or tq % 16):
            pages //= 2
        elif tq > 8 and tq % 16 == 0:
            tq //= 2
        else:
            break
    return tq, pages


def _page_head(buf, slot, h, width):
    """Kv head ``h`` of the pages in ``buf[slot]`` as ``(tokens, width)``
    float32; ``buf`` holds pages as stored, ``(bt, kvh, width)``, or in the
    head view, ``(bt, kvh * width)``."""
    if buf.ndim == 5:
        x = buf[slot, :, :, h, :]
    else:
        x = buf[slot, :, :, pl.ds(h * width, width)]
    return x.astype(jnp.float32).reshape(-1, width)


def _paged_chunk_kernel(tab_ref, len_ref, qv_ref, q_ref, k_hbm, v_hbm, o_ref,
                        k_buf, v_buf, sem, q_scr, m_ref, l_ref, acc_ref, *,
                        scale: float, kvh: int):
    """One (row, query block): walk the pages its causal extent covers,
    ``pages`` at a time, double-buffered, all kv heads per step.

    The block is ``tq`` chunk positions of every query head. Row ``r`` of
    kv head ``h``'s scratch is position ``r // g``, query head
    ``h * g + r % g``. A block with no valid query position does no DMA and
    no compute and writes zeros."""
    bi, qi = pl.program_id(0), pl.program_id(1)
    pages, bt = k_buf.shape[1:3]
    tk = pages * bt
    tq, nh, d = q_ref.shape[1:]
    dv = o_ref.shape[-1]
    g = nh // kvh
    rows = tq * g
    length, q_valid = len_ref[bi], qv_ref[bi]
    q_lo = qi * tq
    # keys [0, end): the causal extent of the block's last valid position
    end = length + jnp.minimum(q_lo + tq, q_valid)
    n_pages = jnp.minimum(pl.cdiv(end, bt), tab_ref.shape[1])
    n_steps = pl.cdiv(n_pages, pages)

    def for_pages(step, slot, act):
        """``act`` on the K and V copy of each page of ``step`` inside the
        extent: pages past it are neither copied nor waited on."""
        def one(p, carry):
            page = tab_ref[bi, step * pages + p]
            act(pltpu.make_async_copy(k_hbm.at[page], k_buf.at[slot, p],
                                      sem.at[0, slot]))
            act(pltpu.make_async_copy(v_hbm.at[page], v_buf.at[slot, p],
                                      sem.at[1, slot]))
            return carry
        jax.lax.fori_loop(0, jnp.minimum(n_pages - step * pages, pages), one,
                          0)

    @pl.when(q_lo < q_valid)
    def _run():
        for_pages(0, 0, lambda c: c.start())
        for h in range(kvh):
            q_scr[h] = q_ref[0, :, pl.ds(h * g, g), :].astype(
                jnp.float32).reshape(rows, d)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        # a row sees keys <= its position and < end; padding rows past
        # q_valid thus see the block's whole extent and stay finite
        qpos = length + q_lo + jax.lax.broadcasted_iota(
            jnp.int32, (rows, 1), 0) // g
        last = jnp.minimum(qpos, end - 1)

        def step_body(step, carry):
            slot = step % 2
            for_pages(step, slot, lambda c: c.wait())

            @pl.when(step + 1 < n_steps)
            def _prefetch():
                for_pages(step + 1, 1 - slot, lambda c: c.start())

            key = step * tk + jax.lax.broadcasted_iota(jnp.int32, (rows, tk), 1)
            seen = key <= last
            # slots past the extent hold stale bytes: zero their values so
            # that a masked key adds exactly 0 (never 0 * NaN)
            key_live = (step * tk + jax.lax.broadcasted_iota(
                jnp.int32, (tk, 1), 0)) < end
            for h in range(kvh):
                k = _page_head(k_buf, slot, h, d)
                v = jnp.where(key_live, _page_head(v_buf, slot, h, dv), 0.0)
                s = jax.lax.dot_general(
                    q_scr[h], k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                s = jnp.where(seen, s, NEG_INF)
                m_prev, l_prev = m_ref[h], l_ref[h]               # (rows, 1)
                m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
                alpha = jnp.exp(m_prev - m_new)
                p = jnp.exp(s - m_new)
                l_ref[h] = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
                acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
                    p, v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                m_ref[h] = m_new
            return carry

        jax.lax.fori_loop(0, n_steps, step_body, 0)
        for h in range(kvh):
            out = acc_ref[h] / jnp.maximum(l_ref[h], 1e-30)
            o_ref[0, :, pl.ds(h * g, g), :] = out.reshape(tq, g, dv).astype(
                o_ref.dtype)

    @pl.when(q_lo >= q_valid)
    def _idle():
        o_ref[...] = jnp.zeros_like(o_ref)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_chunk_attention(q, k_pool, v_pool, block_tables, lengths, q_valid,
                          *, scale: float | None = None,
                          interpret: bool = False):
    """Chunked-prefill attention over the paged pool; see the module
    docstring for the contract. Returns ``(b, s, nh, dv)``."""
    b, s, nh, d = q.shape
    bt, kvh = k_pool.shape[1], k_pool.shape[2]
    g = nh // kvh
    dv = v_pool.shape[-1]
    max_blocks = block_tables.shape[1]
    scale = d ** -0.5 if scale is None else scale
    tq, pages = _chunk_tiles(s, g, kvh, d, dv, bt, max_blocks,
                             q.dtype.itemsize, k_pool.dtype.itemsize)
    nq = pl.cdiv(s, tq)
    if nq * tq != s:
        q = jnp.pad(q, ((0, 0), (0, nq * tq - s), (0, 0), (0, 0)))
    # Copy pages as stored, (bt, kvh, d), where the kv heads fill whole
    # packed sublanes: the head view would cost a relayout of both pools.
    # Odd kv heads of a packed dtype (a single one, as in MQA) pad their
    # sublane tile, which a page copy cannot slice: they take the head view.
    pools = (k_pool, v_pool)
    if kvh % (4 // k_pool.dtype.itemsize) or kvh == 1:
        pools = _head_view(k_pool, v_pool)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,          # block_tables, lengths, q_valid
        grid=(b, nq),
        in_specs=[
            pl.BlockSpec((1, tq, nh, d), lambda bi, qi, *_: (bi, qi, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, tq, nh, dv),
                               lambda bi, qi, *_: (bi, qi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, pages) + pools[0].shape[1:], k_pool.dtype),
            pltpu.VMEM((2, pages) + pools[1].shape[1:], v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((kvh, tq * g, d), jnp.float32),
            pltpu.VMEM((kvh, tq * g, 1), jnp.float32),
            pltpu.VMEM((kvh, tq * g, 1), jnp.float32),
            pltpu.VMEM((kvh, tq * g, dv), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_chunk_kernel, scale=scale, kvh=kvh),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, nq * tq, nh, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32),
      q_valid.astype(jnp.int32), q, *pools)
    return out[:, :s]
