"""Pallas TPU decode-attention kernel (one new token vs. a padded KV cache).

Decode is memory-bound: the kernel streams K/V tiles HBM->VMEM once, keeps the
(tiny) query tile and the fp32 online-softmax state resident in VMEM, and
masks by per-request cache length. Grid: (batch, kv_heads, kv_blocks) with the
kv dimension minor so scratch carries across tiles.

Interface contract
------------------
``decode_attention(q, k_cache, v_cache, lengths)``

* ``q``       — ``(b, 1, nh, d)`` one new query token per request; GQA
                grouping is ``g = nh // kvh`` (``nh % kvh == 0``).
* ``k_cache`` — ``(b, S, kvh, d)`` contiguous per-request key cache, padded
                to a common ``S``; only rows ``[0, lengths[i])`` are live.
* ``v_cache`` — ``(b, S, kvh, dv)``; ``dv`` may differ from ``d`` (MLA-style
                asymmetric heads).
* ``lengths`` — ``(b,) int32`` valid cache tokens per request. The mask is
                ``pos < lengths``: content at or past ``lengths[i]`` (stale
                pages from a previous slot occupant, zero padding) gets
                probability exactly 0 and can never leak into the output.
                Rows must have ``1 <= lengths[i] <= S`` — a zero-length row
                produces an unspecified garbage row (callers mask dead batch
                slots, they don't zero them).

Returns ``(b, 1, nh, dv)`` in ``q.dtype``. Scores/softmax accumulate in fp32
regardless of cache dtype (``preferred_element_type``), matching the jnp
oracle ``ref.decode_attention`` to fp32 tolerance.

``block_s`` tiles the ``S`` dimension (the cache is zero-padded to a whole
number of tiles when ``S`` is not one); tiles whose start is past
``lengths`` skip compute entirely, so the cost of a short request in a
long-padded batch is proportional to its own length, not to ``S``.
``lengths`` reaches the kernel by scalar prefetch, and the caches are viewed
as ``(b, S, kvh * d)`` so one head's tile is a ``(1, block_s, d)`` block —
legal under the TPU's (8, 128) tiling rule for any ``kvh`` when
``d % 128 == 0``.

The *paged* variant of this kernel — same online-softmax structure, but K/V
gathered through a ``(b, max_blocks)`` block table over a pooled
``(num_blocks, block_tokens, kvh, d)`` cache — lives in
``kernels/paged_attention.py``; see ``docs/architecture.md`` for how the two
relate to the simulator's allocator.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                   *, scale: float, block_s: int):
    bi = pl.program_id(0)
    si = pl.program_id(2)
    ns = pl.num_programs(2)

    @pl.when(si == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[bi]
    s_start = si * block_s

    @pl.when(s_start < length)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)                 # (g, d)
        k = k_ref[0].astype(jnp.float32)                    # (bs, d)
        v = v_ref[0].astype(jnp.float32)                    # (bs, dv)
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


@functools.partial(jax.jit, static_argnames=("scale", "block_s", "interpret"))
def decode_attention(q, k_cache, v_cache, lengths, *, scale: float | None = None,
                     block_s: int = 512, interpret: bool = False):
    """q: (b, 1, nh, d); k/v_cache: (b, S, kvh, d); lengths: (b,) int32."""
    b, _, nh, d = q.shape
    S, kvh = k_cache.shape[1], k_cache.shape[2]
    g = nh // kvh
    dv = v_cache.shape[-1]
    scale = d ** -0.5 if scale is None else scale
    block_s = min(block_s, S)
    ns = pl.cdiv(S, block_s)
    pad = [(0, 0), (0, ns * block_s - S), (0, 0)]
    kf = jnp.pad(k_cache.reshape(b, S, kvh * d), pad)
    vf = jnp.pad(v_cache.reshape(b, S, kvh * dv), pad)

    qr = q.reshape(b, kvh, g, d)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,          # lengths
        grid=(b, kvh, ns),
        in_specs=[
            pl.BlockSpec((1, 1, g, d), lambda bi, hi, si, lens: (bi, hi, 0, 0)),
            pl.BlockSpec((1, block_s, d), lambda bi, hi, si, lens: (bi, si, hi)),
            pl.BlockSpec((1, block_s, dv),
                         lambda bi, hi, si, lens: (bi, si, hi)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, dv),
                               lambda bi, hi, si, lens: (bi, hi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, dv), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, block_s=block_s),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(lengths.astype(jnp.int32), qr, kf, vf)
    return out.reshape(b, 1, nh, dv)
