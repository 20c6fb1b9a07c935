"""Pallas TPU flash-attention (prefill) kernel.

TPU adaptation of FlashAttention: HBM->VMEM tiling via BlockSpec, online
softmax with fp32 running max/denominator kept in VMEM scratch across the
minor (kv) grid dimension, MXU-shaped (128-aligned) tiles. GQA is handled in
the index_map (q-head h reads kv-head h // group).

Layout: the wrapper views ``(b, s, heads, d)`` as ``(b, s, heads * d)`` (a
free reshape), so one head's tile is the block ``(1, block, d)``. Its last
two dims are a multiple of 8 and of 128 whenever ``d % 128 == 0``, which is
the TPU's tiling rule; a ``(1, block, 1, d)`` block over the 4-D array breaks
it for every head count above one. Sequences are zero-padded to a whole
number of tiles; padded keys are masked and padded query rows dropped.

Grid: (batch, q_heads, num_q_blocks, num_kv_blocks) — the kv dimension is the
minor-most so scratch carries across kv steps for a fixed q tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                  *, scale: float, causal: bool, block_q: int, block_k: int,
                  seq_k: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * block_q
    k_start = ki * block_k

    should_run = True
    if causal:
        # skip kv tiles strictly above the causal diagonal
        should_run = k_start <= q_start + block_q - 1

    @pl.when(should_run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)                   # (bq, d)
        k = k_ref[0].astype(jnp.float32)                   # (bk, d)
        v = v_ref[0].astype(jnp.float32)                   # (bk, dv)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        spans_q = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        spans_k = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = spans_k < seq_k
        if causal:
            mask = mask & (spans_k <= spans_q)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                                # (bq, 1)
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _pad_seq(x, n: int):
    if x.shape[1] == n:
        return x
    return jnp.pad(x, [(0, 0), (0, n - x.shape[1]), (0, 0)])


@functools.partial(jax.jit, static_argnames=("causal", "scale", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, scale: float | None = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False):
    """q: (b, s, nh, d), k: (b, t, kvh, d), v: (b, t, kvh, dv).
    Returns (b, s, nh, dv) in ``q.dtype``."""
    b, s, nh, d = q.shape
    t, kvh = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    g = nh // kvh
    scale = d ** -0.5 if scale is None else scale
    # a tile of 16 rows is whole for 32- and 16-bit dtypes alike
    block_q = min(block_q, _round_up(s, 16))
    block_k = min(block_k, _round_up(t, 16))
    s_pad, t_pad = _round_up(s, block_q), _round_up(t, block_k)
    qf = _pad_seq(q.reshape(b, s, nh * d), s_pad)
    kf = _pad_seq(k.reshape(b, t, kvh * d), t_pad)
    vf = _pad_seq(v.reshape(b, t, kvh * dv), t_pad)

    grid = (b, nh, s_pad // block_q, t_pad // block_k)
    out = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, seq_k=t),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bi, hi, qi, ki: (bi, qi, hi)),
            pl.BlockSpec((1, block_k, d),
                         lambda bi, hi, qi, ki: (bi, ki, hi // g)),
            pl.BlockSpec((1, block_k, dv),
                         lambda bi, hi, qi, ki: (bi, ki, hi // g)),
        ],
        out_specs=pl.BlockSpec((1, block_q, dv),
                               lambda bi, hi, qi, ki: (bi, qi, hi)),
        out_shape=jax.ShapeDtypeStruct((b, s_pad, nh * dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qf, kf, vf)
    return out[:, :s].reshape(b, s, nh, dv)
