"""jit'd public wrappers around the Pallas kernels with explicit dispatch.

On a TPU each op runs its compiled Pallas kernel when the shapes meet that
kernel's tiling rule (head dims a multiple of 128 — see each kernel's module
docstring), and the pure-jnp reference otherwise; on any other backend every
op runs the reference. There is no other switch: interpret mode is reachable
only through a kernel's own ``interpret=True`` argument, as the tests pass
it.

Every choice is counted in ``DISPATCH`` as ``(op, "pallas" | "ref")`` when
the op is traced (once per compiled program, not per call), so a run can show
which implementation each op of its path took (``dispatch_record``).

Each op runs under ``jax.named_scope(<op name>)``, kernel and reference
alike, so the compiled program's ``op_name`` metadata names the op every
instruction belongs to (``bench/enginetrace.py`` reads it to name device ops).
"""
from __future__ import annotations

import collections
import functools
from typing import Dict

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels import flash_attention as _fa
from repro.kernels import decode_attention as _da
from repro.kernels import paged_attention as _pa
from repro.kernels import pq_scan as _pq

DISPATCH: collections.Counter = collections.Counter()


def dispatch_record() -> Dict[str, Dict[str, int]]:
    """``{op: {impl: traces}}`` for every op traced since the last reset."""
    out: Dict[str, Dict[str, int]] = {}
    for (op, impl), n in sorted(DISPATCH.items()):
        out.setdefault(op, {})[impl] = n
    return out


def _platform() -> str:
    return jax.default_backend()


def _use_kernel(op: str, fits: bool) -> bool:
    use = fits and _platform() == "tpu"
    DISPATCH[(op, "pallas" if use else "ref")] += 1
    return use


def _scoped(op):
    """Run ``op`` under a named scope of its own name."""
    @functools.wraps(op)
    def run(*args, **kw):
        with jax.named_scope(op.__name__):
            return op(*args, **kw)
    return run


def _lane_dims(*dims: int) -> bool:
    """Head dims the kernels tile as whole 128-lane blocks."""
    return all(d % 128 == 0 for d in dims)


@_scoped
def flash_attention(q, k, v, *, causal: bool = True, scale: float | None = None):
    if _use_kernel("flash_attention", _lane_dims(q.shape[-1], v.shape[-1])):
        return _fa.flash_attention(q, k, v, causal=causal, scale=scale)
    s, t = q.shape[1], k.shape[1]
    if s * t > 2048 * 2048:
        bq = 2048 if s <= 8192 else 4096
        return _ref.chunked_flash_attention(q, k, v, causal=causal,
                                            scale=scale, block_q=bq, block_k=bq)
    return _ref.flash_attention(q, k, v, causal=causal, scale=scale)


@_scoped
def decode_attention(q, k_cache, v_cache, lengths, *, scale: float | None = None):
    if _use_kernel("decode_attention",
                   _lane_dims(q.shape[-1], v_cache.shape[-1])):
        return _da.decode_attention(q, k_cache, v_cache, lengths, scale=scale)
    return _ref.decode_attention(q, k_cache, v_cache, lengths, scale=scale)


@_scoped
def paged_decode_attention(q, k_pool, v_pool, block_tables, lengths, *,
                           scale: float | None = None):
    """Block-table-indexed decode attention over pooled KV pages (see
    ``kernels.paged_attention`` for the layout contract)."""
    if _use_kernel("paged_decode_attention",
                   _lane_dims(q.shape[-1], v_pool.shape[-1])):
        return _pa.paged_decode_attention(q, k_pool, v_pool, block_tables,
                                          lengths, scale=scale)
    return _ref.paged_decode_attention(q, k_pool, v_pool, block_tables,
                                       lengths, scale=scale)


@_scoped
def paged_verify_attention(q, k_pool, v_pool, block_tables, lengths, *,
                           scale: float | None = None):
    """Speculative-verify attention: score all s = k+1 draft positions of
    each row in one pass over the block table (query j sits at logical
    position ``lengths + j``). The reference unrolls into per-position
    ``decode_attention`` calls, which makes each position bit-identical to a
    sequential paged decode at the same position — the property the engine's
    spec-vs-plain stream-equality contract rests on."""
    if _use_kernel("paged_verify_attention",
                   _lane_dims(q.shape[-1], v_pool.shape[-1])):
        return _pa.paged_verify_attention(q, k_pool, v_pool, block_tables,
                                          lengths, scale=scale)
    return _ref.paged_verify_attention(q, k_pool, v_pool, block_tables,
                                       lengths, scale=scale)


@_scoped
def paged_chunk_attention(q, k_pool, v_pool, block_tables, lengths, *,
                          scale: float | None = None, q_valid=None):
    """Chunked-prefill attention over pooled KV pages: query j of row r sits
    at logical position ``lengths[r] + j`` and attends over every pooled
    position ``<= lengths[r] + j`` (cached context + causal chunk self).

    ``q_valid`` (b,) counts each row's valid chunk positions (default: all
    ``s``). The kernel walks only the pages a live row's extent covers and
    skips rows with ``q_valid == 0``; outputs at positions ``>= q_valid``
    are unspecified. The reference ignores ``q_valid`` and scores every
    row's whole table: its numerics match ``ref.flash_attention`` bitwise,
    so off the TPU chunked K/V and logits reproduce whole prefill exactly.
    """
    if _use_kernel("paged_chunk_attention",
                   _lane_dims(q.shape[-1], v_pool.shape[-1])):
        if q_valid is None:
            q_valid = jnp.full(q.shape[:1], q.shape[1], jnp.int32)
        return _pa.paged_chunk_attention(q, k_pool, v_pool, block_tables,
                                         lengths, q_valid, scale=scale)
    return _ref.paged_chunk_attention(q, k_pool, v_pool, block_tables,
                                      lengths, scale=scale)


@_scoped
def pq_scan(codes, lut):
    if _use_kernel("pq_scan", True):
        return _pq.pq_scan(codes, lut)
    return _ref.pq_scan(codes, lut)
