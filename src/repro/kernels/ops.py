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
"""
from __future__ import annotations

import collections
from typing import Dict

import jax

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


def _lane_dims(*dims: int) -> bool:
    """Head dims the kernels tile as whole 128-lane blocks."""
    return all(d % 128 == 0 for d in dims)


def flash_attention(q, k, v, *, causal: bool = True, scale: float | None = None):
    if _use_kernel("flash_attention", _lane_dims(q.shape[-1], v.shape[-1])):
        return _fa.flash_attention(q, k, v, causal=causal, scale=scale)
    s, t = q.shape[1], k.shape[1]
    if s * t > 2048 * 2048:
        bq = 2048 if s <= 8192 else 4096
        return _ref.chunked_flash_attention(q, k, v, causal=causal,
                                            scale=scale, block_q=bq, block_k=bq)
    return _ref.flash_attention(q, k, v, causal=causal, scale=scale)


def decode_attention(q, k_cache, v_cache, lengths, *, scale: float | None = None):
    if _use_kernel("decode_attention",
                   _lane_dims(q.shape[-1], v_cache.shape[-1])):
        return _da.decode_attention(q, k_cache, v_cache, lengths, scale=scale)
    return _ref.decode_attention(q, k_cache, v_cache, lengths, scale=scale)


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


def paged_chunk_attention(q, k_pool, v_pool, block_tables, lengths, *,
                          scale: float | None = None):
    """Chunked-prefill attention over pooled KV pages: query j of row r sits
    at logical position ``lengths[r] + j`` and attends over every pooled
    position ``<= lengths[r] + j`` (cached context + causal chunk self).

    No Pallas lowering yet (recorded as ``ref`` on every backend) — the
    chunk pass is prefill-shaped (one big matmul per layer, not memory-bound
    like decode), so the jnp reference compiles to the same XLA fusions as
    whole prefill. Numerics match ``ref.flash_attention`` bitwise so chunked
    K/V + logits reproduce the whole-prompt reference prefill exactly.
    """
    _use_kernel("paged_chunk_attention", False)
    return _ref.paged_chunk_attention(q, k_pool, v_pool, block_tables,
                                      lengths, scale=scale)


def pq_scan(codes, lut):
    if _use_kernel("pq_scan", True):
        return _pq.pq_scan(codes, lut)
    return _ref.pq_scan(codes, lut)
