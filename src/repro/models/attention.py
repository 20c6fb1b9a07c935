"""Attention: GQA/MQA (rope) and MLA (DeepSeek-V2 latent attention).

Prefill paths are causal (or bidirectional for encoder-only); decode paths
consume a static-length KV cache with per-request lengths. The inner
softmax(QK^T)V is routed through ``repro.kernels.ops`` which picks the Pallas
flash kernel on TPU and the jnp reference elsewhere.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import Initializer, apply_rope, init_norm, apply_norm

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_attention(init: Initializer, path: str, cfg: ModelConfig) -> Dict:
    d = cfg.d_model
    hd = cfg.resolved_head_dim
    if cfg.attn_type == "mla":
        m = cfg.mla
        p: Dict = {}
        if m.q_lora_rank:
            p["wdq"] = init.w(f"{path}.wdq", (d, m.q_lora_rank), ("w_embed", "q_lora"))
            p["q_norm"] = init_norm(init, f"{path}.q_norm", cfg, m.q_lora_rank)
            q_in = m.q_lora_rank
        else:
            q_in = d
        p["wuq"] = init.w(
            f"{path}.wuq",
            (q_in, cfg.num_heads, m.qk_nope_head_dim + m.qk_rope_head_dim),
            ("q_lora" if m.q_lora_rank else "w_embed", "heads", "head_dim"),
        )
        p["wdkv"] = init.w(f"{path}.wdkv", (d, m.kv_lora_rank), ("w_embed", "kv_lora"))
        p["wkr"] = init.w(f"{path}.wkr", (d, m.qk_rope_head_dim), ("w_embed", "head_dim"))
        p["kv_norm"] = init_norm(init, f"{path}.kv_norm", cfg, m.kv_lora_rank)
        p["wuk"] = init.w(f"{path}.wuk", (m.kv_lora_rank, cfg.num_heads, m.qk_nope_head_dim),
                          ("kv_lora", "heads", "head_dim"))
        p["wuv"] = init.w(f"{path}.wuv", (m.kv_lora_rank, cfg.num_heads, m.v_head_dim),
                          ("kv_lora", "heads", "head_dim"))
        p["wo"] = init.out(f"{path}.wo", (cfg.num_heads, m.v_head_dim, d),
                           ("heads", "head_dim", "w_embed"),
                           cfg.num_heads * m.v_head_dim)
        return p
    # GQA / MQA / MHA. Baseline tags head_dim with the "head_dim_shard"
    # fallback (takes "model" only when heads couldn't). v2 drops it: rope's
    # rotate-half splits a head_dim-sharded tensor across shards and triggers
    # involuntary resharding, so v2 replicates the (small) attention weights
    # and relies on qseq/cache_seq sharding for the compute instead.
    hd_ax = "head_dim" if cfg.shard_v2 else "head_dim_shard"
    return {
        "wq": init.w(f"{path}.wq", (d, cfg.num_heads, hd),
                     ("w_embed", "heads", hd_ax)),
        "wk": init.w(f"{path}.wk", (d, cfg.num_kv_heads, hd),
                     ("w_embed", "kv_heads", hd_ax)),
        "wv": init.w(f"{path}.wv", (d, cfg.num_kv_heads, hd),
                     ("w_embed", "kv_heads", hd_ax)),
        "wo": init.out(f"{path}.wo", (cfg.num_heads, hd, d),
                       ("heads", hd_ax, "w_embed"), cfg.num_heads * hd),
    }


# ---------------------------------------------------------------------------
# core softmax attention (prefill, batched full-sequence)
# ---------------------------------------------------------------------------

def _sdpa(q, k, v, causal: bool, scale: float):
    """q: (b,s,nh,dq) k: (b,s,kvh,dq) v: (b,s,kvh,dv). GQA-aware reference."""
    from repro.kernels import ops  # lazy: avoids import cycle at module load

    return ops.flash_attention(q, k, v, causal=causal, scale=scale)


def _heads_shardable(cfg: ModelConfig, rules) -> bool:
    if rules is None:
        return True
    m = rules.axis_sizes.get("model", 1)
    return cfg.num_heads % m == 0


def _qseq_constrain(q, cfg, rules):
    """When heads can't shard over 'model', shard the QUERY sequence instead
    so the O(s*t) score computation still splits across the model axis."""
    if rules is None or _heads_shardable(cfg, rules) or q.shape[1] == 1:
        return q
    from repro.models.sharding import constrain
    return constrain(q, rules, ("batch", "attn_qseq", None, None))


def gqa_prefill(params, x, positions, cfg: ModelConfig,
                cache: Optional[Dict] = None,
                rules=None) -> Tuple[jnp.ndarray, Optional[Dict]]:
    """Full-sequence attention. If ``cache`` is given (pre-allocated), the
    computed K/V are written into it (inference prefill)."""
    hd = cfg.resolved_head_dim
    q = jnp.einsum("bsd,dnh->bsnh", x, params["wq"])
    k = jnp.einsum("bsd,dnh->bsnh", x, params["wk"])
    v = jnp.einsum("bsd,dnh->bsnh", x, params["wv"])
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    q = _qseq_constrain(q, cfg, rules)
    out = _sdpa(q, k, v, causal=not cfg.encoder_only, scale=hd ** -0.5)
    new_cache = None
    if cache is not None:
        S = cache["k"].shape[1]
        s = k.shape[1]
        pad = [(0, 0), (0, S - s), (0, 0), (0, 0)]
        new_cache = {
            "k": jnp.pad(k, pad).astype(cache["k"].dtype),
            "v": jnp.pad(v, pad).astype(cache["v"].dtype),
            "length": jnp.full(cache["length"].shape, s, jnp.int32),
        }
    out = jnp.einsum("bsnh,nhd->bsd", out, params["wo"])
    return out, new_cache


def gqa_decode(params, x, cfg: ModelConfig, cache: Dict) -> Tuple[jnp.ndarray, Dict]:
    """One-token decode against a static-length cache.

    x: (b, 1, d); cache k/v: (b, S, kvh, hd); cache["length"]: (b,) current
    number of valid tokens (the new token is written at that index). A paged
    cache (``k_pool`` present — see ``paged_cache_spec``) routes to
    ``gqa_decode_paged`` instead.
    """
    from repro.kernels import ops

    if "k_pool" in cache:
        return gqa_decode_paged(params, x, cfg, cache)
    hd = cfg.resolved_head_dim
    lengths = cache["length"]
    q = jnp.einsum("bsd,dnh->bsnh", x, params["wq"])
    k = jnp.einsum("bsd,dnh->bsnh", x, params["wk"])
    v = jnp.einsum("bsd,dnh->bsnh", x, params["wv"])
    pos = lengths[:, None]
    q = apply_rope(q, pos, cfg.rope_theta)
    k = apply_rope(k, pos, cfg.rope_theta)

    def upd(buf, new):
        def one(b, n, i):
            return jax.lax.dynamic_update_slice(b, n.astype(b.dtype), (i, 0, 0))
        return jax.vmap(one)(buf, new, lengths)

    k_cache = upd(cache["k"], k)
    v_cache = upd(cache["v"], v)
    out = ops.decode_attention(q, k_cache, v_cache, lengths + 1, scale=hd ** -0.5)
    out = jnp.einsum("bsnh,nhd->bsd", out, params["wo"])
    return out, {"k": k_cache, "v": v_cache, "length": lengths + 1}


def gqa_decode_paged(params, x, cfg: ModelConfig,
                     cache: Dict) -> Tuple[jnp.ndarray, Dict]:
    """One-token decode against a *paged* cache (block-table-indexed pool).

    The per-layer cache (see ``paged_cache_spec``) holds shared physical
    pools ``k_pool``/``v_pool`` of shape ``(num_pages, block_tokens, kvh,
    hd)`` plus per-request indirection: ``block_tables`` ``(b, max_blocks)``
    and ``length`` ``(b,)``. The new token's K/V is written at logical
    position ``length`` — physical slot ``(block_tables[i, length // bt],
    length % bt)`` — so the caller (the paged ``Engine``) must have grown the
    table to cover that position *before* the step, and must guarantee the
    written page is unshared (refcount 1). Dead batch rows follow the same
    contract as the dense path: their table points at the engine's trash page
    and their output row is garbage the caller ignores.
    """
    from repro.kernels import ops

    hd = cfg.resolved_head_dim
    lengths = cache["length"]
    tables = cache["block_tables"]
    k_pool, v_pool = cache["k_pool"], cache["v_pool"]
    bt, mb = k_pool.shape[1], tables.shape[1]
    q = jnp.einsum("bsd,dnh->bsnh", x, params["wq"])
    k = jnp.einsum("bsd,dnh->bsnh", x, params["wk"])
    v = jnp.einsum("bsd,dnh->bsnh", x, params["wv"])
    pos = lengths[:, None]
    q = apply_rope(q, pos, cfg.rope_theta)
    k = apply_rope(k, pos, cfg.rope_theta)

    blk = jnp.take_along_axis(tables,
                              jnp.minimum(lengths // bt, mb - 1)[:, None],
                              axis=1)[:, 0]
    slot = blk * bt + lengths % bt                 # (b,) flat pool row

    def upd(pool, new):
        flat = pool.reshape(-1, *pool.shape[2:])
        flat = flat.at[slot].set(new[:, 0].astype(pool.dtype))
        return flat.reshape(pool.shape)

    k_pool = upd(k_pool, k)
    v_pool = upd(v_pool, v)
    out = ops.paged_decode_attention(q, k_pool, v_pool, tables, lengths + 1,
                                     scale=hd ** -0.5)
    out = jnp.einsum("bsnh,nhd->bsd", out, params["wo"])
    return out, {"k_pool": k_pool, "v_pool": v_pool, "block_tables": tables,
                 "length": lengths + 1}


def gqa_prefill_paged(params, x, cfg: ModelConfig, cache: Dict,
                      q_valid: jnp.ndarray) -> Tuple[jnp.ndarray, Dict]:
    """Prefill a CHUNK of each request against a *paged* cache (the
    continuation-state path of chunked prefill).

    x: (b, s, d) — row ``r`` carries ``q_valid[r]`` valid chunk tokens
    (left-aligned; the rest is padding). The chunk starts at logical
    position ``cache["length"][r]``, i.e. everything before it is already
    written in the pools and serves as attention context. Chunk K/V is
    scattered into the pools first, then each query attends over cached
    context + the causal part of its own chunk via
    ``ops.paged_chunk_attention``.

    Write-safety contract: positions ``j >= q_valid[r]`` (padding, decode
    rows riding along with ``q_valid == 0``, dead rows) are routed to the
    trash page — by convention the LAST pool page — so a mixed iteration
    can never corrupt live pages. Valid positions may target prefix-shared
    pages (refcount > 1): sharers rewrite matched blocks bitwise
    identically (aliasing dedups memory, not compute), so concurrent
    readers of those pages are unperturbed.

    Off the TPU, numerics match whole-prompt ``gqa_prefill`` bitwise: same
    einsum/rope recipe per position, and the chunk attention reference
    mirrors ``flash_attention``'s fp32 path with exact-zero masked tails.
    On the TPU the Pallas chunk kernel walks only the pages each row with
    ``q_valid > 0`` covers.
    """
    from repro.kernels import ops

    hd = cfg.resolved_head_dim
    lengths = cache["length"]
    tables = cache["block_tables"]
    k_pool, v_pool = cache["k_pool"], cache["v_pool"]
    bt, mb = k_pool.shape[1], tables.shape[1]
    b, s, _ = x.shape
    j = jnp.arange(s)[None, :]
    pos = lengths[:, None] + j                       # (b, s) logical pos
    valid_q = j < q_valid[:, None]                   # (b, s)

    q = jnp.einsum("bsd,dnh->bsnh", x, params["wq"])
    k = jnp.einsum("bsd,dnh->bsnh", x, params["wk"])
    v = jnp.einsum("bsd,dnh->bsnh", x, params["wv"])
    q = apply_rope(q, pos, cfg.rope_theta)
    k = apply_rope(k, pos, cfg.rope_theta)

    blk = jnp.take_along_axis(tables, jnp.clip(pos // bt, 0, mb - 1), axis=1)
    trash = k_pool.shape[0] - 1
    slot = jnp.where(valid_q, blk * bt + pos % bt, trash * bt + j % bt)

    def upd(pool, new):
        flat = pool.reshape(-1, *pool.shape[2:])
        flat = flat.at[slot.reshape(-1)].set(
            new.reshape(b * s, *new.shape[2:]).astype(pool.dtype))
        return flat.reshape(pool.shape)

    k_pool = upd(k_pool, k)
    v_pool = upd(v_pool, v)
    out = ops.paged_chunk_attention(q, k_pool, v_pool, tables, lengths,
                                    scale=hd ** -0.5, q_valid=q_valid)
    out = jnp.einsum("bsnh,nhd->bsd", out, params["wo"])
    return out, {"k_pool": k_pool, "v_pool": v_pool, "block_tables": tables,
                 "length": lengths + q_valid}


def gqa_verify_paged(params, x, cfg: ModelConfig, cache: Dict,
                     q_valid: jnp.ndarray) -> Tuple[jnp.ndarray, Dict]:
    """Speculative-verify pass against a *paged* cache: row ``r`` carries
    ``q_valid[r]`` feed tokens — the last committed token plus its draft
    continuation — occupying logical positions ``cache["length"][r] + j``.

    Scatter recipe (rope positions, trash-page routing of padding and dead
    rows, flat-slot K/V writes) is exactly ``gqa_prefill_paged``'s; the
    attention is ``ops.paged_verify_attention``, whose position ``j``
    output is bit-identical to a one-token ``gqa_decode_paged`` at the same
    position. That makes the verify logits for position ``j`` — given the
    same committed stream — bitwise equal to sequential decode logits, the
    property the engine's spec-vs-plain stream-equality contract rests on.

    The caller must have fork-grown the table to cover ``length + q_valid``
    slots, with every block in the write range private (refcount 1,
    unregistered) — ``PagedKVStore.fork_table`` guarantees both. Rejected
    positions' writes stay as garbage beyond the committed length; the
    exact-zero mask means no later pass can observe them, and
    ``commit_fork`` trims the pages they rode in on.
    """
    from repro.kernels import ops

    hd = cfg.resolved_head_dim
    lengths = cache["length"]
    tables = cache["block_tables"]
    k_pool, v_pool = cache["k_pool"], cache["v_pool"]
    bt, mb = k_pool.shape[1], tables.shape[1]
    b, s, _ = x.shape
    j = jnp.arange(s)[None, :]
    pos = lengths[:, None] + j                       # (b, s) logical pos
    valid_q = j < q_valid[:, None]                   # (b, s)

    q = jnp.einsum("bsd,dnh->bsnh", x, params["wq"])
    k = jnp.einsum("bsd,dnh->bsnh", x, params["wk"])
    v = jnp.einsum("bsd,dnh->bsnh", x, params["wv"])
    q = apply_rope(q, pos, cfg.rope_theta)
    k = apply_rope(k, pos, cfg.rope_theta)

    blk = jnp.take_along_axis(tables, jnp.clip(pos // bt, 0, mb - 1), axis=1)
    trash = k_pool.shape[0] - 1
    slot = jnp.where(valid_q, blk * bt + pos % bt, trash * bt + j % bt)

    def upd(pool, new):
        flat = pool.reshape(-1, *pool.shape[2:])
        flat = flat.at[slot.reshape(-1)].set(
            new.reshape(b * s, *new.shape[2:]).astype(pool.dtype))
        return flat.reshape(pool.shape)

    k_pool = upd(k_pool, k)
    v_pool = upd(v_pool, v)
    out = ops.paged_verify_attention(q, k_pool, v_pool, tables, lengths,
                                     scale=hd ** -0.5)
    out = jnp.einsum("bsnh,nhd->bsd", out, params["wo"])
    return out, {"k_pool": k_pool, "v_pool": v_pool, "block_tables": tables,
                 "length": lengths + q_valid}


# ---------------------------------------------------------------------------
# MLA
# ---------------------------------------------------------------------------

def _mla_qkv_prefill(params, x, positions, cfg: ModelConfig):
    m = cfg.mla
    if m.q_lora_rank:
        cq = apply_norm(params["q_norm"], x @ params["wdq"], cfg)
    else:
        cq = x
    q = jnp.einsum("bsd,dnh->bsnh", cq, params["wuq"])
    q_nope, q_rope = q[..., : m.qk_nope_head_dim], q[..., m.qk_nope_head_dim:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    c_kv = apply_norm(params["kv_norm"], x @ params["wdkv"], cfg)
    k_rope = apply_rope((x @ params["wkr"])[:, :, None, :], positions, cfg.rope_theta)
    return q_nope, q_rope, c_kv, k_rope


def mla_prefill(params, x, positions, cfg: ModelConfig,
                cache: Optional[Dict] = None,
                rules=None) -> Tuple[jnp.ndarray, Optional[Dict]]:
    m = cfg.mla
    q_nope, q_rope, c_kv, k_rope = _mla_qkv_prefill(params, x, positions, cfg)
    k_nope = jnp.einsum("bsl,lnh->bsnh", c_kv, params["wuk"])
    v = jnp.einsum("bsl,lnh->bsnh", c_kv, params["wuv"])
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_rope, (*k_nope.shape[:3], m.qk_rope_head_dim))], axis=-1)
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    q = _qseq_constrain(q, cfg, rules)
    out = _sdpa(q, k, v, causal=not cfg.encoder_only, scale=scale)
    new_cache = None
    if cache is not None:
        S = cache["c_kv"].shape[1]
        s = c_kv.shape[1]
        new_cache = {
            "c_kv": jnp.pad(c_kv, [(0, 0), (0, S - s), (0, 0)]).astype(cache["c_kv"].dtype),
            "k_rope": jnp.pad(k_rope[:, :, 0, :], [(0, 0), (0, S - s), (0, 0)]).astype(
                cache["k_rope"].dtype),
            "length": jnp.full(cache["length"].shape, s, jnp.int32),
        }
    out = jnp.einsum("bsnh,nhd->bsd", out, params["wo"])
    return out, new_cache


def mla_decode(params, x, cfg: ModelConfig, cache: Dict) -> Tuple[jnp.ndarray, Dict]:
    """One-token MLA decode. Baseline path re-expands K/V from the latent
    cache; ``cfg.mla.absorb`` switches to the absorbed (latent-space) path,
    which never materializes per-head K/V — the DeepSeek-V2 serving trick."""
    m = cfg.mla
    lengths = cache["length"]
    pos = lengths[:, None]
    if m.q_lora_rank:
        cq = apply_norm(params["q_norm"], x @ params["wdq"], cfg)
    else:
        cq = x
    q = jnp.einsum("bsd,dnh->bsnh", cq, params["wuq"])
    q_nope, q_rope = q[..., : m.qk_nope_head_dim], q[..., m.qk_nope_head_dim:]
    q_rope = apply_rope(q_rope, pos, cfg.rope_theta)

    c_kv_new = apply_norm(params["kv_norm"], x @ params["wdkv"], cfg)  # (b,1,l)
    k_rope_new = apply_rope((x @ params["wkr"])[:, :, None, :], pos, cfg.rope_theta)[:, :, 0, :]

    def upd(buf, new):
        def one(b, n, i):
            return jax.lax.dynamic_update_slice(b, n.astype(b.dtype), (i, 0))
        return jax.vmap(one)(buf, new, lengths)

    c_kv = upd(cache["c_kv"], c_kv_new)          # (b,S,l)
    k_rope = upd(cache["k_rope"], k_rope_new)    # (b,S,r)
    S = c_kv.shape[1]
    valid = jnp.arange(S)[None, :] < (lengths + 1)[:, None]
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5

    if m.absorb:
        # q_nope -> latent space: (b,1,n,h) x (l,n,h) -> (b,1,n,l)
        q_lat = jnp.einsum("bsnh,lnh->bsnl", q_nope, params["wuk"])
        scores = (jnp.einsum("bsnl,bSl->bnS", q_lat, c_kv)
                  + jnp.einsum("bsnh,bSh->bnS", q_rope, k_rope)) * scale
        scores = jnp.where(valid[:, None, :], scores.astype(jnp.float32), NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1).astype(c_kv.dtype)
        o_lat = jnp.einsum("bnS,bSl->bnl", probs, c_kv)
        out = jnp.einsum("bnl,lnh->bnh", o_lat, params["wuv"])[:, None]
    else:
        k_nope = jnp.einsum("bSl,lnh->bSnh", c_kv, params["wuk"])
        v = jnp.einsum("bSl,lnh->bSnh", c_kv, params["wuv"])
        scores = (jnp.einsum("bsnh,bSnh->bnS", q_nope, k_nope)
                  + jnp.einsum("bsnh,bSh->bnS", q_rope, k_rope)) * scale
        scores = jnp.where(valid[:, None, :], scores.astype(jnp.float32), NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        out = jnp.einsum("bnS,bSnh->bnh", probs, v)[:, None]
    out = jnp.einsum("bsnh,nhd->bsd", out, params["wo"])
    return out, {"c_kv": c_kv, "k_rope": k_rope, "length": lengths + 1}


# ---------------------------------------------------------------------------
# cache factories (shapes only; used for both allocation and ShapeDtypeStructs)
# ---------------------------------------------------------------------------

def cache_spec(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    """Abstract KV-cache entry for ONE attention layer."""
    if cfg.attn_type == "mla":
        m = cfg.mla
        return {
            "c_kv": jax.ShapeDtypeStruct((batch, max_len, m.kv_lora_rank), dtype),
            "k_rope": jax.ShapeDtypeStruct((batch, max_len, m.qk_rope_head_dim), dtype),
            "length": jax.ShapeDtypeStruct((batch,), jnp.int32),
        }
    hd = cfg.resolved_head_dim
    return {
        "k": jax.ShapeDtypeStruct((batch, max_len, cfg.num_kv_heads, hd), dtype),
        "v": jax.ShapeDtypeStruct((batch, max_len, cfg.num_kv_heads, hd), dtype),
        "length": jax.ShapeDtypeStruct((batch,), jnp.int32),
    }


def paged_cache_spec(cfg: ModelConfig, num_pages: int, block_tokens: int,
                     batch: int, max_blocks: int, dtype=jnp.bfloat16):
    """Abstract *paged* KV-cache entry for ONE attention layer.

    ``num_pages`` counts every physical page in the pool, including any
    sentinel/trash page the engine reserves; block-table entries must index
    into ``[0, num_pages)``. MLA's latent cache is not paged yet (the paged
    engine serves GQA-family models only)."""
    if cfg.attn_type == "mla":
        raise NotImplementedError("paged KV cache supports gqa/mqa/mha only")
    hd = cfg.resolved_head_dim
    return {
        "k_pool": jax.ShapeDtypeStruct(
            (num_pages, block_tokens, cfg.num_kv_heads, hd), dtype),
        "v_pool": jax.ShapeDtypeStruct(
            (num_pages, block_tokens, cfg.num_kv_heads, hd), dtype),
        "block_tables": jax.ShapeDtypeStruct((batch, max_blocks), jnp.int32),
        "length": jax.ShapeDtypeStruct((batch,), jnp.int32),
    }


def cache_axes(cfg: ModelConfig, seq_sharded: bool = False):
    seq_ax = "cache_seq" if cfg.shard_v2 else "seq"
    if cfg.attn_type == "mla":
        return {
            "c_kv": ("batch", seq_ax, "kv_lora"),
            "k_rope": ("batch", seq_ax, None),
            "length": ("batch",),
        }
    hd_ax = None if cfg.shard_v2 else "head_dim_shard"
    return {
        "k": ("batch", seq_ax, "kv_heads", hd_ax),
        "v": ("batch", seq_ax, "kv_heads", hd_ax),
        "length": ("batch",),
    }
