"""Mixture-of-Experts (DeepSeek-V2 style: shared + routed top-k experts).

Two interchangeable implementations:

* ``ragged_ep`` (default): sort-by-expert + ``jax.lax.ragged_dot`` so compiled
  FLOPs track *routed* work only. Expert weights are sharded over the
  ``model`` mesh axis (expert parallelism) via ``shard_map``; each shard
  computes its local experts' contribution for its tokens and the results are
  combined with a single psum — no GShard dispatch einsum, no all_to_all of
  activations.
* ``dispatch_einsum``: the classic GShard capacity-based dispatch/combine
  einsum formulation, kept as the well-trodden baseline for §Perf comparisons.

Both are validated against a dense loop-over-experts oracle in tests.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.layers import Initializer, init_mlp, apply_mlp


def init_moe(init: Initializer, path: str, cfg: ModelConfig) -> Dict:
    d = cfg.d_model
    m = cfg.moe
    f = m.expert_d_ff
    glu = cfg.mlp_type in ("swiglu", "geglu")
    p = {
        "router": init.w(f"{path}.router", (d, m.num_experts), ("w_embed", "experts"),
                         scale=d ** -0.5),
        "wi": init.w(f"{path}.wi", (m.num_experts, d, (2 * f if glu else f)),
                     ("experts", "w_embed", "ff")),
        "wo": init.out(f"{path}.wo", (m.num_experts, f, d),
                       ("experts", "ff", "w_embed"), f),
    }
    if m.num_shared_experts:
        p["shared"] = init_mlp(init, f"{path}.shared", cfg, d_ff=m.shared_d_ff)
    return p


def _activate(h, cfg: ModelConfig):
    if cfg.mlp_type in ("swiglu", "geglu"):
        gate, up = jnp.split(h, 2, axis=-1)
        act = jax.nn.silu(gate) if cfg.mlp_type == "swiglu" else jax.nn.gelu(gate)
        return act * up
    if cfg.mlp_type == "relu2":
        return jnp.square(jax.nn.relu(h))
    return jax.nn.gelu(h)


def _router(params, x2d, cfg: ModelConfig):
    """x2d: (T, d) -> (weights (T,k), idx (T,k), aux_loss scalar)."""
    m = cfg.moe
    logits = (x2d.astype(jnp.float32) @ params["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    weights, idx = jax.lax.top_k(probs, m.top_k)
    weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)
    # switch-style load-balancing aux loss
    density = jnp.mean(jax.nn.one_hot(idx, m.num_experts, dtype=jnp.float32), axis=(0, 1))
    mean_probs = jnp.mean(probs, axis=0)
    aux = m.num_experts * jnp.sum(density * mean_probs) * m.aux_loss_coef
    return weights, idx, aux


# ---------------------------------------------------------------------------
# ragged_dot implementation (per-shard local compute)
# ---------------------------------------------------------------------------

def _moe_local(x2d, wi, wo, weights, idx, cfg: ModelConfig,
               expert_offset: int, num_local: int, capacity: int):
    """Contribution of experts [offset, offset+num_local) to all tokens.

    x2d: (T, d); wi: (num_local, d, F); wo: (num_local, f, d);
    weights/idx: (T, k). Returns (T, d).
    """
    T, d = x2d.shape
    k = idx.shape[1]
    rows = T * k
    eid = idx.reshape(rows)
    tok = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    w = weights.reshape(rows)

    local = (eid >= expert_offset) & (eid < expert_offset + num_local)
    local_eid = jnp.where(local, eid - expert_offset, num_local)
    order = jnp.argsort(local_eid, stable=True)          # local rows first, by expert
    capacity = min(capacity, rows)
    take = order[:capacity]
    e_sel = local_eid[take]
    x_sel = x2d[tok[take]]
    w_sel = w[take]

    counts = jnp.bincount(local_eid, length=num_local + 1)[:num_local]
    # cap overflow: experts later in the sort may exceed capacity
    cum = jnp.cumsum(counts)
    gs = jnp.clip(counts - jnp.maximum(cum - capacity, 0), 0, None)
    valid_rows = jnp.arange(capacity) < jnp.sum(gs)

    h = jax.lax.ragged_dot(x_sel, wi, gs.astype(jnp.int32))
    h = _activate(h, cfg)
    y = jax.lax.ragged_dot(h, wo, gs.astype(jnp.int32))
    y = jnp.where(valid_rows[:, None], y, 0.0) * w_sel[:, None].astype(y.dtype)
    out = jnp.zeros((T, d), y.dtype).at[tok[take]].add(y)
    return out


def _capacity(tokens: int, k: int, num_experts: int, num_local: int, slack: float) -> int:
    expected = tokens * k * num_local / max(1, num_experts)
    cap = int(math.ceil(expected * slack))
    cap = max(cap, k)
    return min(max(cap, 8), tokens * k)


def moe_ragged(params, x, cfg: ModelConfig, mesh=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (..., d) -> (same shape, aux_loss). EP over 'model' if present."""
    m = cfg.moe
    shape = x.shape
    x2d = x.reshape(-1, shape[-1])
    T = x2d.shape[0]

    ep = (mesh is not None and "model" in mesh.axis_names
          and mesh.shape["model"] > 1 and m.num_experts % mesh.shape["model"] == 0)
    if not ep:
        weights, idx, aux = _router(params, x2d, cfg)
        cap = _capacity(T, m.top_k, m.num_experts, m.num_experts, m.capacity_slack)
        out = _moe_local(x2d, params["wi"], params["wo"], weights, idx, cfg,
                         0, m.num_experts, cap)
        return out.reshape(shape).astype(x.dtype), aux

    n_model = mesh.shape["model"]
    num_local = m.num_experts // n_model
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    T_local = T // math.prod(mesh.shape[a] for a in dp_axes) if dp_axes else T
    cap = _capacity(max(T_local, 1), m.top_k, m.num_experts, num_local, m.capacity_slack)

    def shard_fn(x_l, router_w, wi_l, wo_l):
        midx = jax.lax.axis_index("model")
        weights, idx, aux = _router({"router": router_w}, x_l, cfg)
        out = _moe_local(x_l, wi_l, wo_l, weights, idx, cfg,
                         midx * num_local, num_local, cap)
        out = jax.lax.psum(out, "model")
        aux = jax.lax.pmean(aux, "model")
        return out, aux

    xs = P(dp_axes if dp_axes else None, None)
    out, aux = shard_map(
        shard_fn, mesh=mesh,
        in_specs=(xs, P(None, None), P("model", None, None), P("model", None, None)),
        out_specs=(xs, P()),
        check_vma=False,
    )(x2d, params["router"], params["wi"], params["wo"])
    return out.reshape(shape).astype(x.dtype), jnp.mean(aux)


# ---------------------------------------------------------------------------
# GShard dispatch-einsum implementation (baseline)
# ---------------------------------------------------------------------------

def moe_dispatch_einsum(params, x, cfg: ModelConfig, mesh=None,
                        group_size: int = 4096) -> Tuple[jnp.ndarray, jnp.ndarray]:
    m = cfg.moe
    shape = x.shape
    x2d = x.reshape(-1, shape[-1])
    T, d = x2d.shape
    weights, idx, aux = _router(params, x2d, cfg)

    g_sz = min(group_size, T)
    n_groups = T // g_sz if T % g_sz == 0 else 1
    if T % g_sz != 0:
        g_sz = T
    xg = x2d.reshape(n_groups, g_sz, d)
    wg = weights.reshape(n_groups, g_sz, m.top_k)
    ig = idx.reshape(n_groups, g_sz, m.top_k)

    mean_load = g_sz * m.top_k / m.num_experts
    cap_per_e = min(max(int(math.ceil(mean_load * m.capacity_slack)), 4),
                    g_sz * m.top_k)

    # assignment granularity: a = (s, k) flattened so slots never collide
    a_sz = g_sz * m.top_k
    onehot = jax.nn.one_hot(ig.reshape(n_groups, a_sz), m.num_experts,
                            dtype=jnp.float32)                   # (g,a,e)
    pos = jnp.cumsum(onehot, axis=1) - onehot                    # slot per expert
    posidx = jnp.sum(pos * onehot, axis=-1)                      # (g,a)
    keep = (posidx < cap_per_e).astype(jnp.float32)
    slot = jax.nn.one_hot(posidx, cap_per_e, dtype=jnp.float32)  # (g,a,c)
    disp_a = onehot[:, :, :, None] * slot[:, :, None, :] * keep[:, :, None, None]
    disp_a = disp_a.reshape(n_groups, g_sz, m.top_k, m.num_experts, cap_per_e)
    dispatch = jnp.sum(disp_a, axis=2)                           # (g,s,e,c)
    combine = jnp.einsum("gskec,gsk->gsec", disp_a, wg.astype(jnp.float32))

    xd = jnp.einsum("gsec,gsd->gecd", dispatch.astype(x.dtype), xg)
    h = jnp.einsum("gecd,edf->gecf", xd, params["wi"])
    h = _activate(h, cfg)
    y = jnp.einsum("gecf,efd->gecd", h, params["wo"])
    out = jnp.einsum("gsec,gecd->gsd", combine.astype(y.dtype), y)
    return out.reshape(shape).astype(x.dtype), aux


def apply_moe(params, x, cfg: ModelConfig, mesh=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    if cfg.moe.impl == "dispatch_einsum":
        out, aux = moe_dispatch_einsum(params, x, cfg, mesh)
    else:
        out, aux = moe_ragged(params, x, cfg, mesh)
    if cfg.moe.num_shared_experts:
        out = out + apply_mlp(params["shared"], x, cfg)
    return out, aux


def moe_reference(params, x, cfg: ModelConfig) -> jnp.ndarray:
    """Dense loop-over-experts oracle (no capacity drops). Tests only."""
    m = cfg.moe
    shape = x.shape
    x2d = x.reshape(-1, shape[-1]).astype(jnp.float32)
    weights, idx, _ = _router(params, x2d, cfg)
    out = jnp.zeros_like(x2d)
    for e in range(m.num_experts):
        h = x2d @ params["wi"][e].astype(jnp.float32)
        h = _activate(h, cfg)
        y = h @ params["wo"][e].astype(jnp.float32)
        w_e = jnp.sum(jnp.where(idx == e, weights, 0.0), axis=-1)
        out = out + y * w_e[:, None]
    if m.num_shared_experts:
        out = out + apply_mlp(params["shared"], x2d.astype(x.dtype), cfg).astype(jnp.float32)
    return out.reshape(shape).astype(x.dtype)
