"""Shared layers: initializer/axes recorder, norms, RoPE, MLP variants."""
from __future__ import annotations

import hashlib
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig


def _path_key(root_key, path: str):
    h = int.from_bytes(hashlib.sha256(path.encode()).digest()[:4], "little")
    return jax.random.fold_in(root_key, h)


class Initializer:
    """Creates parameters and records their logical sharding axes.

    The same code path builds both real parameters (under ``init``) and
    abstract ones (under ``jax.eval_shape``); the axes dict is a Python-side
    effect so it is populated either way.
    """

    def __init__(self, cfg: ModelConfig, key, zero_out: bool = True):
        self.cfg = cfg
        self.key = key
        self.zero_out = zero_out
        self.dtype = jnp.dtype(cfg.param_dtype)
        self.axes: Dict[str, Tuple] = {}

    def w(self, path: str, shape, axes, scale: Optional[float] = None):
        """Dense weight, truncated-normal fan-in init."""
        assert len(shape) == len(axes), (path, shape, axes)
        self.axes[path] = tuple(axes)
        if scale is None:
            fan_in = shape[0] if len(shape) >= 2 else shape[-1]
            scale = 1.0 / np.sqrt(max(1, fan_in))
        k = _path_key(self.key, path)
        return (jax.random.truncated_normal(k, -2.0, 2.0, shape, jnp.float32)
                * scale).astype(self.dtype)

    def out(self, path: str, shape, axes, fan_in: int):
        """Output projection (attention, MLP, expert ``wo``): zero under the
        training init (``zero_out``), so every block starts as the identity;
        fan-in normal otherwise, so random weights exercise every block."""
        if self.zero_out:
            return self.z(path, shape, axes)
        return self.w(path, shape, axes, scale=fan_in ** -0.5)

    def z(self, path: str, shape, axes):
        """Zero-init weight (biases, norm offsets, training-init ``out``)."""
        self.axes[path] = tuple(axes)
        return jnp.zeros(shape, self.dtype)

    def ones(self, path: str, shape, axes):
        self.axes[path] = tuple(axes)
        return jnp.ones(shape, self.dtype)

    def const(self, path: str, value: np.ndarray, axes):
        self.axes[path] = tuple(axes)
        return jnp.asarray(value, self.dtype)


def stack_inits(fn, n: int):
    """Build ``n`` stacked copies of a per-layer param subtree (for lax.scan).

    ``fn(i)`` must return the subtree for layer ``i``; all layers share the
    same structure. Leading axis is tagged "scan" by the caller's Initializer
    convention (we just stack here).
    """
    trees = [fn(i) for i in range(n)]
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *trees)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rms_norm(x, gamma, eps: float):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(var + eps)
    return (out * (1.0 + gamma.astype(jnp.float32))).astype(dt)


def layer_norm(x, gamma, beta, eps: float):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    out = (x - mu) * jax.lax.rsqrt(var + eps)
    return (out * (1.0 + gamma.astype(jnp.float32)) + beta.astype(jnp.float32)).astype(dt)


def init_norm(init: Initializer, path: str, cfg: ModelConfig, dim: int):
    if cfg.norm_type == "layernorm":
        return {"gamma": init.z(f"{path}.gamma", (dim,), ("norm",)),
                "beta": init.z(f"{path}.beta", (dim,), ("norm",))}
    return {"gamma": init.z(f"{path}.gamma", (dim,), ("norm",))}


def apply_norm(params, x, cfg: ModelConfig):
    if cfg.norm_type == "layernorm":
        return layer_norm(x, params["gamma"], params["beta"], cfg.norm_eps)
    return rms_norm(x, params["gamma"], cfg.norm_eps)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float):
    exponent = np.arange(0, head_dim, 2, dtype=np.float64) / head_dim
    return jnp.asarray(1.0 / (theta ** exponent), jnp.float32)


def apply_rope(x, positions, theta: float):
    """x: (..., seq, heads, head_dim); positions: (..., seq)."""
    head_dim = x.shape[-1]
    inv_freq = rope_frequencies(head_dim, theta)
    angles = positions[..., :, None].astype(jnp.float32) * inv_freq  # (..., s, hd/2)
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP variants
# ---------------------------------------------------------------------------

def init_mlp(init: Initializer, path: str, cfg: ModelConfig, d_ff: Optional[int] = None):
    d, f = cfg.d_model, (d_ff or cfg.d_ff)
    p = {}
    if cfg.mlp_type in ("swiglu", "geglu"):
        p["wi"] = init.w(f"{path}.wi", (d, 2, f), ("w_embed", None, "ff"))
    else:  # relu2 | gelu
        p["wi"] = init.w(f"{path}.wi", (d, f), ("w_embed", "ff"))
    p["wo"] = init.out(f"{path}.wo", (f, d), ("ff", "w_embed"), f)
    return p


def apply_mlp(params, x, cfg: ModelConfig):
    if cfg.mlp_type in ("swiglu", "geglu"):
        h = jnp.einsum("...d,dgf->...gf", x, params["wi"])
        gate, up = h[..., 0, :], h[..., 1, :]
        act = jax.nn.silu(gate) if cfg.mlp_type == "swiglu" else jax.nn.gelu(gate)
        h = act * up
    elif cfg.mlp_type == "relu2":
        h = jnp.square(jax.nn.relu(x @ params["wi"]))
    else:  # gelu
        h = jax.nn.gelu(x @ params["wi"])
    return h @ params["wo"]


def mlp_flops(cfg: ModelConfig, d_ff: Optional[int] = None) -> int:
    """FLOPs per token for one MLP block (fwd)."""
    f = d_ff or cfg.d_ff
    mult = 3 if cfg.mlp_type in ("swiglu", "geglu") else 2
    return 2 * mult * cfg.d_model * f


def softcap(logits, cap: float):
    if not cap:
        return logits
    return jnp.tanh(logits / cap) * cap
