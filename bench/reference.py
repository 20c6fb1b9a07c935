"""Plain float32 reference of a dense GQA decoder, and the logit-gap check.

Imports nothing of the program under test. The weights are made again from
the seed by the recipe the served model's random init follows (truncated
normal on [-2, 2], fan-in scale, per-leaf keys folded from a SHA-256 of the
leaf's path, rounded to bfloat16), so the reference takes no array that the
program made. ``bench/tests/test_reference.py`` holds the two to the same
bits on the CPU.

The forward pass is written from the published block, with the two
conventions the served model uses for random weights: embeddings scaled by
sqrt(hidden), and RMSNorm as ``x * rsqrt(mean(x^2) + eps) * (1 + gamma)``.
Rotary embedding rotates the two halves of each head (as in the published
Llama-style ``rotate_half``). Everything runs in float32 at "highest"
matmul precision, one layer at a time, with attention in blocks of
queries, so it fits beside nothing else on one chip.

``control=True`` computes the same pass with every matmul's operands
rounded to float8 (e4m3) under a per-row scale: the precision below
bfloat16 that the check must refuse.
"""
from __future__ import annotations

import hashlib
import math
from dataclasses import dataclass
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 512
NEG_INF = -1e30


@dataclass(frozen=True)
class Dims:
    hidden: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int
    vocab: int
    rope_theta: float
    eps: float

    @classmethod
    def from_config(cls, c: dict) -> "Dims":
        return cls(c["hidden_size"], c["num_hidden_layers"],
                   c["num_attention_heads"], c["num_key_value_heads"],
                   c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"],
                   c["intermediate_size"], c["vocab_size"],
                   float(c["rope_theta"]), float(c["rms_norm_eps"]))


def seed_key(seed: int):
    """A key for any whole seed (``PRNGKey`` alone keeps 32 bits)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2 ** 32), seed // 2 ** 32)


# ---------------------------------------------------------------------------
# weights, made again from the seed
# ---------------------------------------------------------------------------

def _leaf_key(root, path: str):
    h = int.from_bytes(hashlib.sha256(path.encode()).digest()[:4], "little")
    return jax.random.fold_in(root, h)


def _normal(root, path, shape, scale):
    x = jax.random.truncated_normal(_leaf_key(root, path), -2.0, 2.0, shape,
                                    jnp.float32)
    return (x * scale).astype(jnp.bfloat16)


def outer_weights(dims: Dims, key) -> Dict[str, jax.Array]:
    """Embedding and output head (bf16); the final norm's gamma is zero."""
    root = jax.random.fold_in(key, 0xE0)
    d, v = dims.hidden, dims.vocab
    return {"embed": _normal(root, "embed", (v, d), d ** -0.5),
            "head": _normal(root, "head", (d, v), d ** -0.5)}


def layer_weights(dims: Dims, key, i) -> Dict[str, jax.Array]:
    """Layer ``i``'s matrices (bf16); its two norm gammas are zero."""
    root = jax.random.fold_in(jax.random.fold_in(key, 1), i)
    d, h, kv, hd, f = (dims.hidden, dims.heads, dims.kv_heads, dims.head_dim,
                       dims.ffn)
    fan_d = 1.0 / np.sqrt(d)
    return {
        "wq": _normal(root, "layers.attn.wq", (d, h, hd), fan_d),
        "wk": _normal(root, "layers.attn.wk", (d, kv, hd), fan_d),
        "wv": _normal(root, "layers.attn.wv", (d, kv, hd), fan_d),
        "wo": _normal(root, "layers.attn.wo", (h, hd, d), (h * hd) ** -0.5),
        "wi": _normal(root, "layers.mlp.wi", (d, 2, f), fan_d),
        "wo_mlp": _normal(root, "layers.mlp.wo", (f, d), f ** -0.5),
    }


# ---------------------------------------------------------------------------
# forward pass
# ---------------------------------------------------------------------------

def _fp8(x, axis):
    """Round to float8 e4m3 under a scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(x, w, spec, control):
    """einsum in float32; in the control both operands pass through fp8
    first (activations per row, weights per output column)."""
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if control:
        x = _fp8(x, -1)
        w = _fp8(w, 0)
    return jnp.einsum(spec, x, w)


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, theta):
    """x: (s, heads, hd) at positions 0..s-1; rotate the two halves."""
    s, _, hd = x.shape
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _layer(w, x, dims: Dims, control: bool):
    """One block over a whole (padded) sequence x: (s, d) float32."""
    s = x.shape[0]
    h = _rms(x, dims.eps)
    q = _rope(_mm(h, w["wq"], "sd,dnh->snh", control), dims.rope_theta)
    k = _rope(_mm(h, w["wk"], "sd,dnh->snh", control), dims.rope_theta)
    v = _mm(h, w["wv"], "sd,dnh->snh", control)
    g = dims.heads // dims.kv_heads
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    scale = dims.head_dim ** -0.5
    nb = s // QUERY_BLOCK
    qb = q.reshape(nb, QUERY_BLOCK, dims.heads, dims.head_dim)
    key_pos = jnp.arange(s)

    def attend(args):
        qi, start = args
        sc = jnp.einsum("qnh,knh->nqk", qi, k) * scale
        qpos = start + jnp.arange(QUERY_BLOCK)
        sc = jnp.where(key_pos[None, None, :] <= qpos[None, :, None], sc,
                       NEG_INF)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("nqk,knh->qnh", p, v)

    o = jax.lax.map(attend, (qb, jnp.arange(nb) * QUERY_BLOCK))
    o = o.reshape(s, dims.heads, dims.head_dim)
    x = x + _mm(o, w["wo"], "snh,nhd->sd", control)
    h = _rms(x, dims.eps)
    gu = _mm(h, w["wi"], "sd,dgf->sgf", control)
    a = jax.nn.silu(gu[:, 0]) * gu[:, 1]
    return x + _mm(a, w["wo_mlp"], "sf,fd->sd", control)


def _padded(n: int) -> int:
    """Sequence lengths in a few buckets, so the layer compiles a few times."""
    return max(QUERY_BLOCK, 1 << math.ceil(math.log2(n)))


class Reference:
    """Logits of the plain model for whole token sequences, on the default
    device. Holds the bf16 weights (the served model's size) and nothing
    else between calls."""

    def __init__(self, config: dict, seed: int):
        self.dims = Dims.from_config(config)
        key = seed_key(seed)
        gen_outer = jax.jit(outer_weights, static_argnums=0)
        gen_layer = jax.jit(layer_weights, static_argnums=0)
        self.outer = gen_outer(self.dims, key)
        self.layers = [gen_layer(self.dims, key, i)
                       for i in range(self.dims.layers)]
        dims = self.dims

        def embed(table, tokens):
            x = table[tokens].astype(jnp.float32)
            return x * jnp.float32(dims.hidden ** 0.5)

        def layer(w, x, control):
            with jax.default_matmul_precision("highest"):
                return _layer(w, x, dims, control)

        def logits(head, x, rows, control):
            with jax.default_matmul_precision("highest"):
                return _mm(_rms(x[rows], dims.eps), head, "sd,dv->sv", control)

        self._embed = jax.jit(embed)
        self._layer = jax.jit(layer, static_argnums=2)
        self._logits = jax.jit(logits, static_argnums=3)

    def logits(self, tokens: np.ndarray, rows: np.ndarray,
               control: bool = False) -> np.ndarray:
        """Float32 logits (len(rows), vocab) after positions ``rows`` of the
        causal pass over ``tokens``."""
        n = len(tokens)
        pad = np.zeros(_padded(n), np.int32)
        pad[:n] = tokens
        x = self._embed(self.outer["embed"], jnp.asarray(pad))
        for w in self.layers:
            x = self._layer(w, x, control)
        out = self._logits(self.outer["head"], x, jnp.asarray(rows, np.int32),
                           control)
        return np.asarray(out)


def gaps(ref: Reference, prompt: np.ndarray, served: Sequence[int],
         control: bool = False) -> np.ndarray:
    """Per served token, how far the reference's logit of the token lies
    below its best logit at that position.

    Without ``control`` the tokens judged are those served. With it, the
    reference is run in fp8 over the same prompt and served tokens, and the
    token judged at each position is the one fp8 puts first: what a served
    model computing in fp8 would have emitted there."""
    served = np.asarray(served, np.int32)
    seq = np.concatenate([prompt.astype(np.int32), served])[:-1]
    rows = np.arange(len(prompt) - 1, len(seq))
    best = ref.logits(seq, rows)
    if control:
        chosen = ref.logits(seq, rows, control=True).argmax(-1)
    else:
        chosen = served
    return best.max(-1) - best[np.arange(len(rows)), chosen]


def sample_requests(done: List, rng: np.random.Generator, min_tokens: int,
                    max_requests: int) -> List:
    """The finished request with the longest sequence, then others drawn
    from ``rng`` until ``min_tokens`` served tokens are covered."""
    if not done:
        return []
    order = sorted(done, key=lambda r: len(r.prompt) + len(r.tokens))
    pick = [order[-1]]
    rest = order[:-1]
    for i in rng.permutation(len(rest)):
        if (sum(len(r.tokens) for r in pick) >= min_tokens
                or len(pick) >= max_requests):
            break
        pick.append(rest[i])
    return pick
