"""Dense decoder with GQA attention: RoPE, SwiGLU MLP, RMSNorm, untied head.

A configuration file names this module with ``"arch": "dense_gqa"``; the
harness loads it by path (``bench/run.py``). What it holds:

* ``model_config(config)``: the program's ``ModelConfig`` for the file;
* ``Reference(config, seed)``: the plain float32 forward pass, whose
  ``logits(tokens, rows, control=False)`` the output check compares with;
* ``Shape.from_config(config, block_tokens)``: the block's sizes and the
  model FLOPs and kernel bytes that follow from its equations;
* ``PALLAS_OPS``: the ops that must take their Pallas kernel on a TPU;
* ``SMALL``: the keys that cut a configuration to a CPU test's size.

The reference is written from the published block, with the two
conventions the served model uses for random weights: embeddings scaled by
sqrt(hidden), and RMSNorm as ``x * rsqrt(mean(x^2) + eps) * (1 + gamma)``.
Rotary embedding rotates the two halves of each head (as in the published
Llama-style ``rotate_half``). Attention runs in blocks of queries, one
layer at a time, so it fits beside nothing else on one chip. It imports
nothing of the program; ``bench/tests/test_reference.py`` holds its weights
to the served model's bit for bit on the CPU.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Iterable, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from reference import (NEG_INF, QUERY_BLOCK, mm, normal, padded, rms, rope,
                       seed_key)

# keys of a configuration file that model_config maps onto ModelConfig
MAPPED = frozenset({
    "hidden_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "head_dim", "intermediate_size", "vocab_size",
    "rope_theta", "rms_norm_eps", "hidden_act", "tie_word_embeddings",
    "torch_dtype"})
# keys that describe the configuration and change nothing that is built;
# max_position_embeddings bounds a context longer than any cell's
METADATA = frozenset({
    "name", "arch", "source", "architecture", "max_position_embeddings",
    "reduced", "deployment", "assumed", "memory"})

# the dispatch names (kernels/ops.py) of the window's paged attention, which
# must take the Pallas kernel on a TPU: a silent fall-back to the float32
# reference is refused at warm-up
PALLAS_OPS = ("paged_decode_attention", "paged_chunk_attention")

# published widths replaced by a small decoder of the same kind, so a CPU
# can run a cell's whole harness in seconds
SMALL = {"hidden_size": 64, "num_hidden_layers": 2,
         "num_attention_heads": 4, "num_key_value_heads": 2,
         "head_dim": 16, "intermediate_size": 128, "vocab_size": 512}


def model_config(config: dict):
    """The program's ModelConfig for a configuration file. A key that is
    neither mapped nor metadata (``kv_lora_rank``, ``n_routed_experts``,
    ``sliding_window``, ...) belongs to another architecture: it is
    refused, not built as a dense decoder."""
    from repro.configs.base import ModelConfig
    unknown = sorted(set(config) - MAPPED - METADATA)
    if unknown:
        raise ValueError(f"configuration {config.get('name')!r}: keys "
                         f"{unknown} are not part of a dense GQA decoder")
    if config.get("hidden_act") != "silu":
        raise ValueError("only SwiGLU (silu) decoders are wired here")
    if config.get("torch_dtype") != "bfloat16":
        raise ValueError("the served weights are bfloat16; the file states "
                         f"{config.get('torch_dtype')!r}")
    return ModelConfig(
        name=config["name"], family="dense",
        num_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config.get("head_dim") or 0,
        d_ff=config["intermediate_size"], vocab_size=config["vocab_size"],
        mlp_type="swiglu", attn_type="gqa",
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        param_dtype="bfloat16", compute_dtype="bfloat16",
        logits_dtype="float32")


# ---------------------------------------------------------------------------
# counts
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class Shape:
    """A dense GQA decoder's sizes, for the counts and the reference. The
    counts take shapes and lengths only, so a CPU test can check them by
    hand (``bench/tests/test_roofline.py``)."""
    hidden: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int
    vocab: int
    rope_theta: float
    eps: float
    block_tokens: int = 16
    itemsize: int = 2          # bf16 KV pages

    @classmethod
    def from_config(cls, c: dict, block_tokens: int = 16) -> "Shape":
        return cls(c["hidden_size"], c["num_hidden_layers"],
                   c["num_attention_heads"], c["num_key_value_heads"],
                   c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"],
                   c["intermediate_size"], c["vocab_size"],
                   float(c["rope_theta"]), float(c["rms_norm_eps"]),
                   block_tokens)

    @property
    def block_params(self) -> int:
        """Matrix parameters of all blocks (attention + SwiGLU MLP)."""
        d, hd = self.hidden, self.head_dim
        attn = d * self.heads * hd * 2 + d * self.kv_heads * hd * 2
        return self.layers * (attn + 3 * d * self.ffn)

    def paged_decode_cost(self, lengths: Iterable[int]) -> Tuple[int, int]:
        """FLOPs and HBM bytes one paged decode-attention call needs in ONE
        layer, for rows whose KV holds ``lengths`` tokens (the new one
        included): scores and the weighted sum over the tokens, and every
        page those tokens occupy read once for K and once for V, plus the
        query and output rows. Pages past a row's length are not counted:
        the kernel need not touch them."""
        h, kv, hd, bt = self.heads, self.kv_heads, self.head_dim, self.block_tokens
        flops = 0
        nbytes = 0
        for n in lengths:
            pages = -(-int(n) // bt)
            flops += 4 * h * hd * int(n)
            nbytes += 2 * pages * bt * kv * hd * self.itemsize
            nbytes += 2 * h * hd * self.itemsize
        return flops, nbytes

    def token_flops(self, position: int, logits: bool) -> int:
        """Model FLOPs of one token at ``position`` (0-based): the block
        matrices, causal attention over position + 1 keys in every layer,
        and the output head where the token yields logits."""
        f = 2 * self.block_params
        f += 4 * self.layers * self.heads * self.head_dim * (position + 1)
        if logits:
            f += self.head_flops()
        return f

    def head_flops(self) -> int:
        """Model FLOPs of the output head for one token: all that a
        request's first output token adds to its prompt's last position,
        which ``span_flops`` counts."""
        return 2 * self.hidden * self.vocab

    def span_flops(self, start: int, stop: int) -> int:
        """Model FLOPs of prompt positions [start, stop) written in a chunk,
        none of which yields logits (the prompt's last one is counted with
        the first output token)."""
        n = stop - start
        if n <= 0:
            return 0
        pos_sum = (start + stop + 1) * n // 2          # sum of (p + 1)
        return (2 * self.block_params * n
                + 4 * self.layers * self.heads * self.head_dim * pos_sum)


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def outer_weights(dims: Shape, key) -> Dict[str, jax.Array]:
    """Embedding and output head (bf16); the final norm's gamma is zero."""
    root = jax.random.fold_in(key, 0xE0)
    d, v = dims.hidden, dims.vocab
    return {"embed": normal(root, "embed", (v, d), d ** -0.5),
            "head": normal(root, "head", (d, v), d ** -0.5)}


def layer_weights(dims: Shape, key, i) -> Dict[str, jax.Array]:
    """Layer ``i``'s matrices (bf16); its two norm gammas are zero."""
    root = jax.random.fold_in(jax.random.fold_in(key, 1), i)
    d, h, kv, hd, f = (dims.hidden, dims.heads, dims.kv_heads, dims.head_dim,
                       dims.ffn)
    fan_d = 1.0 / np.sqrt(d)
    return {
        "wq": normal(root, "layers.attn.wq", (d, h, hd), fan_d),
        "wk": normal(root, "layers.attn.wk", (d, kv, hd), fan_d),
        "wv": normal(root, "layers.attn.wv", (d, kv, hd), fan_d),
        "wo": normal(root, "layers.attn.wo", (h, hd, d), (h * hd) ** -0.5),
        "wi": normal(root, "layers.mlp.wi", (d, 2, f), fan_d),
        "wo_mlp": normal(root, "layers.mlp.wo", (f, d), f ** -0.5),
    }


def _layer(w, x, dims: Shape, control: bool):
    """One block over a whole (padded) sequence x: (s, d) float32."""
    s = x.shape[0]
    h = rms(x, dims.eps)
    q = rope(mm(h, w["wq"], "sd,dnh->snh", control), dims.rope_theta)
    k = rope(mm(h, w["wk"], "sd,dnh->snh", control), dims.rope_theta)
    v = mm(h, w["wv"], "sd,dnh->snh", control)
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
    x = x + mm(o, w["wo"], "snh,nhd->sd", control)
    h = rms(x, dims.eps)
    gu = mm(h, w["wi"], "sd,dgf->sgf", control)
    a = jax.nn.silu(gu[:, 0]) * gu[:, 1]
    return x + mm(a, w["wo_mlp"], "sf,fd->sd", control)


class Reference:
    """Logits of the plain model for whole token sequences, on the default
    device. Holds the bf16 weights (the served model's size) and nothing
    else between calls."""

    def __init__(self, config: dict, seed: int):
        self.dims = Shape.from_config(config)
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
                return mm(rms(x[rows], dims.eps), head, "sd,dv->sv", control)

        self._embed = jax.jit(embed)
        self._layer = jax.jit(layer, static_argnums=2)
        self._logits = jax.jit(logits, static_argnums=3)

    def logits(self, tokens: np.ndarray, rows: np.ndarray,
               control: bool = False) -> np.ndarray:
        """Float32 logits (len(rows), vocab) after positions ``rows`` of the
        causal pass over ``tokens``."""
        n = len(tokens)
        pad = np.zeros(padded(n), np.int32)
        pad[:n] = tokens
        x = self._embed(self.outer["embed"], jnp.asarray(pad))
        for w in self.layers:
            x = self._layer(w, x, control)
        out = self._logits(self.outer["head"], x, jnp.asarray(rows, np.int32),
                           control)
        return np.asarray(out)
