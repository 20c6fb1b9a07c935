"""Peaks of the chip, and the operations and bytes the work needs.

The peaks table (``bench/peaks.json``) is keyed by ``device_kind``; a
device that is not in it is an error, never a default. The counting
functions take shapes and lengths only, so a CPU test can check them by
hand (``bench/tests/test_roofline.py``).
"""
from __future__ import annotations

import json
import os
from dataclasses import dataclass
from typing import Iterable, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add it to "
                       f"bench/peaks.json with its source")
    return table[device_kind]


@dataclass(frozen=True)
class Shape:
    """What the counts need of a dense GQA decoder."""
    hidden: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int
    vocab: int
    block_tokens: int = 16
    itemsize: int = 2          # bf16 KV pages

    @classmethod
    def from_config(cls, c: dict, block_tokens: int = 16) -> "Shape":
        return cls(c["hidden_size"], c["num_hidden_layers"],
                   c["num_attention_heads"], c["num_key_value_heads"],
                   c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"],
                   c["intermediate_size"], c["vocab_size"], block_tokens)

    @property
    def block_params(self) -> int:
        """Matrix parameters of all blocks (attention + SwiGLU MLP)."""
        d, hd = self.hidden, self.head_dim
        attn = d * self.heads * hd * 2 + d * self.kv_heads * hd * 2
        return self.layers * (attn + 3 * d * self.ffn)


def paged_decode_cost(shape: Shape, lengths: Iterable[int]) -> Tuple[int, int]:
    """FLOPs and HBM bytes one paged decode-attention call needs in ONE
    layer, for rows whose KV holds ``lengths`` tokens (the new one
    included): scores and the weighted sum over the tokens, and every page
    those tokens occupy read once for K and once for V, plus the query and
    output rows. Pages past a row's length are not counted: the kernel
    need not touch them."""
    h, kv, hd, bt = shape.heads, shape.kv_heads, shape.head_dim, shape.block_tokens
    flops = 0
    nbytes = 0
    for n in lengths:
        pages = -(-int(n) // bt)
        flops += 4 * h * hd * int(n)
        nbytes += 2 * pages * bt * kv * hd * shape.itemsize
        nbytes += 2 * h * hd * shape.itemsize
    return flops, nbytes


def least_time(flops: float, nbytes: float, peak: dict) -> Tuple[float, str]:
    """The larger of compute time and memory time at the chip's peaks, and
    which of the two bounds it."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


def token_flops(shape: Shape, position: int, logits: bool) -> int:
    """Model FLOPs of one token at ``position`` (0-based): the block
    matrices, causal attention over position + 1 keys in every layer, and
    the output head where the token yields logits."""
    f = 2 * shape.block_params
    f += 4 * shape.layers * shape.heads * shape.head_dim * (position + 1)
    if logits:
        f += 2 * shape.hidden * shape.vocab
    return f


def span_flops(shape: Shape, start: int, stop: int) -> int:
    """Model FLOPs of prompt positions [start, stop) written in a chunk,
    none of which yields logits (the prompt's last one is counted with the
    first output token)."""
    n = stop - start
    if n <= 0:
        return 0
    pos_sum = (start + stop + 1) * n // 2          # sum of (p + 1)
    return (2 * shape.block_params * n
            + 4 * shape.layers * shape.heads * shape.head_dim * pos_sum)
