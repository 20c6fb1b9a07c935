"""What every architecture's plain float32 reference shares, and the
logit-gap check.

Each architecture's module (``bench/archs/<arch>.py``) holds its own
reference, a class ``Reference(config, seed)`` whose ``logits(tokens, rows,
control=False)`` gives the plain model's float32 logits. It builds on the
pieces here and imports nothing of the program under test.

The weights are made again from the seed by the recipe the served model's
random init follows (truncated normal on [-2, 2], fan-in scale, per-leaf
keys folded from a SHA-256 of the leaf's path, rounded to bfloat16), so a
reference takes no array that the program made.

Everything runs in float32 at "highest" matmul precision. ``control=True``
computes the same pass with every matmul's operands rounded to float8
(e4m3) under a per-row scale: the precision below bfloat16 that the check
must refuse.
"""
from __future__ import annotations

import hashlib
import math
from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 512
NEG_INF = -1e30


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


def normal(root, path, shape, scale):
    """The served init of leaf ``path`` under key ``root``, in bfloat16."""
    x = jax.random.truncated_normal(_leaf_key(root, path), -2.0, 2.0, shape,
                                    jnp.float32)
    return (x * scale).astype(jnp.bfloat16)


# ---------------------------------------------------------------------------
# forward-pass pieces
# ---------------------------------------------------------------------------

def fp8(x, axis):
    """Round to float8 e4m3 under a scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def mm(x, w, spec, control):
    """einsum in float32; in the control both operands pass through fp8
    first (activations per row, weights per output column)."""
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if control:
        x = fp8(x, -1)
        w = fp8(w, 0)
    return jnp.einsum(spec, x, w)


def rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def rope(x, theta):
    """x: (s, heads, hd) at positions 0..s-1; rotate the two halves."""
    s, _, hd = x.shape
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def padded(n: int) -> int:
    """Sequence lengths in a few buckets, so a layer compiles a few times."""
    return max(QUERY_BLOCK, 1 << math.ceil(math.log2(n)))


# ---------------------------------------------------------------------------
# the check
# ---------------------------------------------------------------------------

def gaps(ref, prompt: np.ndarray, served: Sequence[int],
         control: bool = False) -> np.ndarray:
    """Per served token, how far the reference's logit of the token lies
    below its best logit at that position. ``ref`` is an architecture's
    ``Reference``.

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
