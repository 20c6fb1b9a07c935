"""Pallas TPU IVF-PQ ADC-scan kernel (the RAG retrieval hot loop).

GPU implementations keep the per-query distance LUT in shared memory and
gather per-code — TPUs have no per-lane gather into scratch, so the scan is
reformulated MXU/VPU-natively: codes are expanded against an iota over the
codebook axis and reduced against the LUT, i.e. a masked sum instead of a
gather. The LUT (M x K fp32, ~16 KB) stays VMEM-resident across all N tiles;
codes stream HBM->VMEM once.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

def _pq_kernel(codes_ref, lut_ref, out_ref):
    codes = codes_ref[...].astype(jnp.int32)        # (bn, M)
    lut = lut_ref[...].astype(jnp.float32)          # (M, K)
    M, K = lut.shape
    # one-hot over the codebook axis, one subquantizer at a time: a masked
    # row sum on the VPU instead of a per-element gather
    book = jax.lax.broadcasted_iota(jnp.int32, (1, K), 1)
    acc = jnp.zeros((codes.shape[0], 1), jnp.float32)
    for m in range(M):
        hit = codes[:, m:m + 1] == book                          # (bn, K)
        acc = acc + jnp.sum(jnp.where(hit, lut[m:m + 1, :], 0.0), axis=1,
                            keepdims=True)
    out_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def pq_scan(codes, lut, *, block_n: int = 1024, interpret: bool = False):
    """codes: (N, M) integer PQ codes; lut: (M, K) distances. -> (N,) f32.

    The output is produced as an ``(N, 1)`` column (a rank-1 output block
    has no TPU layout) and flattened on return."""
    N, M = codes.shape
    K = lut.shape[1]
    block_n = min(block_n, N)
    out = pl.pallas_call(
        _pq_kernel,
        grid=(pl.cdiv(N, block_n),),
        in_specs=[
            pl.BlockSpec((block_n, M), lambda i: (i, 0)),
            pl.BlockSpec((M, K), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N, 1), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
    )(codes.astype(jnp.int32), lut)
    return out[:, 0]
