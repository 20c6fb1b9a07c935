"""Compile the served path for a described TPU v5e (no chip attached).

The TPU compiler is installed with jax; ``get_topology_desc`` describes a
v5e:2x2 host without one, and ``lower(...).compile()`` then raises what the
chip's compiler would raise: block shapes that break the (8, 128) tiling
rule, VMEM overuse, programs that do not fit HBM. Nothing runs, so these
tests say nothing about results or times.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library, and
under pytest-xdist every worker imports every test file.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.paged_attention import (paged_chunk_attention,
                                           paged_decode_attention,
                                           paged_verify_attention)
from repro.kernels.pq_scan import pq_scan
from repro.models import steps
from repro.models import transformer as tf

# (num_heads, kv_heads, head_dim): gemma-2b (MQA), a GQA shape with kvh > 1,
# and the guard-2b draft (MHA, kvh 16)
HEADS = [(8, 1, 256), (64, 8, 128), (16, 16, 128)]
B, BT, MB = 8, 16, 128                  # batch 8, 16-token pages, 2048 tokens


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("nh,kvh,d", HEADS)
def test_paged_kernels_compile(one_chip, nh, kvh, d):
    nb = B * MB + 1
    pool = _spec(one_chip, (nb, BT, kvh, d))
    tab = _spec(one_chip, (B, MB), jnp.int32)
    lens = _spec(one_chip, (B,), jnp.int32)
    for fn, s in ((paged_decode_attention, 1), (paged_verify_attention, 5)):
        q = _spec(one_chip, (B, s, nh, d))
        assert "tpu_custom_call" in _compiled_text(fn, q, pool, pool, tab,
                                                   lens)
    for s in (256, 200):          # whole query blocks, and a padded chunk
        q = _spec(one_chip, (B, s, nh, d))
        assert "tpu_custom_call" in _compiled_text(
            paged_chunk_attention, q, pool, pool, tab, lens, lens)


@pytest.mark.parametrize("nh,kvh,d", HEADS)
@pytest.mark.parametrize("s", [512, 200])
def test_flash_compiles(one_chip, nh, kvh, d, s):
    q = _spec(one_chip, (1, s, nh, d))
    kv = _spec(one_chip, (1, s, kvh, d))
    assert "tpu_custom_call" in _compiled_text(flash_attention, q, kv, kv)


@pytest.mark.parametrize("nh,kvh,d", HEADS)
def test_dense_decode_compiles(one_chip, nh, kvh, d):
    q = _spec(one_chip, (B, 1, nh, d))
    cache = _spec(one_chip, (B, 2048, kvh, d))
    lens = _spec(one_chip, (B,), jnp.int32)
    assert "tpu_custom_call" in _compiled_text(decode_attention, q, cache,
                                               cache, lens)


@pytest.mark.parametrize("n,m,k", [(4096, 16, 256), (1000, 32, 64)])
def test_pq_scan_compiles(one_chip, n, m, k):
    codes = _spec(one_chip, (n, m), jnp.int32)
    lut = _spec(one_chip, (m, k), jnp.float32)
    assert "tpu_custom_call" in _compiled_text(pq_scan, codes, lut)


@pytest.fixture
def gemma_abstract(one_chip, monkeypatch):
    """Published gemma-2b params + a paged pool for 8 x 2048 tokens, as
    shapes on the described chip, with ``ops`` steered to its TPU branch."""
    monkeypatch.setattr(ops, "_platform", lambda: "tpu")
    ops.DISPATCH.clear()
    cfg = get_config("gemma_2b")

    def on_chip(tree):
        return jax.tree.map(lambda s: _spec(one_chip, s.shape, s.dtype), tree)

    params = on_chip(jax.eval_shape(
        functools.partial(tf.init_params, cfg), jax.random.PRNGKey(0)))
    caches = on_chip(jax.eval_shape(
        lambda: tf.init_paged_cache(cfg, B, B * MB, BT, MB)))
    return cfg, params, caches


def test_gemma_serve_step_compiles(one_chip, gemma_abstract):
    cfg, params, caches = gemma_abstract
    tokens = _spec(one_chip, (B, 1), jnp.int32)
    compiled = jax.jit(functools.partial(steps.serve_step, cfg=cfg)).lower(
        params, tokens, caches).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert ops.dispatch_record()["paged_decode_attention"] == {"pallas": 1}
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


def test_gemma_prefill_and_chunk_steps_compile(one_chip, gemma_abstract):
    cfg, params, caches = gemma_abstract
    prefill = jax.jit(functools.partial(
        steps.prefill_step, cfg=cfg, max_len=MB * BT)).lower(
        params, {"tokens": _spec(one_chip, (1, 256), jnp.int32)}).compile()
    assert "tpu_custom_call" in prefill.as_text()
    jax.jit(functools.partial(steps.chunk_step, cfg=cfg)).lower(
        params, _spec(one_chip, (B, 256), jnp.int32),
        _spec(one_chip, (B,), jnp.int32), caches).compile()
    assert ops.dispatch_record() == {
        "flash_attention": {"pallas": 1},
        "paged_chunk_attention": {"pallas": 1}}


def test_internlm2_chunk_step_compiles(one_chip, monkeypatch):
    """The benchmark's chunk pass: InternLM2-20B widths, 8 layers, batch 8,
    chunk 256, 2,501 pages of 16 tokens, 512 table entries. The chunk
    kernel walks pages in VMEM, so the program holds no table-wide score
    tensor: its temporaries stay under 1 GB (3.57 GB with the reference)."""
    monkeypatch.setattr(ops, "_platform", lambda: "tpu")
    ops.DISPATCH.clear()
    cfg = get_config("internlm2_20b").replace(num_layers=8, head_dim=128)

    def on_chip(tree):
        return jax.tree.map(lambda s: _spec(one_chip, s.shape, s.dtype), tree)

    params = on_chip(jax.eval_shape(
        lambda: tf.init_params(cfg, jax.random.PRNGKey(0), False)))
    caches = on_chip(jax.eval_shape(
        lambda: tf.init_paged_cache(cfg, 8, 2500, 16, 512)))
    compiled = jax.jit(functools.partial(steps.chunk_step, cfg=cfg)).lower(
        params, _spec(one_chip, (8, 256), jnp.int32),
        _spec(one_chip, (8,), jnp.int32), caches).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert ops.dispatch_record() == {"paged_chunk_attention": {"pallas": 1}}
    assert compiled.memory_analysis().temp_size_in_bytes < 1e9
