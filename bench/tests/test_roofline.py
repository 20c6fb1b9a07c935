"""Operation and byte counts, against shapes worked by hand."""
import json
import os

import pytest

from roofline import (Shape, least_time, paged_decode_cost, peaks, span_flops,
                      token_flops)

SMALL = Shape(hidden=64, layers=2, heads=4, kv_heads=2, head_dim=16, ffn=128,
              vocab=512, block_tokens=16)


def test_block_params_by_hand():
    # attention 64*4*16*2 + 64*2*16*2 = 12288, SwiGLU 3*64*128 = 24576
    assert SMALL.block_params == 2 * (12288 + 24576)


def test_paged_decode_cost_by_hand():
    flops, nbytes = paged_decode_cost(SMALL, [17, 32])
    # scores and weighted sum: 4 * heads * head_dim * tokens per row
    assert flops == 4 * 4 * 16 * 17 + 4 * 4 * 16 * 32
    # both rows need 2 pages: K and V pages of 16 x 2 kv heads x 16 x 2 B,
    # plus the query and output rows (4 heads x 16 x 2 B each)
    assert nbytes == 2 * (2 * 2 * 16 * 2 * 16 * 2 + 2 * 4 * 16 * 2)


def test_token_and_span_flops_by_hand():
    # position 9: block matrices, attention over 10 keys in 2 layers, head
    assert token_flops(SMALL, 9, True) == (2 * 73728 + 4 * 2 * 4 * 16 * 10
                                           + 2 * 64 * 512)
    assert token_flops(SMALL, 9, False) == 2 * 73728 + 4 * 2 * 4 * 16 * 10
    # positions 3, 4, 5 attend over 4 + 5 + 6 keys
    assert span_flops(SMALL, 3, 6) == 3 * 2 * 73728 + 4 * 2 * 4 * 16 * 15
    assert span_flops(SMALL, 3, 6) == sum(token_flops(SMALL, p, False)
                                          for p in (3, 4, 5))
    assert span_flops(SMALL, 5, 5) == 0


def test_least_time_names_its_bound():
    peak = peaks("TPU v5 lite")
    assert least_time(197e12, 1.0, peak) == pytest.approx((1.0, "compute"))
    assert least_time(1.0, 819e9, peak) == pytest.approx((1.0, "memory"))


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        peaks("TPU v9 imaginary")


@pytest.mark.parametrize("name,params_bytes", [
    ("internlm2-20b.s8", 8515694592)])
def test_counts_match_the_served_parameter_bytes(name, params_bytes):
    # compiled.memory_analysis() of the served programs gave the parameter
    # bytes; matrices plus embedding, head and the (2 L + 1) norm vectors
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "configs", f"{name}.json")) as f:
        c = json.load(f)
    s = Shape.from_config(c)
    n = (s.block_params + 2 * s.vocab * s.hidden
         + (2 * s.layers + 1) * s.hidden)
    assert 2 * n == params_bytes == c["memory"]["params_bytes"]
