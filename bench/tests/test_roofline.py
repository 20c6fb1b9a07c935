"""Operation and byte counts, against shapes worked by hand and against the
values the counts gave before they moved into the architecture's module."""
import json
import os

import pytest

from conftest import arch
from roofline import least_time, peaks

dense_gqa = arch("dense_gqa")
Shape = dense_gqa.Shape
SMALL = Shape(hidden=64, layers=2, heads=4, kv_heads=2, head_dim=16, ffn=128,
              vocab=512, rope_theta=10000.0, eps=1e-5, block_tokens=16)
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def config(name):
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        return json.load(f)


def test_block_params_by_hand():
    # attention 64*4*16*2 + 64*2*16*2 = 12288, SwiGLU 3*64*128 = 24576
    assert SMALL.block_params == 2 * (12288 + 24576)


def test_paged_decode_cost_by_hand():
    flops, nbytes = SMALL.paged_decode_cost([17, 32])
    # scores and weighted sum: 4 * heads * head_dim * tokens per row
    assert flops == 4 * 4 * 16 * 17 + 4 * 4 * 16 * 32
    # both rows need 2 pages: K and V pages of 16 x 2 kv heads x 16 x 2 B,
    # plus the query and output rows (4 heads x 16 x 2 B each)
    assert nbytes == 2 * (2 * 2 * 16 * 2 * 16 * 2 + 2 * 4 * 16 * 2)


def test_token_and_span_flops_by_hand():
    # position 9: block matrices, attention over 10 keys in 2 layers, head
    assert SMALL.token_flops(9, True) == (2 * 73728 + 4 * 2 * 4 * 16 * 10
                                          + 2 * 64 * 512)
    assert SMALL.token_flops(9, False) == 2 * 73728 + 4 * 2 * 4 * 16 * 10
    assert SMALL.head_flops() == 2 * 64 * 512
    # positions 3, 4, 5 attend over 4 + 5 + 6 keys
    assert SMALL.span_flops(3, 6) == 3 * 2 * 73728 + 4 * 2 * 4 * 16 * 15
    assert SMALL.span_flops(3, 6) == sum(SMALL.token_flops(p, False)
                                         for p in (3, 4, 5))
    assert SMALL.span_flops(5, 5) == 0


def test_counts_are_pinned_on_internlm2():
    """The parent's ``bench/roofline.py`` gave these on the cell's
    configuration; moving the counts may not change them."""
    s = Shape.from_config(config("internlm2-20b.s8"), 16)
    assert s.block_params == 3_120_562_176
    assert [s.token_flops(p, True) for p in (0, 1019, 8191)] == [
        7_378_501_632, 7_578_845_184, 8_988_917_760]
    assert s.head_flops() == 1_137_180_672
    assert s.span_flops(0, 256) == 1_604_195_450_880
    assert s.span_flops(1024, 1280) == 1_655_735_058_432
    assert s.paged_decode_cost([1, 17, 1135, 4129]) == (129_810_432,
                                                        21_921_792)


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
    c = config(name)
    s = Shape.from_config(c)
    n = (s.block_params + 2 * s.vocab * s.hidden
         + (2 * s.layers + 1) * s.hidden)
    assert 2 * n == params_bytes == c["memory"]["params_bytes"]
