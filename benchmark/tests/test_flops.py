"""flops.py against counts made by hand for both configurations."""
import json
import os

import pytest

from benchmark import flops, weights

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs")


def cfg(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


def test_bert_large_by_hand():
    c = cfg("bert_large")
    # per layer: q, k, v, proj 4 x 1024^2 = 4,194,304; ffn 2 x 1024 x 4096
    # = 8,388,608; 24 layers = 301,989,888; head 1024 x 30522 = 31,254,528
    assert flops.gemm_params(c) == 301_989_888 + 31_254_528 == 333_244_416
    # attention per token at 512: 24 layers x 4 x 512 x 1024 = 50,331,648
    fwd = 2 * 333_244_416 + 50_331_648
    assert flops.forward_flops_per_token(c, 512) == fwd == 716_820_480
    assert flops.train_flops_per_token(c, 512) == 3 * fwd == 2_150_461_440


def test_bertgen_large_by_hand():
    c = cfg("bertgen_large")
    assert flops.gemm_params(c) == 301_989_888 + 1024 * 50358
    # one token against 200 cached positions: 24 x 4 x 200 x 1024
    assert flops.decode_flops_per_token(c, 200) == \
        2 * flops.gemm_params(c) + 19_660_800
    # 32 slots x 512 positions x 1024 x 2 (k, v) x 24 layers x 4 bytes
    table = 32 * 512 * 1024 * 2 * 24 * 4
    assert table == 3_221_225_472
    assert flops.decode_step_bytes(c, 32, 512) == \
        4 * flops.gemm_params(c) + table


@pytest.mark.parametrize("name", ["bert_large", "bertgen_large"])
def test_param_count_is_the_weights_layout(name):
    c = cfg(name)
    n = sum(int.__mul__(*s) if len(s) == 2 else s[0]
            for s in weights.leaf_shapes(c).values())
    assert flops.param_count(c) == n


def test_flash_attention_call_and_roofline():
    # 8 x 16 heads x 512 x 512 x 64: forward 4 x 8*16*512*512*64
    ops, nbytes = flops.flash_attention_call(8, 16, 512, 512, 64, False,
                                             False, 2)
    assert ops == 4 * 8 * 16 * 512 * 512 * 64 == 8_589_934_592
    assert nbytes == 4 * (8 * 16 * 512 * 64 * 2)
    b_ops, b_bytes = flops.flash_attention_call(8, 16, 512, 512, 64, False,
                                                True, 2)
    assert b_ops == 2.5 * ops and b_bytes == 2 * nbytes
    t, bound = flops.roofline_seconds(ops, nbytes, 197e12, 819e9)
    assert bound == "compute" and t == ops / 197e12
    assert flops.roofline_seconds(1.0, 819e9, 197e12, 819e9) == \
        (1.0, "memory")
