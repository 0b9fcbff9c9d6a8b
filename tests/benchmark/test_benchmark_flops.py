"""The FLOP and byte functions against counts made by hand."""

import pytest

from benchmark import flops, harness

BENCH = harness.load_benchmark()
RESNET = harness.Cell(BENCH, "resnet50.fit_b256").config
STAR = harness.Cell(BENCH, "starcoder2-3b.chat_closed32").config


def test_one_resnet50_bottleneck_by_hand():
    # s2b1: an identity block at 56x56 over 256 channels (64, 64, 256)
    by_name = {n: (h, w, k, ci, co) for n, h, w, k, ci, co, _ in
               flops.resnet50_layers(RESNET)}
    assert by_name["s2b1_a"] == (56, 56, 1, 256, 64)
    assert by_name["s2b1_b"] == (56, 56, 3, 64, 64)
    assert by_name["s2b1_c"] == (56, 56, 1, 64, 256)
    hand = 2 * 56 * 56 * (256 * 64 + 9 * 64 * 64 + 64 * 256)
    got = sum(flops.conv_flops(*by_name[f"s2b1_{p}"]) for p in "abc")
    assert got == hand == 436_731_904


@pytest.mark.parametrize("name,shape", [
    ("stem", (112, 112, 7, 3, 64)),
    ("s3b0_a", (28, 28, 1, 256, 128)),        # stride 2 on the first 1x1
    ("s3b0_skip", (28, 28, 1, 256, 512)),
    ("s5b2_c", (7, 7, 1, 512, 2048)),
    ("output", (1, 1, 1, 2048, 1000)),
])
def test_resnet50_layer_shapes(name, shape):
    by_name = {n: tuple(r) for n, *r, _ in flops.resnet50_layers(RESNET)}
    assert by_name[name] == shape


def test_resnet50_totals():
    layers = flops.resnet50_layers(RESNET)
    assert len(layers) == 1 + 16 * 3 + 4 + 1
    fwd = flops.resnet50_forward_flops(RESNET)
    stem = flops.conv_flops(112, 112, 7, 3, 64)
    assert 7.6e9 < fwd < 7.8e9            # 3.86 GMAC, stride on the 1x1
    # forward + weight gradient + input gradient, the stem without the last
    assert flops.resnet50_train_flops(RESNET) == 3 * fwd - stem


def test_one_starcoder2_layer_by_hand():
    h, kv, i = 3072, 2 * 128, 12288
    hand = 2 * (h * h + 2 * h * kv + h * h + 2 * h * i)
    assert flops.starcoder2_layer_matmul_flops(STAR) == hand == 191_889_408
    # one query over 1,000 keys: QK^T and PV, 24 heads of 128
    assert flops.starcoder2_attention_flops(STAR, 1000) == \
        4 * 24 * 128 * 1000
    assert flops.starcoder2_head_flops(STAR) == 2 * 3072 * 49152


@pytest.mark.parametrize("context", [1, 384, 4096])
def test_starcoder2_decode_token(context):
    want = 30 * (191_889_408 + 4 * 24 * 128 * context) + 2 * 3072 * 49152
    assert flops.starcoder2_decode_flops(STAR, context) == want


def test_starcoder2_prefill_is_the_sum_of_its_tokens_less_the_heads():
    n = 17
    by_token = sum(flops.starcoder2_decode_flops(STAR, c)
                   for c in range(1, n + 1))
    assert flops.starcoder2_prefill_flops(STAR, n) == \
        by_token - (n - 1) * flops.starcoder2_head_flops(STAR)


def test_paged_attention_bytes_follow_the_work_not_the_pages():
    # one row with 1,000 live keys: K and V of 2 heads x 128 in bf16, plus
    # the query in and the output out (24 heads x 128, bf16)
    assert flops.paged_attention_bytes(STAR, [1000]) == \
        1000 * 2 * 2 * 128 * 2 + 2 * 24 * 128 * 2
    assert flops.paged_attention_bytes(STAR, [10, 20]) == \
        flops.paged_attention_bytes(STAR, [10]) + \
        flops.paged_attention_bytes(STAR, [20])
    assert flops.paged_attention_flops(STAR, [10, 20]) == \
        flops.starcoder2_attention_flops(STAR, 30)
