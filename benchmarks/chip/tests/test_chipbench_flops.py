"""The dense block kind's operation and byte counts against hand counts
for both configs."""
from chipbench import flops, spec

Q05_CONFIG = spec.load_config("qwen1.5-0.5b")
DENSE = spec.load_block(Q05_CONFIG)
Q05 = DENSE.dims(Q05_CONFIG)
Q8 = DENSE.dims(spec.load_config("qwen3-8b-12l"))


def test_qwen15_hand_counts():
    # attention: 1024x1024 for q, k, v and o; MLP: 3 x 1024 x 2816
    per_layer = 4 * 1024 * 1024 + 3 * 1024 * 2816
    assert per_layer == 12_845_056
    assert DENSE.layer_params(Q05) == per_layer
    assert DENSE.flops_per_token(Q05) == 2 * 24 * per_layer
    assert DENSE.logits_flops(Q05) == 2 * 1024 * 151936
    # 16 kv heads of 64, K and V, bf16: 4096 bytes a position a layer
    assert DENSE.kv_bytes_per_position(Q05) == 4096
    assert 24 * DENSE.kv_bytes_per_position(Q05) == 96 * 1024
    assert DENSE.attention_flops(Q05, 100) == 4 * 24 * 100 * 16 * 64


def test_qwen3_8b_12l_hand_counts():
    # q and o: 4096 x 4096; k and v: 4096 x 1024; MLP 3 x 4096 x 12288
    per_layer = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 12288
    assert per_layer == 192_937_984
    assert DENSE.layer_params(Q8) == per_layer
    assert DENSE.flops_per_token(Q8) == 2 * 12 * per_layer
    assert 12 * DENSE.kv_bytes_per_position(Q8) == 48 * 1024
    # a decode token over 4000 positions moves K/V of each plus q and out
    assert DENSE.decode_attention_bytes(Q8, 4000) == \
        4000 * 2 * 8 * 128 * 2 + 2 * 32 * 128 * 2


def test_prefill_attended_positions():
    # tokens at 3, 4, 5 attend 4, 5 and 6 positions
    assert flops.prefill_attended(3, 3) == 15
    assert flops.prefill_attended(0, 1) == 1
    assert sum(flops.prefill_attended(s, 1) for s in range(10)) == \
        flops.prefill_attended(0, 10)
