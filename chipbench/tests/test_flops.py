"""Operation and byte counts against shapes worked by hand."""
import pytest

import flops

# 2 layers, d 8, 2 heads of 4, 1 KV head, d_ff 16, vocab 10
TINY = {"num_hidden_layers": 2, "hidden_size": 8, "num_attention_heads": 2,
        "num_key_value_heads": 1, "intermediate_size": 16, "vocab_size": 10}


def test_weight_and_head_flops():
    # per layer: q 8x8, k 8x4, v 8x4, o 8x8, ffn 3 x 8x16 = 64+32+32+64+384
    assert flops.weight_flops_per_token(TINY) == 2 * 2 * 576
    assert flops.head_flops(TINY) == 2 * 8 * 10


def test_attention_and_steps():
    # scores and values: 2 x (2 heads x 4 dims x ctx) MACs per layer
    assert flops.attention_flops(TINY, 5) == 4 * 2 * 2 * 4 * 5
    per_tok = 2 * 2 * 576
    assert flops.decode_flops(TINY, [3, 5]) == pytest.approx(
        2 * per_tok + 2 * 160 + 4 * 2 * 2 * 4 * (3 + 5))
    # suffix of 3 after 4 cached: contexts 5, 6, 7; head once
    assert flops.prefill_flops(TINY, 4, 3) == pytest.approx(
        3 * per_tok + 4 * 2 * 2 * 4 * 18 + 160)
    assert flops.prefill_flops(TINY, 4, 0) == 0


def test_paged_attention_bytes_count_whole_pages():
    ops, nbytes = flops.paged_attention_cost(TINY, [33, 1], 32)
    assert ops == flops.attention_flops(TINY, 34)
    # pages 2 + 1; K and V of 32 tokens x 1 head x 4 dims x 2 B; q and out
    kv = 2 * 3 * 32 * 1 * 4 * 2
    qo = 2 * 2 * 2 * 4 * 2
    assert nbytes == 2 * (kv + qo)


def test_stablelm_step_is_bandwidth_scale():
    import json
    from run import BENCH
    conf = json.loads((BENCH / "configs" / "stablelm-3b.json").read_text())
    # 2.65e9 matmul parameters per token (without embedding and head)
    assert flops.weight_flops_per_token(conf) == pytest.approx(
        2 * 32 * (4 * 2560 * 2560 + 3 * 2560 * 6912))
