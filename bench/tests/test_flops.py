"""The work counts, against shapes worked out by hand."""
import pytest

from bench import flops

# qwen2_7b's block at published widths
Q7 = {"hidden_size": 3584, "num_attention_heads": 28,
      "num_key_value_heads": 4, "intermediate_size": 18944,
      "vocab_size": 152064}
TINY = {"hidden_size": 8, "num_attention_heads": 2,
        "num_key_value_heads": 1, "intermediate_size": 16,
        "vocab_size": 10}


def test_per_layer_params_qwen2_7b():
    # q 3584*3584, k and v 3584*512 each, o 3584*3584, gate+up 2*3584*18944,
    # down 18944*3584
    want = (3584 * 3584 * 2 + 2 * 3584 * 512 + 3 * 3584 * 18944)
    assert flops.per_layer_matmul_params(Q7) == want == 233_046_016


def test_attended_keys_counts_causal_pairs():
    # a chunk of 3 tokens at position 5 attends 6, 7 and 8 keys; a decode
    # token at position 0 attends itself
    assert flops.attended_keys([(5, 3), (0, 1)]) == 6 + 7 + 8 + 1


def test_step_flops_tiny_by_hand():
    # d=8, H=2, KV=1, Dh=4, f=16, V=10; params a layer:
    # q 8*8 + k,v 2*8*4 + o 8*8 + mlp 3*8*16 = 64+64+64+384 = 576
    assert flops.per_layer_matmul_params(TINY) == 576
    fed = [(2, 2), (7, 1)]            # 3 tokens, 2 slots
    keys = (3 + 4) + 8
    want = (2 * 3 * 2 * 576            # 2 layers of matmuls
            + 4 * 2 * 4 * keys * 2     # q.k and p.v, 2 layers
            + 2 * 8 * 10 * 2)          # the head, once a slot
    assert flops.step_flops(TINY, 2, fed) == want


def test_paged_attention_work_tiny_by_hand():
    fed = [(2, 2), (7, 1)]
    f, b = flops.paged_attention_work(TINY, 3, fed)
    assert f == 4 * 2 * 4 * (3 + 4 + 8) * 3
    # K and V of 4 and 8 positions, 1 head of 4, bf16; q and o of 3 tokens
    # of 2 heads of 4
    assert b == ((4 + 8) * 1 * 4 * 2 * 2 + 3 * 2 * 4 * 2 * 2) * 3


def test_roofline_picks_the_binding_peak():
    peaks = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.roofline_seconds(200.0, 10.0, peaks) == (2.0, "compute")
    assert flops.roofline_seconds(100.0, 50.0, peaks) == (5.0, "memory")


def test_decode_step_of_qwen2_7b_is_memory_bound():
    # 32 slots decoding at position 2000: attention reads every slot's KV
    fed = [(2000, 1)] * 32
    f, b = flops.paged_attention_work(Q7, 14, fed)
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    t, bound = flops.roofline_seconds(f, b, peaks)
    assert bound == "memory"
    assert t == pytest.approx(b / 819e9)
