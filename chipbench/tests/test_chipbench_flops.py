"""Operation and byte counts against hand counts for both
configurations."""
import _paths  # noqa: F401

from chipbench import flops, spec

Q05 = spec.load_json(spec.config_path("qwen2-0.5b.resident"))
Q14 = spec.load_json(spec.config_path("qwen2.5-14b-l8"))


def test_parameter_counts_match_the_published_models():
    # qwen2-0.5b, per layer: q/k/v 896x(14+2+2)x64 + bias 1152, o
    # 14x64x896, gated mlp 3x896x4864, two norms; tied embedding once
    layer = 896 * 1152 + 1152 + 14 * 64 * 896 + 3 * 896 * 4864 + 2 * 896
    assert layer == 14_912_384
    assert flops.params(Q05) == 24 * layer + 151936 * 896 + 896
    assert flops.params(Q05) == 494_032_768          # "0.49B"
    # qwen2.5-14b: 48 layers of 275,268,608 plus untied embedding and head
    layer = 5120 * 7168 + 7168 + 40 * 128 * 5120 + 3 * 5120 * 13824 \
        + 2 * 5120
    assert flops.layer_params(Q14) == layer == 275_268_608
    full = dict(Q14, num_hidden_layers=48)
    assert flops.params(full) == 14_770_033_664       # "14.7B"
    assert flops.params(Q14) == 8 * layer + 2 * 152064 * 5120 + 5120


def test_prefill_flops_by_hand():
    p = 1024
    linear = 2 * 24 * (896 * 1152 + 14 * 64 * 896 + 3 * 896 * 4864)
    attn = 4 * 24 * 14 * 64 * (p * (p + 1) // 2)
    head = 2 * 896 * 151936
    assert flops.prefill_flops(Q05, p) == p * linear + attn + head
    p = 256
    linear = 2 * 8 * (5120 * 7168 + 40 * 128 * 5120 + 3 * 5120 * 13824)
    attn = 4 * 8 * 40 * 128 * (p * (p + 1) // 2)
    assert flops.prefill_flops(Q14, p) == \
        p * linear + attn + 2 * 5120 * 152064


def test_decode_counts_by_hand():
    ctx = [100, 300]
    per_tok = lambda c: 2 * 24 * (896 * 1152 + 14 * 64 * 896
                                  + 3 * 896 * 4864) \
        + 4 * 24 * 14 * 64 * c + 2 * 896 * 151936
    assert flops.decode_flops(Q05, ctx) == per_tok(100) + per_tok(300)
    row = 2 * 24 * 2 * 64 * 2                       # K and V, bf16
    assert flops.kv_row_bytes(Q05) == row == 12_288
    weights = 2 * 494_032_768                        # tied head read once
    assert flops.decode_bytes(Q05, ctx) == \
        weights + (99 + 299) * row + 2 * row
    row14 = 2 * 8 * 8 * 128 * 2
    weights14 = 2 * (8 * 275_268_608 + 152064 * 5120 + 5120 + 3 * 5120)
    assert flops.decode_bytes(Q14, [10, 20, 30]) == \
        weights14 + (9 + 19 + 29) * row14 + 3 * row14
    assert flops.page_live_bytes(Q14, 1024) == 1024 * row14
