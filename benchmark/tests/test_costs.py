"""costs.py against numbers worked by hand."""

from benchmark import costs, harness

MISTRAL = harness.load_json("configs", "mistral-7b-1chip.json")
VIT = harness.load_json("configs", "vit-b16.json")


def test_mistral_layer_is_218_1_million():
    # wq, wo: 4096 x 4096; wk, wv: 4096 x 1024; gate, up, down: 4096 x 14336
    by_hand = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert by_hand == 218_103_808
    assert costs.llama_layer_matmul_params(MISTRAL) == by_hand


def test_mistral_depth8_is_8_gb_of_f32():
    lora = 8 * (4 * (4096 + 4096) - 2 * 3072 + 3 * (4096 + 14336))
    assert costs.llama_lora_params_per_layer(MISTRAL) == lora == 655_360
    total = 8 * (218_103_808 + lora + 2 * 4096) + 2 * 32000 * 4096 + 4096
    assert costs.llama_param_count(MISTRAL) == total == 2_012_286_976
    assert costs.llama_weight_bytes(MISTRAL) == 4 * total  # 8.05 GB
    assert costs.llama_kv_bytes_per_token(MISTRAL) == 32 * 1024


def test_mistral_flops_per_token():
    dense = 8 * (218_103_808 + 655_360) + 4096 * 32000
    assert costs.llama_flops_per_token(MISTRAL) == 2.0 * dense
    assert costs.llama_flops_per_token(MISTRAL, context=100) \
        == 2.0 * dense + 8 * 4.0 * 100 * 4096


def test_vit_b16_counts():
    assert costs.vit_seq_len(VIT) == 197
    # the parameter count the chip reported for the template (PERF.md, PR 22)
    assert costs.vit_param_count(VIT) == 86_567_656
    layer = 2 * (4 * 768 * 768 + 2 * 768 * 3072) * 197 + 4 * 197 * 197 * 768
    fwd = 12 * layer + 2 * 196 * 768 * 768 + 2 * 768 * 1000
    assert costs.vit_forward_flops_per_sample(VIT) == fwd
    # 17.56 G multiply-adds forward — the "17.5 GFLOPs" of the model cards
    assert round(fwd / 2 / 1e9, 2) == 17.56
    # forward + backward: 52.7 G multiply-adds = 105.4 GFLOP as peaks count
    assert round(costs.vit_train_flops_per_sample(VIT) / 2 / 1e9, 1) == 52.7
    assert round(costs.vit_train_flops_per_sample(VIT) / 1e9, 1) == 105.4


def test_kernel_costs():
    c = costs.paged_step_cost(MISTRAL, live_tokens=1000)
    assert c["bytes"] == 2 * 1024 * 2 * 1000  # K and V, 8 x 128 bf16 a row
    assert c["flops"] == 4 * 4096 * 1000
    w = costs.paged_window_cost(MISTRAL, live_tokens=1000, window=4)
    assert w["bytes"] == c["bytes"] and w["flops"] == 4 * c["flops"]
    p = costs.patch_embed_cost(VIT, batch=128)
    assert p["flops"] == 2.0 * 128 * 196 * 768 * 768
