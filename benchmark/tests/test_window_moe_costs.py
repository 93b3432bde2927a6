"""costs_window_moe.py against numbers worked by hand from the published
configuration (ISSUE 37's arithmetic)."""

from benchmark import costs_latent_moe, harness
from benchmark import costs_window_moe as costs

CFG = harness.load_json("configs", "mellum2-12b-1chip.json")


def test_layers_by_kind():
    assert CFG["layer_types"] == (["sliding_attention"] * 3
                                  + ["full_attention"]) * 7
    assert costs.layers_of(CFG, "sliding_attention") == 21 \
        == CFG["sliding_layers"]
    assert costs.layers_of(CFG, "full_attention") == 7 == CFG["full_layers"]


def test_the_share_is_3_83_billion_parameters_and_the_model_12_15():
    # wq 2304 x 4096, wk and wv 2304 x 512, wo 4096 x 2304
    attention = 2 * 2304 * 4096 + 2 * 2304 * 512
    assert costs.attention_matmul_params(CFG) == attention == 21_233_664
    assert costs.expert_params(CFG) == 3 * 2304 * 896 == 6_193_152
    layer = attention + 2304 * 64 + 2 * 2304 + 16 * 6_193_152
    share = 28 * layer + 2 * 98304 * 2304 + 2304
    assert costs.window_moe_param_count(CFG) == share == 3_826_319_616
    assert abs(costs.window_moe_weight_bytes(CFG) / 2 ** 30 - 7.127) < 0.001
    whole = dict(CFG, num_experts=64)
    assert abs(costs.window_moe_param_count(whole) / 1e9 - 12.15) < 0.01


def test_cache_bytes():
    assert costs.key_bytes(CFG) == 2 * 4 * 128 * 2 == 2048
    assert costs.kv_bytes_per_token(CFG) == 7 * 2048 == 14_336
    # window 1,024 + 8 rows x 64 tokens a prefill call + one page of 32
    assert costs.ring_positions(CFG) == 1568
    assert costs.window_kv_bytes_per_slot(CFG) == 1568 * 21 * 2048 \
        == 67_436_544
    # the cell's cache: 32 slots' rings (and a scratch row) beside 32 x
    # 7,168 pooled positions; through one table it would be 13.2 GB
    rings = 33 * 67_436_544
    pool = (1 + 32 * 224) * 32 * 14_336
    assert abs((rings + pool) / 1e9 - 5.51) < 0.01
    assert abs(28 * 2048 * 32 * 7168 / 1e9 - 13.15) < 0.01


def test_flops_per_token():
    # 8 choices x 16 / 64 held = 2 routed experts a token
    layer = 21_233_664 + 2304 * 64 + 2 * 6_193_152
    assert costs.window_moe_flops_per_token(CFG) == 2.0 * (
        28 * layer + 2304 * 98304)


def test_kernel_costs():
    # 32 slots of a full window: 32 x 1,024 keys of 2,048 B
    s = costs.attn_step_cost(CFG, live_keys=32 * 1024)
    assert s["bytes"] == 32 * 1024 * 2048
    assert s["flops"] == 4 * 32 * 128 * 32 * 1024
    # bandwidth-bound: 82 us of bytes against 2.7 us of flops
    assert s["bytes"] / 819e9 > 20 * s["flops"] / 197e12
    # the experts' cost function is the other expert cell's, at these widths
    e = costs_latent_moe.moe_grouped_cost(CFG, experts_touched=16,
                                          rows_held=64)
    assert e["flops"] == 64 * 3 * 2 * 2304 * 896
    assert abs(e["bytes"] - 16 * 3 * 2304 * 896 * 2) < 0.01 * e["bytes"]
