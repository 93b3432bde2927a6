"""costs_hybrid_ssm.py against numbers worked by hand from the published
configuration (ISSUE 35's arithmetic)."""

from benchmark import costs_hybrid_ssm as costs
from benchmark import harness

CFG = harness.load_json("configs", "nemotron-3-super-1chip.json")


def test_layers_by_kind():
    assert CFG["hybrid_override_pattern"] == "MEMEM*EMEME"
    assert [costs.layers_of(CFG, k) for k in "ME*"] == [5, 5, 1]
    assert (CFG["mamba_layers"], CFG["expert_layers"]) == (5, 5)
    whole = CFG["published"]["hybrid_override_pattern"]
    assert (whole.count("M"), whole.count("E"), whole.count("*")) == (
        40, 40, 8)
    assert whole[2:13] == CFG["hybrid_override_pattern"]


def test_a_mamba_layer_is_109_64_million():
    # in 4096 x (8192 + 10240 + 128), out 8192 x 4096, conv 10240 x 4 + bias,
    # three 128-vectors, the gated norm's 8192 and the layer's norm
    by_hand = (4096 * 18560 + 8192 * 4096 + 10240 * 4 + 10240 + 3 * 128
               + 8192 + 4096)
    assert costs.mamba_layer_params(CFG) == by_hand == 109_640_064


def test_the_share_is_5_45_billion_parameters_and_the_model_120_67():
    attention = 2 * 4096 * 4096 + 2 * 4096 * 256 + 4096
    assert attention == 35_655_680
    assert costs.expert_params(CFG) == 2 * 1024 * 2688 == 5_505_024
    outside = (4096 * 512 + 512 + 2 * 4096 * 5376 + 2 * 4096 * 1024 + 4096)
    share = (5 * 109_640_064 + attention
             + 5 * (outside + 128 * 5_505_024) + 2 * 131072 * 4096 + 4096)
    assert costs.hybrid_param_count(CFG) == share == 5_453_470_080
    assert abs(costs.hybrid_weight_bytes(CFG) / 2 ** 30 - 10.158) < 0.001
    whole = dict(CFG, n_routed_experts=512, hybrid_override_pattern=CFG[
        "published"]["hybrid_override_pattern"])
    assert abs(costs.hybrid_param_count(whole) / 1e9 - 120.67) < 0.01


def test_cache_bytes():
    assert costs.kv_bytes_per_token(CFG) == 2 * 2 * 128 * 2 == 1024
    assert costs.ssm_state_bytes_per_slot(CFG) == 5 * (
        128 * 64 * 128 * 4 + 3 * 10240 * 2) == 21_278_720


def test_flops_per_token():
    mamba = 2 * (4096 * 18560 + 8192 * 4096) + 6 * 128 * 64 * 128
    attention = 2 * (2 * 4096 * 4096 + 2 * 4096 * 256)
    # 22 choices x 128 / 512 held = 5.5 routed experts a token
    experts = 2 * (4096 * 512 + 2 * 4096 * 5376 + 2 * 4096 * 1024
                   + 5.5 * 5_505_024)
    assert costs.hybrid_flops_per_token(CFG) == (
        5 * mamba + attention + 5 * experts + 2 * 4096 * 131072)


def test_kernel_costs():
    s = costs.ssm_step_cost(CFG, rows=64)
    state = 128 * 64 * 128 * 4
    assert s["flops"] == 64 * 6 * 128 * 64 * 128
    # the state read and written is all but 0.9% of the bytes
    assert 0 < s["bytes"] - 64 * 2 * state < 0.01 * s["bytes"]
    e = costs.latent_experts_cost(CFG, experts_touched=120, rows_held=352)
    assert e["flops"] == 352 * 2 * 2 * 1024 * 2688
    assert abs(e["bytes"] - 120 * 2 * 1024 * 2688 * 2) < 0.01 * e["bytes"]
