"""Operations and bytes of the latent-attention / routed-expert decoder
(``kind: serve_latent_moe``) and its kernels, from shapes alone — beside
``costs.py``, whose functions are the dense decoder's and the ViT's. Every
function takes the configuration (the JSON object under
``benchmark/configs/``) and plain numbers; none imports the program or jax.
One multiply-add is TWO operations.

The configuration is ONE CHIP'S SHARE of a deployment: ``n_routed_experts``
counts the experts held here, ``published.n_routed_experts`` is the router's
width, and a token's expected work in the routed experts is its
``num_experts_per_tok`` choices times the share held.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmark.costs import DTYPE_BYTES


def _dtype_bytes(cfg: Dict[str, Any], key: str) -> int:
    name = str((cfg.get("assumed") or {}).get(key, "bfloat16")).split()[0]
    return DTYPE_BYTES[name]


def router_width(cfg: Dict[str, Any]) -> int:
    return int((cfg.get("published") or cfg)["n_routed_experts"])


def attention_params(cfg: Dict[str, Any]) -> int:
    """wq_a, wq_b, wkv_a, wkv_b, wo of one layer."""
    h, nh = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    q, r = int(cfg["q_lora_rank"]), int(cfg["kv_lora_rank"])
    dn, dr = int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"])
    dv = int(cfg["v_head_dim"])
    return (h * q + q * nh * (dn + dr) + h * (r + dr)
            + r * nh * (dn + dv) + nh * dv * h)


def expert_params(cfg: Dict[str, Any]) -> int:
    """gate, up and down of ONE expert."""
    return 3 * int(cfg["hidden_size"]) * int(cfg["moe_intermediate_size"])


def layer_params_outside_experts(cfg: Dict[str, Any]) -> int:
    """Attention, shared expert(s), router and the four norms."""
    h = int(cfg["hidden_size"])
    return (attention_params(cfg)
            + int(cfg.get("n_shared_experts", 0)) * expert_params(cfg)
            + h * router_width(cfg)
            + 2 * h + int(cfg["q_lora_rank"]) + int(cfg["kv_lora_rank"]))


def latent_moe_param_count(cfg: Dict[str, Any]) -> int:
    """Every parameter stored HERE: the layers with the experts held,
    embedding, final norm, untied head."""
    h, v = int(cfg["hidden_size"]), int(cfg["vocab_size"])
    per_layer = layer_params_outside_experts(cfg) \
        + int(cfg["n_routed_experts"]) * expert_params(cfg)
    return int(cfg["num_hidden_layers"]) * per_layer + 2 * v * h + h


def latent_moe_weight_bytes(cfg: Dict[str, Any]) -> int:
    return latent_moe_param_count(cfg) * _dtype_bytes(cfg, "param_dtype")


def latent_moe_flops_per_token(cfg: Dict[str, Any]) -> float:
    """Forward operations of THIS CHIP'S SHARE for one token: 2 per matmul
    parameter of attention, shared expert, router and head, plus the
    routed experts at the expected ``num_experts_per_tok x held / router
    width`` choices a token. Attention over the cached context is left
    out (a lower bound, as ``costs.llama_flops_per_token``)."""
    h, v = int(cfg["hidden_size"]), int(cfg["vocab_size"])
    held_choices = int(cfg["num_experts_per_tok"]) \
        * int(cfg["n_routed_experts"]) / router_width(cfg)
    per_layer = (attention_params(cfg)
                 + int(cfg.get("n_shared_experts", 0)) * expert_params(cfg)
                 + h * router_width(cfg)
                 + held_choices * expert_params(cfg))
    return 2.0 * (int(cfg["num_hidden_layers"]) * per_layer + h * v)


def latent_bytes_per_token(cfg: Dict[str, Any]) -> int:
    """One cached position over all layers: latent + rotary key."""
    width = int(cfg["kv_lora_rank"]) + int(cfg["qk_rope_head_dim"])
    b = DTYPE_BYTES[(cfg.get("engine") or {}).get("kv_dtype", "bfloat16")]
    return width * b * int(cfg["num_hidden_layers"])


def moe_grouped_cost(cfg: Dict[str, Any], experts_touched: float,
                     rows_held: float) -> Dict[str, float]:
    """The three grouped products (gate, up, down) of ONE expert layer in
    ONE call: the kernels of the experts TOUCHED (at least one row) are
    streamed once, the rows in and out beside them; the flops are those of
    the rows that fell on held experts."""
    h, f = int(cfg["hidden_size"]), int(cfg["moe_intermediate_size"])
    b = _dtype_bytes(cfg, "compute_dtype")
    return {"bytes": b * (experts_touched * 3.0 * h * f
                          + rows_held * (2.0 * h + 3.0 * f)),
            "flops": rows_held * 3.0 * 2.0 * h * f}


def latent_step_cost(cfg: Dict[str, Any], live_tokens: float
                     ) -> Dict[str, float]:
    """One call of the single-token latent-attention kernel in ONE layer,
    over slots whose cached contexts add up to ``live_tokens``: each live
    row (latent + rotary key) is read ONCE, for scores and values both;
    every head scores it (2 x width) and weighs its latent (2 x rank)."""
    r, dr = int(cfg["kv_lora_rank"]), int(cfg["qk_rope_head_dim"])
    b = DTYPE_BYTES[(cfg.get("engine") or {}).get("kv_dtype", "bfloat16")]
    nh = int(cfg["num_attention_heads"])
    return {"bytes": float(r + dr) * b * live_tokens,
            "flops": 2.0 * nh * (2 * r + dr) * live_tokens}
