"""Operations and bytes of the pattern-driven state-space / attention /
latent-expert decoder (``kind: serve_hybrid_ssm``) and its kernels, from
shapes alone — beside ``costs.py`` and ``costs_latent_moe.py``. Every
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


def layers_of(cfg: Dict[str, Any], kind: str) -> int:
    return cfg["hybrid_override_pattern"].count(kind)


def ssm_dims(cfg: Dict[str, Any]):
    """(heads, head dim, groups, state, inner width, conv channels)."""
    h, p = int(cfg["mamba_num_heads"]), int(cfg["mamba_head_dim"])
    g, n = int(cfg["n_groups"]), int(cfg["ssm_state_size"])
    return h, p, g, n, h * p, h * p + 2 * g * n


def mamba_matmul_params(cfg: Dict[str, Any]) -> int:
    """in_proj and out_proj of one ``M`` layer."""
    d = int(cfg["hidden_size"])
    h, _, _, _, d_in, channels = ssm_dims(cfg)
    return d * (d_in + channels + h) + d_in * d


def mamba_layer_params(cfg: Dict[str, Any]) -> int:
    """One ``M`` layer: the two projections, the convolution with its
    bias, ``A_log`` / ``dt_bias`` / ``D``, the gated norm's scale and the
    layer's norm."""
    h, _, _, _, d_in, channels = ssm_dims(cfg)
    return (mamba_matmul_params(cfg) + channels * int(cfg["conv_kernel"])
            + channels + 3 * h + d_in + int(cfg["hidden_size"]))


def attention_matmul_params(cfg: Dict[str, Any]) -> int:
    d, dh = int(cfg["hidden_size"]), int(cfg["head_dim"])
    nq, nkv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    return 2 * d * nq * dh + 2 * d * nkv * dh


def expert_params(cfg: Dict[str, Any]) -> int:
    """The two kernels of ONE routed expert, in the latent."""
    return 2 * int(cfg["moe_latent_size"]) * int(cfg["moe_intermediate_size"])


def expert_layer_matmul_params_outside(cfg: Dict[str, Any]) -> int:
    """Router, shared expert and the two latent projections."""
    d = int(cfg["hidden_size"])
    return (d * router_width(cfg)
            + 2 * d * int(cfg["moe_shared_expert_intermediate_size"])
            + 2 * d * int(cfg["moe_latent_size"]))


def hybrid_param_count(cfg: Dict[str, Any]) -> int:
    """Every parameter stored HERE: the pattern's layers with the experts
    held, embedding, final norm, untied head."""
    d, v = int(cfg["hidden_size"]), int(cfg["vocab_size"])
    expert_layer = (expert_layer_matmul_params_outside(cfg)
                    + router_width(cfg) + d  # correction bias, norm
                    + int(cfg["n_routed_experts"]) * expert_params(cfg))
    return (layers_of(cfg, "M") * mamba_layer_params(cfg)
            + layers_of(cfg, "*") * (attention_matmul_params(cfg) + d)
            + layers_of(cfg, "E") * expert_layer + 2 * v * d + d)


def hybrid_weight_bytes(cfg: Dict[str, Any]) -> int:
    return hybrid_param_count(cfg) * _dtype_bytes(cfg, "param_dtype")


def hybrid_flops_per_token(cfg: Dict[str, Any]) -> float:
    """Forward operations of THIS CHIP'S SHARE for one token: 2 per matmul
    parameter of the three kinds of layer and the head, the routed experts
    at the expected ``num_experts_per_tok x held / router width`` choices a
    token, and the recurrence's own arithmetic (decay, outer product and
    read-out: 6 a state element). Attention over the cached context is left
    out (a lower bound, as ``costs.llama_flops_per_token``)."""
    d, v = int(cfg["hidden_size"]), int(cfg["vocab_size"])
    h, p, _, n, _, _ = ssm_dims(cfg)
    held_choices = int(cfg["num_experts_per_tok"]) \
        * int(cfg["n_routed_experts"]) / router_width(cfg)
    return (layers_of(cfg, "M") * (2.0 * mamba_matmul_params(cfg)
                                   + 6.0 * h * p * n)
            + layers_of(cfg, "*") * 2.0 * attention_matmul_params(cfg)
            + layers_of(cfg, "E") * 2.0 * (
                expert_layer_matmul_params_outside(cfg)
                + held_choices * expert_params(cfg))
            + 2.0 * d * v)


def kv_bytes_per_token(cfg: Dict[str, Any]) -> int:
    """One cached position over the attention layers: K and V."""
    b = DTYPE_BYTES[(cfg.get("engine") or {}).get("kv_dtype", "bfloat16")]
    return (2 * int(cfg["num_key_value_heads"]) * int(cfg["head_dim"]) * b
            * layers_of(cfg, "*"))


def ssm_state_bytes_per_slot(cfg: Dict[str, Any]) -> int:
    """One slot's recurrent state (float32) and convolution tail (the
    compute dtype) over the ``M`` layers."""
    h, p, _, n, _, channels = ssm_dims(cfg)
    return layers_of(cfg, "M") * (
        h * p * n * 4 + (int(cfg["conv_kernel"]) - 1) * channels
        * _dtype_bytes(cfg, "compute_dtype"))


def ssm_step_cost(cfg: Dict[str, Any], rows: float) -> Dict[str, float]:
    """ONE call of the single-token state kernel (one ``M`` layer, one
    step) that advances ``rows`` rows: each row's state is read once and
    written once, its operands (x, dt, B, C) beside it; a state element
    takes a decay, an outer-product term and a read-out term."""
    h, p, g, n, _, _ = ssm_dims(cfg)
    return {"bytes": rows * (2.0 * h * p * n * 4
                             + 4.0 * (2 * h * p + h + 2 * g * n)),
            "flops": rows * 6.0 * h * p * n}


def latent_experts_cost(cfg: Dict[str, Any], experts_touched: float,
                        rows_held: float) -> Dict[str, float]:
    """The two grouped products (up with ``relu^2``, down) of ONE expert
    layer in ONE call: the two kernels of the experts TOUCHED (at least
    one row) are streamed once, the rows in and out beside them; the flops
    are those of the rows that fell on held experts."""
    k, f = int(cfg["moe_latent_size"]), int(cfg["moe_intermediate_size"])
    b = _dtype_bytes(cfg, "compute_dtype")
    return {"bytes": b * (experts_touched * 2.0 * k * f
                          + rows_held * (2.0 * k + 2.0 * f)),
            "flops": rows_held * 2.0 * 2.0 * k * f}
