"""Operations and bytes of the decoder that mixes sliding-window and full
attention over routed SwiGLU experts (``kind: serve_window_moe``) and its
kernels, from shapes alone — beside ``costs.py``, ``costs_latent_moe.py``
(whose ``moe_grouped_cost`` is this kind's experts' too: it reads
``hidden_size`` and ``moe_intermediate_size``) and ``costs_hybrid_ssm.py``.
Every function takes the configuration (the JSON object under
``benchmark/configs/``) and plain numbers; none imports the program or jax.
One multiply-add is TWO operations.

The configuration is ONE CHIP'S SHARE of a deployment: ``num_experts``
counts the experts held here, ``published.num_experts`` is the router's
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
    return int((cfg.get("published") or cfg)["num_experts"])


def layers_of(cfg: Dict[str, Any], kind: str) -> int:
    """Layers whose ``layer_types`` entry is ``kind``."""
    return list(cfg["layer_types"]).count(kind)


def attention_matmul_params(cfg: Dict[str, Any]) -> int:
    """wq, wk, wv, wo of one layer, of either kind."""
    d, dh = int(cfg["hidden_size"]), int(cfg["head_dim"])
    nq, nkv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    return 2 * d * nq * dh + 2 * d * nkv * dh


def expert_params(cfg: Dict[str, Any]) -> int:
    """gate, up and down of ONE expert."""
    return 3 * int(cfg["hidden_size"]) * int(cfg["moe_intermediate_size"])


def window_moe_param_count(cfg: Dict[str, Any]) -> int:
    """Every parameter stored HERE: each layer's attention, router, two
    norms and the experts held; embedding, final norm, untied head."""
    d, v = int(cfg["hidden_size"]), int(cfg["vocab_size"])
    per_layer = (attention_matmul_params(cfg) + d * router_width(cfg)
                 + 2 * d + int(cfg["num_experts"]) * expert_params(cfg))
    return int(cfg["num_hidden_layers"]) * per_layer + 2 * v * d + d


def window_moe_weight_bytes(cfg: Dict[str, Any]) -> int:
    return window_moe_param_count(cfg) * _dtype_bytes(cfg, "param_dtype")


def window_moe_flops_per_token(cfg: Dict[str, Any]) -> float:
    """Forward operations of THIS CHIP'S SHARE for one token: 2 per matmul
    parameter of attention, router and head, plus the routed experts at
    the expected ``num_experts_per_tok x held / router width`` choices a
    token. Attention over the cached context is left out (a lower bound,
    as ``costs.llama_flops_per_token``)."""
    d, v = int(cfg["hidden_size"]), int(cfg["vocab_size"])
    held_choices = int(cfg["num_experts_per_tok"]) \
        * int(cfg["num_experts"]) / router_width(cfg)
    per_layer = (attention_matmul_params(cfg) + d * router_width(cfg)
                 + held_choices * expert_params(cfg))
    return 2.0 * (int(cfg["num_hidden_layers"]) * per_layer + d * v)


def key_bytes(cfg: Dict[str, Any]) -> int:
    """One cached position of ONE layer: K and V of every kv head."""
    b = DTYPE_BYTES[(cfg.get("engine") or {}).get("kv_dtype", "bfloat16")]
    return 2 * int(cfg["num_key_value_heads"]) * int(cfg["head_dim"]) * b


def kv_bytes_per_token(cfg: Dict[str, Any]) -> int:
    """One cached position over the layers that keep PAGES: the full
    ones."""
    return key_bytes(cfg) * layers_of(cfg, "full_attention")


def ring_positions(cfg: Dict[str, Any]) -> int:
    """Positions a slot's ring holds: the window, the tokens one prefill
    call may write for a slot (``prefill_chunk`` x the call's rows: 8, or
    fewer slots), in whole pages, and one page more."""
    eng = cfg["engine"]
    page = int(eng["kv_page_size"])
    call = min(int(eng["max_slots"]), 8) * int(eng["prefill_chunk"])
    return (-(-(int(cfg["sliding_window"]) + call) // page) + 1) * page


def window_kv_bytes_per_slot(cfg: Dict[str, Any]) -> int:
    """One slot's rings over the sliding layers."""
    return (ring_positions(cfg) * key_bytes(cfg)
            * layers_of(cfg, "sliding_attention"))


def attn_step_cost(cfg: Dict[str, Any], live_keys: float
                   ) -> Dict[str, float]:
    """ONE call of a single-token attention kernel in ONE layer (the
    sliding layers' ``window_attn_step`` or the full layers'
    ``paged_attn_step``) over slots whose visible keys add up to
    ``live_keys``: each key and value is read once; every query head
    scores a key (2 x head dim) and weighs its value (2 x head dim)."""
    nq, dh = int(cfg["num_attention_heads"]), int(cfg["head_dim"])
    return {"bytes": float(key_bytes(cfg)) * live_keys,
            "flops": 4.0 * nq * dh * live_keys}
