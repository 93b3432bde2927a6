"""Operations and bytes of the benchmark's models and kernels, from shapes
alone. Every function takes a configuration (the JSON object under
``benchmark/configs/``) and plain numbers; none imports the program or jax.

One multiply-add is TWO operations, as the chips' published peaks count
them. (The "17.5 GFLOPs" often quoted for ViT-B/16 counts multiply-adds.)
"""

from __future__ import annotations

from typing import Any, Dict

#: bytes of one stored element
DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1}


# ---------------------------------------------------------------- llama
def llama_head_dim(cfg: Dict[str, Any]) -> int:
    return int(cfg.get("head_dim")
               or cfg["hidden_size"] // cfg["num_attention_heads"])


def llama_layer_matmul_params(cfg: Dict[str, Any]) -> int:
    """Base-kernel parameters of one decoder layer: wq and wo (h x h),
    wk and wv (h x kv), gate, up and down (h x f)."""
    h, f = int(cfg["hidden_size"]), int(cfg["intermediate_size"])
    dh = llama_head_dim(cfg)
    q = int(cfg["num_attention_heads"]) * dh
    kv = int(cfg["num_key_value_heads"]) * dh
    return 2 * h * q + 2 * h * kv + 3 * h * f


def llama_lora_params_per_layer(cfg: Dict[str, Any]) -> int:
    r = int((cfg.get("assumed") or {}).get("lora_rank", 0))
    if r <= 0:
        return 0
    h, f = int(cfg["hidden_size"]), int(cfg["intermediate_size"])
    dh = llama_head_dim(cfg)
    q = int(cfg["num_attention_heads"]) * dh
    kv = int(cfg["num_key_value_heads"]) * dh
    sites = [(h, q), (h, kv), (h, kv), (q, h), (h, f), (h, f), (f, h)]
    return sum(r * (i + o) for i, o in sites)


def llama_param_count(cfg: Dict[str, Any]) -> int:
    """Every stored parameter: layers (base, adapters, two norms),
    embedding, final norm, untied head."""
    h, v = int(cfg["hidden_size"]), int(cfg["vocab_size"])
    layers = int(cfg["num_hidden_layers"])
    per_layer = (llama_layer_matmul_params(cfg)
                 + llama_lora_params_per_layer(cfg) + 2 * h)
    return layers * per_layer + 2 * v * h + h


def llama_flops_per_token(cfg: Dict[str, Any], context: float = 0.0
                          ) -> float:
    """Forward operations for one token: 2 per matmul parameter (layers,
    adapters and head; the embedding is a lookup) plus attention over
    ``context`` cached positions (QK^T and PV: 4 * context * q_dim a
    layer). ``context=0`` leaves attention out: a lower bound."""
    h, v = int(cfg["hidden_size"]), int(cfg["vocab_size"])
    layers = int(cfg["num_hidden_layers"])
    q = int(cfg["num_attention_heads"]) * llama_head_dim(cfg)
    dense = layers * (llama_layer_matmul_params(cfg)
                      + llama_lora_params_per_layer(cfg)) + h * v
    return 2.0 * dense + layers * 4.0 * float(context) * q


def llama_kv_bytes_per_token(cfg: Dict[str, Any]) -> int:
    """K and V of one position over all layers, in the engine's KV type."""
    kv = int(cfg["num_key_value_heads"]) * llama_head_dim(cfg)
    b = DTYPE_BYTES[(cfg.get("engine") or {}).get("kv_dtype", "bfloat16")]
    return 2 * kv * b * int(cfg["num_hidden_layers"])


def llama_weight_bytes(cfg: Dict[str, Any]) -> int:
    b = 4 if "float32" in str((cfg.get("assumed") or {}).get(
        "param_dtype", "float32")) else 2
    return llama_param_count(cfg) * b


def paged_step_cost(cfg: Dict[str, Any], live_tokens: float
                    ) -> Dict[str, float]:
    """One call of the single-token paged-attention kernel in ONE layer,
    over slots whose cached contexts add up to ``live_tokens``: it must
    read each live K and V row once (bytes) and do QK^T and PV over them
    (flops). Queries and outputs are noise beside the KV."""
    dh = llama_head_dim(cfg)
    kv = int(cfg["num_key_value_heads"]) * dh
    q = int(cfg["num_attention_heads"]) * dh
    b = DTYPE_BYTES[(cfg.get("engine") or {}).get("kv_dtype", "bfloat16")]
    return {"bytes": 2.0 * kv * b * live_tokens,
            "flops": 4.0 * q * live_tokens}


def paged_window_cost(cfg: Dict[str, Any], live_tokens: float,
                      window: int) -> Dict[str, float]:
    """One call of the multi-token (window) kernel in one layer: every
    slot's ``window`` queries attend over its cached context; the KV is
    read once per slot, the flops scale with the window."""
    one = paged_step_cost(cfg, live_tokens)
    return {"bytes": one["bytes"], "flops": one["flops"] * window}


# ------------------------------------------------------------------ vit
def vit_seq_len(cfg: Dict[str, Any]) -> int:
    side = int(cfg["image_size"]) // int(cfg["patch_size"])
    return side * side + 1  # patches + class token


def vit_layer_matmul_params(cfg: Dict[str, Any]) -> int:
    h, f = int(cfg["hidden_size"]), int(cfg["intermediate_size"])
    return 4 * h * h + 2 * h * f  # qkv, proj, two MLP matrices


def vit_param_count(cfg: Dict[str, Any]) -> int:
    h, f = int(cfg["hidden_size"]), int(cfg["intermediate_size"])
    p, c = int(cfg["patch_size"]), int(cfg["num_channels"])
    per_layer = vit_layer_matmul_params(cfg) + 4 * h + h + f + 4 * h
    #            kernels; qkv+proj biases (3h+h); MLP biases (f+h);
    #            two LayerNorms (2 * 2h)
    return (int(cfg["num_hidden_layers"]) * per_layer
            + p * p * c * h + h            # patch embedding
            + h + vit_seq_len(cfg) * h     # class token, positions
            + 2 * h                        # final norm
            + h * int(cfg["num_labels"]) + int(cfg["num_labels"]))


def vit_forward_flops_per_sample(cfg: Dict[str, Any]) -> float:
    h = int(cfg["hidden_size"])
    s = vit_seq_len(cfg)
    p, c = int(cfg["patch_size"]), int(cfg["num_channels"])
    layers = int(cfg["num_hidden_layers"])
    dense = 2.0 * vit_layer_matmul_params(cfg) * s
    attn = 4.0 * s * s * h  # QK^T and PV over all heads
    patch = 2.0 * (s - 1) * (p * p * c) * h
    head = 2.0 * h * int(cfg["num_labels"])
    return layers * (dense + attn) + patch + head


def vit_train_flops_per_sample(cfg: Dict[str, Any]) -> float:
    """Forward plus backward (twice the forward), nothing recomputed."""
    return 3.0 * vit_forward_flops_per_sample(cfg)


def patch_embed_cost(cfg: Dict[str, Any], batch: int) -> Dict[str, float]:
    """The patch projection's forward for ``batch`` images in bf16:
    read the images and the kernel, write the tokens."""
    h = int(cfg["hidden_size"])
    p, c = int(cfg["patch_size"]), int(cfg["num_channels"])
    n = (int(cfg["image_size"]) // p) ** 2
    k = p * p * c
    return {"flops": 2.0 * batch * n * k * h,
            "bytes": 2.0 * (batch * n * k + k * h + batch * n * h)}
