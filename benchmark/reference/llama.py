"""Plain reference of the decoder the serving cells run: a dense GQA
transformer with RMSNorm, rotary positions (the two-halves convention of
the HF Llama/Mistral checkpoints), SwiGLU, LoRA on the seven projections of
a block and an untied head — written from the published description in
straightforward ``jax.numpy``, float32, matmuls at ``highest``. No cache, no
kernels, no batching tricks, and nothing of the program is imported.

``quant`` makes it the CONTROL, not a model: the same forward with every
base matmul's operands rounded to a lower precision — ``"fp8"`` (e4m3) or
``"int8"`` (weights per output channel, rows per token, symmetric absmax):
the two nearest below the bfloat16 the cells state, int8 being the step a
later PR would be tempted by on a chip whose MXU takes it — or
``"bfloat16"`` (for the float32 rehearsal configurations).

Parameters are the benchmark's own tree (``benchmark/weights.py``), read by
name: ``tok_embed/embedding``, ``block_<i>/{RMSNorm_0,RMSNorm_1}/scale``,
``block_<i>/attn/{wq,wk,wv,wo}``, ``block_<i>/{gate,up,down}`` (each with
``kernel``, ``lora_a``, ``lora_b``), ``final_norm/scale``,
``lm_head/kernel``.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def _fake_int8(x: jnp.ndarray, axis: int) -> jnp.ndarray:
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True),
                    1e-12) / 127.0
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def _fake_fp8(x: jnp.ndarray, axis: int) -> jnp.ndarray:
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True),
                    1e-12) / 448.0  # e4m3's largest finite value
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _matmul(x, w, quant: Optional[str]):
    """``x @ w`` with both operands rounded to the control's precision
    first: one scale a row of ``x`` (a token), one an output channel."""
    if quant == "int8":
        x, w = _fake_int8(x, -1), _fake_int8(w, 0)
    elif quant == "fp8":
        x, w = _fake_fp8(x, -1), _fake_fp8(w, 0)
    elif quant == "bfloat16":
        x = x.astype(jnp.bfloat16).astype(jnp.float32)
        w = w.astype(jnp.bfloat16).astype(jnp.float32)
    elif quant is not None:
        raise ValueError(f"unknown control precision {quant!r}")
    return jnp.matmul(x, w, precision=HIGHEST)


def _dense(x, p: Dict[str, Any], alpha_over_r: float, quant):
    y = _matmul(x, p["kernel"], quant)
    if "lora_a" in p:
        y = y + jnp.matmul(jnp.matmul(x, p["lora_a"], precision=HIGHEST),
                           p["lora_b"], precision=HIGHEST) * alpha_over_r
    return y


def _rms(x, scale, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def _rope(x, theta: float):
    """x: (t, heads, dh); position i rotates pair (j, j + dh/2) by
    i * theta^(-j / (dh/2))."""
    t, _, dh = x.shape
    half = dh // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def forward(params: Dict[str, Any], ids: jnp.ndarray, cfg: Dict[str, Any],
            quant: Optional[str] = None) -> jnp.ndarray:
    """Logits (t, vocab), float32, of ONE sequence ``ids`` (t,) under a
    causal mask. Positions past a sequence's real end may hold padding:
    no earlier position sees them."""
    n_q = int(cfg["num_attention_heads"])
    n_kv = int(cfg["num_key_value_heads"])
    dh = int(cfg.get("head_dim") or cfg["hidden_size"] // n_q)
    eps = float(cfg.get("rms_norm_eps", 1e-5))
    theta = float(cfg.get("rope_theta", 10000.0))
    assumed = cfg.get("assumed") or {}
    r = int(assumed.get("lora_rank", 0))
    a_r = float(assumed.get("lora_alpha", 16.0)) / r if r else 0.0
    window = cfg.get("sliding_window")
    t = ids.shape[0]
    pos = jnp.arange(t)
    mask = pos[None, :] <= pos[:, None]
    if window:  # a position sees itself and the window - 1 before it
        mask = mask & (pos[:, None] - pos[None, :] < int(window))

    x = params["tok_embed"]["embedding"][ids].astype(jnp.float32)
    for i in range(int(cfg["num_hidden_layers"])):
        p = params[f"block_{i}"]
        h = _rms(x, p["RMSNorm_0"]["scale"], eps)
        a = p["attn"]
        q = _rope(_dense(h, a["wq"], a_r, quant).reshape(t, n_q, dh), theta)
        k = _rope(_dense(h, a["wk"], a_r, quant).reshape(t, n_kv, dh),
                  theta)
        v = _dense(h, a["wv"], a_r, quant).reshape(t, n_kv, dh)
        k = jnp.repeat(k, n_q // n_kv, axis=1)
        v = jnp.repeat(v, n_q // n_kv, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) \
            / np.sqrt(dh)
        s = jnp.where(mask[None], s, -jnp.inf)
        o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v,
                       precision=HIGHEST).reshape(t, n_q * dh)
        x = x + _dense(o, a["wo"], a_r, quant)
        h = _rms(x, p["RMSNorm_1"]["scale"], eps)
        g = _dense(h, p["gate"], a_r, quant)
        u = _dense(h, p["up"], a_r, quant)
        x = x + _dense(jax.nn.silu(g) * u, p["down"], a_r, quant)
    x = _rms(x, params["final_norm"]["scale"], eps)
    return _matmul(x, params["lm_head"]["kernel"], quant)


def served_token_gaps(params, cfg, prompt: np.ndarray, served: np.ndarray,
                      pad_to: int, quant: Optional[str] = None,
                      _jit_cache: Dict = {}) -> Dict[str, Any]:
    """Teacher-force ``prompt + served`` through the reference and read,
    at every position that produced a served token, how far that token's
    logit lies below the reference's best (0 where they agree).

    With ``quant`` the same positions are ALSO run in the control
    precision, and the gap read is that of the token the control puts
    first — what a program computing in that precision would have served.
    """
    prompt = np.asarray(prompt, np.int32)
    served = np.asarray(served, np.int32)
    p, g = len(prompt), len(served)
    seq = np.concatenate([prompt, served[:-1]])
    if len(seq) > pad_to:
        raise ValueError(f"sequence {len(seq)} longer than {pad_to}")
    ids = np.zeros((pad_to,), np.int32)
    ids[:len(seq)] = seq
    key = (id(cfg), pad_to, quant)
    if key not in _jit_cache:
        def run(params, ids):
            ref = forward(params, ids, cfg)
            if quant is None:
                return ref, jnp.argmax(ref, -1)
            return ref, jnp.argmax(forward(params, ids, cfg, quant), -1)
        _jit_cache[key] = jax.jit(run)
    ref, first = _jit_cache[key](params, jnp.asarray(ids))
    ref = np.asarray(ref[p - 1:p - 1 + g])
    best = ref.max(-1)
    judged = served if quant is None else np.asarray(
        first[p - 1:p - 1 + g])
    gaps = best - ref[np.arange(g), judged]
    return {"gaps": gaps,
            "agree": int((ref.argmax(-1) == judged).sum()), "n": g,
            "logit_std": float(ref.std())}
