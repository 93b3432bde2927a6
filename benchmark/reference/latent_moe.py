"""Plain reference of a decoder with multi-head LATENT attention and, in
every layer, routed SwiGLU experts beside one shared expert — written from
the published configuration in straightforward ``jax.numpy``: float32,
matmuls at ``highest``, no cache, no kernels, keys and values EXPANDED from
the latent (nothing absorbed), and nothing of the program imported.

One layer, for ``x`` of (t, d), RMSNorm eps from the config, no biases:

1. ``h = norm(x)``; ``c_q = norm_q(h W_qa)``; ``q = c_q W_qb`` -> heads x
   ``[q_nope | q_rope]``.
2. ``[c_kv | k_r] = h W_kva``; ``c_kv = norm_kv(c_kv)``; ``k_r`` is one
   rotary key a token, shared by all heads.
3. Rotary on ``q_rope`` and ``k_r``: pairs ``(2i, 2i + 1)``
   (``rope_interleave``), YaRN frequencies from ``rope_parameters``.
4. ``[k_nope | v]`` per head ``= c_kv W_kvb``; scores ``(q_nope . k_nope +
   q_rope . k_r) * s``, causal, softmax, times ``v``, ``W_o``, added to x.
5. ``h = norm(x)``; router logits ``h W_r`` in float32; top-k of their
   softmax; gates = the chosen probabilities over their sum
   (``norm_topk_prob``) times ``routed_scaling_factor``; ``y = sum_e g_e
   down_e(silu(gate_e h) * up_e h)`` plus the shared expert; added to x.
6. Final norm, untied head.

**Departures from the published description, each on purpose**: the vision
tower is left out (text only); THE CHIP'S SHARE — the sum in step 5 runs
over the experts HELD (``deployment.experts_held_first``, the
configuration's ``n_routed_experts``) while the router stays
``published.n_routed_experts`` wide (``held=None`` gives the uncut layer:
the shares-add-up test); and the three rules the config does not state,
which the configuration file lists under ``assumed``: softmax router
scores without a correction bias; ``s = qk_head_dim^-0.5 * m^2`` with ``m
= 0.1 * mscale_all_dim * ln(factor) + 1``; queries times ``1 +
llama_4_scaling_beta * ln(1 + floor(pos / original_max))``.

``quant`` makes it the CONTROL (as ``reference/llama.py``): every weight
matmul's operands rounded to ``"fp8"`` (e4m3), ``"int8"`` or
``"bfloat16"`` first — except the router's, which the program computes in
float32 whatever its compute dtype, so a program in the control precision
would too. So that it fits beside ~11 GB of bfloat16 weights, one expert at
a time is raised to float32 (a scan over the stack) and the head is taken
in column blocks, with no (t, vocab) array kept.

Parameters are read by name: ``tok_embed/embedding``,
``block_<i>/{attn_norm,ffn_norm}/scale``, ``block_<i>/attn/{wq_a,wq_b,
wkv_a,wkv_b,wo}/kernel`` and ``{q_norm,kv_norm}/scale``,
``block_<i>/moe/{router,experts_gate,experts_up,experts_down}/kernel``
(experts stacked over those held), ``block_<i>/shared_{gate,up,down}/
kernel``, ``final_norm/scale``, ``lm_head/kernel``.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
#: head columns taken at a time
HEAD_BLOCK = 16384


def _fake_int8(x: jnp.ndarray, axis: int) -> jnp.ndarray:
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True),
                    1e-12) / 127.0
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def _fake_fp8(x: jnp.ndarray, axis: int) -> jnp.ndarray:
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True),
                    1e-12) / 448.0  # e4m3's largest finite value
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _matmul(x, w, quant: Optional[str]):
    """``x @ w`` in float32 with both operands rounded to the control's
    precision first: one scale a row of ``x``, one an output channel."""
    x, w = x.astype(jnp.float32), w.astype(jnp.float32)
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


def _rms(x, scale, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def yarn_inv_freq(rp: Dict[str, Any], dim: int) -> np.ndarray:
    """The ``dim / 2`` frequencies of ``rope_parameters``: plain
    ``theta^(-2i/dim)`` for another ``rope_type`` than ``yarn``; for yarn
    the blend of those and the same over ``factor`` by a linear ramp
    between the two correction dims (``beta_fast``, ``beta_slow``)."""
    theta = float(rp["rope_theta"])
    plain = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if rp.get("rope_type", rp.get("type")) != "yarn":
        return plain.astype(np.float32)
    factor = float(rp["factor"])
    orig = float(rp["original_max_position_embeddings"])

    def correction_dim(turns: float) -> float:
        return dim * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(float(rp["beta_fast"]))), 0)
    high = min(math.ceil(correction_dim(float(rp["beta_slow"]))), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3),
                   0.0, 1.0)
    return (plain / factor * ramp + plain * (1.0 - ramp)).astype(np.float32)


def _mscale(factor: float, m: float) -> float:
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def attention_scales(cfg: Dict[str, Any]) -> Tuple[float, float]:
    """(factor on cos and sin, softmax scale ``s``)."""
    rp = cfg["rope_parameters"]
    s = float(cfg["qk_head_dim"]) ** -0.5
    if rp.get("rope_type", rp.get("type")) != "yarn":
        return 1.0, s
    factor = float(rp["factor"])
    m_all = _mscale(factor, float(rp.get("mscale_all_dim", 0) or 0))
    m = _mscale(factor, float(rp.get("mscale", 1)))
    return m / m_all, s * m_all * m_all


def query_scale(cfg: Dict[str, Any], pos: jnp.ndarray) -> jnp.ndarray:
    rp = cfg["rope_parameters"]
    beta = float(rp.get("llama_4_scaling_beta", 0) or 0)
    orig = float(rp["original_max_position_embeddings"])
    return 1.0 + beta * jnp.log1p(jnp.floor(
        pos.astype(jnp.float32) / orig))


def _rope(x, pos, inv_freq, factor: float):
    """x: (t, ..., dim); pair (2i, 2i + 1) turns by pos * inv_freq[i]."""
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(inv_freq)
    ang = ang.reshape((ang.shape[0],) + (1,) * (x.ndim - 2)
                      + (ang.shape[1],))
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * cos - odd * sin, even * sin + odd * cos], -1)
    return out.reshape(x.shape)


def attention(p: Dict[str, Any], h, pos, cfg: Dict[str, Any], quant):
    t = h.shape[0]
    nh = int(cfg["num_attention_heads"])
    r = int(cfg["kv_lora_rank"])
    dn, dr = int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"])
    dv = int(cfg["v_head_dim"])
    eps = float(cfg["rms_norm_eps"])
    inv_freq = yarn_inv_freq(cfg["rope_parameters"], dr)
    rot, s = attention_scales(cfg)
    c_q = _rms(_matmul(h, p["wq_a"]["kernel"], quant),
               p["q_norm"]["scale"], eps)
    q = _matmul(c_q, p["wq_b"]["kernel"], quant).reshape(t, nh, dn + dr)
    kva = _matmul(h, p["wkv_a"]["kernel"], quant)
    c_kv = _rms(kva[:, :r], p["kv_norm"]["scale"], eps)
    k_r = _rope(kva[:, r:], pos, inv_freq, rot)            # (t, dr)
    q_nope = q[..., :dn]
    q_rope = _rope(q[..., dn:], pos, inv_freq, rot)
    qs = query_scale(cfg, pos)[:, None, None]
    q_nope, q_rope = q_nope * qs, q_rope * qs
    kv = _matmul(c_kv, p["wkv_b"]["kernel"], quant).reshape(t, nh, dn + dv)
    scores = (jnp.einsum("qhd,khd->hqk", q_nope, kv[..., :dn],
                         precision=HIGHEST)
              + jnp.einsum("qhd,kd->hqk", q_rope, k_r,
                           precision=HIGHEST)) * s
    seen = pos[None, :] <= pos[:, None]
    probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
    o = jnp.einsum("hqk,khd->qhd", probs, kv[..., dn:],
                   precision=HIGHEST).reshape(t, nh * dv)
    return _matmul(o, p["wo"]["kernel"], quant)


def routed_experts(p: Dict[str, Any], h, cfg: Dict[str, Any], quant,
                   held: Optional[Tuple[int, int]]):
    """Step 5's routed sum over the experts ``held`` = (first id, count)
    whose stacked kernels ``p`` holds; gates from ALL the router's
    logits."""
    k = int(cfg["num_experts_per_tok"])
    logits = jnp.matmul(h, p["router"]["kernel"].astype(jnp.float32),
                        precision=HIGHEST)
    probs = jax.nn.softmax(logits, -1)
    top_p, top_i = jax.lax.top_k(probs, k)
    if cfg.get("norm_topk_prob", True):
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    top_p = top_p * float(cfg.get("routed_scaling_factor", 1.0))
    n = p["experts_gate"]["kernel"].shape[0]
    first = held[0] if held else 0

    def one(acc, xs):
        e, wg, wu, wd = xs  # ONE expert raised to float32 at a time
        gate = jnp.sum(jnp.where(top_i == first + e, top_p, 0.0), -1)
        y = _matmul(jax.nn.silu(_matmul(h, wg, quant))
                    * _matmul(h, wu, quant), wd, quant)
        return acc + gate[:, None] * y, None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(h), (
        jnp.arange(n), p["experts_gate"]["kernel"],
        p["experts_up"]["kernel"], p["experts_down"]["kernel"]))
    return acc


def layer(p: Dict[str, Any], x, pos, cfg: Dict[str, Any], quant=None,
          held: Optional[Tuple[int, int]] = None,
          shared: bool = True):
    """One decoder layer. ``shared=False`` leaves the shared expert out
    (for adding up the chips' shares, where it is counted once)."""
    eps = float(cfg["rms_norm_eps"])
    x = x + attention(p["attn"], _rms(x, p["attn_norm"]["scale"], eps),
                      pos, cfg, quant)
    h = _rms(x, p["ffn_norm"]["scale"], eps)
    y = routed_experts(p["moe"], h, cfg, quant, held)
    if shared and "shared_gate" in p:
        y = y + _matmul(
            jax.nn.silu(_matmul(h, p["shared_gate"]["kernel"], quant))
            * _matmul(h, p["shared_up"]["kernel"], quant),
            p["shared_down"]["kernel"], quant)
    return x + y


def held_experts(cfg: Dict[str, Any]) -> Optional[Tuple[int, int]]:
    dep = cfg.get("deployment")
    if not dep:
        return None
    return int(dep.get("experts_held_first", 0)), int(cfg["n_routed_experts"])


def hidden(params: Dict[str, Any], ids: jnp.ndarray, cfg: Dict[str, Any],
           quant: Optional[str] = None) -> jnp.ndarray:
    """Final-norm activations (t, d), float32, of ONE sequence ``ids``
    (t,) under a causal mask."""
    pos = jnp.arange(ids.shape[0])
    held = held_experts(cfg)
    x = params["tok_embed"]["embedding"][ids].astype(jnp.float32)
    for i in range(int(cfg["num_hidden_layers"])):
        x = layer(params[f"block_{i}"], x, pos, cfg, quant, held)
    return _rms(x, params["final_norm"]["scale"],
                float(cfg["rms_norm_eps"]))


def forward(params: Dict[str, Any], ids: jnp.ndarray, cfg: Dict[str, Any],
            quant: Optional[str] = None) -> jnp.ndarray:
    """Logits (t, vocab), float32: for small sizes (tests)."""
    return _matmul(hidden(params, ids, cfg, quant),
                   params["lm_head"]["kernel"], quant)


def _head_stats(x, w, judged, x_first=None, quant=None):
    """Over the head ``w`` (d, vocab) in column blocks, for rows ``x``:
    each row's best logit and its index, the logit at ``judged`` (or,
    with ``x_first``, at the index the CONTROL rows ``x_first`` put
    first), and the sum and sum of squares of all logits."""
    t, vocab = x.shape[0], w.shape[1]
    block = min(HEAD_BLOCK, vocab)
    assert vocab % block == 0, (vocab, block)

    def one(carry, j):
        best, arg, cbest, carg, s1, s2 = carry
        wj = jax.lax.dynamic_slice_in_dim(w, j * block, block, axis=1)
        lg = _matmul(x, wj, None)
        m, a = jnp.max(lg, -1), jnp.argmax(lg, -1) + j * block
        arg = jnp.where(m > best, a, arg)
        best = jnp.maximum(best, m)
        if x_first is not None:
            lc = _matmul(x_first, wj, quant)
            mc, ac = jnp.max(lc, -1), jnp.argmax(lc, -1) + j * block
            carg = jnp.where(mc > cbest, ac, carg)
            cbest = jnp.maximum(cbest, mc)
        return (best, arg, cbest, carg, s1 + jnp.sum(lg),
                s2 + jnp.sum(lg * lg)), None

    neg = jnp.full((t,), -jnp.inf, jnp.float32)
    zero = jnp.zeros((t,), jnp.int32)
    (best, arg, _, carg, s1, s2), _ = jax.lax.scan(
        one, (neg, zero, neg, zero, jnp.float32(0), jnp.float32(0)),
        jnp.arange(vocab // block))
    if x_first is not None:
        judged = carg
    at = jnp.einsum("td,dt->t", x, w[:, judged].astype(jnp.float32),
                    precision=HIGHEST)
    n = t * vocab
    std = jnp.sqrt(jnp.maximum(s2 / n - (s1 / n) ** 2, 0.0))
    return best, arg, at, judged, std


def served_token_gaps(params, cfg, prompt: np.ndarray, served: np.ndarray,
                      pad_to: int, quant: Optional[str] = None,
                      _jit_cache: Dict = {}) -> Dict[str, Any]:
    """Teacher-force ``prompt + served`` through the reference and read,
    at every position that produced a served token, how far that token's
    logit lies below the reference's best (0 where they agree). With
    ``quant`` the gap read is that of the token the CONTROL (the same
    positions in that precision) puts first. Same contract as
    ``reference/llama.py``'s."""
    prompt = np.asarray(prompt, np.int32)
    served = np.asarray(served, np.int32)
    p, g = len(prompt), len(served)
    seq = np.concatenate([prompt, served[:-1]])
    if len(seq) > pad_to:
        raise ValueError(f"sequence {len(seq)} longer than {pad_to}")
    ids = np.zeros((pad_to,), np.int32)
    ids[:len(seq)] = seq
    judged = np.zeros((pad_to,), np.int32)
    judged[p - 1:p - 1 + g] = served
    key = (id(cfg), pad_to, quant)
    if key not in _jit_cache:
        def run(params, ids, judged):
            x = hidden(params, ids, cfg)
            x_first = None if quant is None else hidden(params, ids, cfg,
                                                        quant)
            return _head_stats(x, params["lm_head"]["kernel"], judged,
                               x_first, quant)
        _jit_cache[key] = jax.jit(run)
    best, arg, at, judged, std = _jit_cache[key](
        params, jnp.asarray(ids), jnp.asarray(judged))
    rows = slice(p - 1, p - 1 + g)
    best, arg, at, judged = (np.asarray(a)[rows]
                             for a in (best, arg, at, judged))
    return {"gaps": best - at, "agree": int((arg == judged).sum()),
            "n": g, "logit_std": float(std)}
