"""Plain reference of a decoder whose layers follow a PATTERN over three
kinds — ``M`` a Mamba-2 mixer, ``*`` attention without a position
embedding, ``E`` routed experts in a latent narrower than the model beside
one shared expert — written from the published configuration in
straightforward ``jax.numpy``: float32, matmuls at ``highest``, no cache, no
kernels, the recurrence as a plain scan over TOKENS (not the chunked form),
and nothing of the program imported.

A layer is ``x + f(norm(x))`` with one mixer OR one feed-forward part;
RMSNorm with ``layer_norm_epsilon``, no bias on any linear. For ``h`` of
(t, d):

- ``M``: ``[z | xBC | dt] = h W_in`` (``H P`` | ``H P + 2 G N`` | ``H``,
  with ``H`` = ``mamba_num_heads``, ``P`` = ``mamba_head_dim``, ``G`` =
  ``n_groups``, ``N`` = ``ssm_state_size``); ``xBC = silu(conv(xBC))``,
  depthwise, causal, over the last ``conv_kernel`` positions, with bias;
  ``[x | B | C] = xBC``, head ``h`` uses group ``h // (H / G)``; ``dt =
  softplus(dt + dt_bias)``, ``A = -exp(A_log)``; per head ``S_t = exp(dt_t
  A) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t + D x_t``; ``y =
  norm_grouped(y * silu(z))``, the norm over each of the ``G`` groups of ``H
  P / G`` channels with one scale of ``H P``; ``y W_out``.
- ``*``: ``num_attention_heads`` query heads over ``num_key_value_heads`` kv
  heads of ``head_dim``, causal, scale ``head_dim^-0.5``, no rotary
  embedding.
- ``E``: router in float32, ``s = sigmoid(h W_r)``; the
  ``num_experts_per_tok`` largest of ``s + b`` (``b`` the correction bias;
  ``n_group`` = ``topk_group`` = 1, no group limiting) chosen; gates
  ``s[chosen]`` over their sum (``norm_topk_prob``) times
  ``routed_scaling_factor``; ``u = h W_down`` (d -> ``moe_latent_size``); ``r =
  sum_e g_e W2_e relu(W1_e u)^2``; ``y = r W_up + W2_s relu(W1_s h)^2``.
- Embedding, final norm, untied head.

**Departures from the published description, each on purpose**: the
multi-token-prediction module is left out (the main model's logits do not
depend on it); THE CHIP'S SHARE — the sum in ``E`` runs over the experts
HELD (``deployment.experts_held_first``, the configuration's
``n_routed_experts``) while the router stays ``published.n_routed_experts``
wide (``held=None`` gives the uncut layer: the shares-add-up test); and
what the configuration file lists under ``assumed``: no rotary embedding in
attention (``rope_theta`` and ``partial_rotary_factor`` are unused).

``quant`` makes it the CONTROL (as ``reference/latent_moe.py``): every
weight matmul's operands rounded to ``"fp8"`` (e4m3), ``"int8"`` or
``"bfloat16"`` first — except the router's, which the program computes in
float32 whatever its compute dtype; the convolution and the recurrence are
no matmuls and stay float32. So that it fits beside ~11 GB of bfloat16
weights at 4,096 positions: one expert at a time is raised to float32 (a
scan over the stack), attention takes its queries in blocks, and the head
is taken in column blocks with no (t, vocab) array kept (``_matmul``,
``_rms`` and ``_head_stats`` are ``reference/latent_moe.py``'s).

Parameters are read by name: ``tok_embed/embedding``, ``block_<i>/norm/
scale``, and under ``block_<i>/mixer`` — ``M``: ``{in_proj,out_proj}/
kernel``, ``conv1d/{kernel,bias}`` ((K, channels): ``kernel[K - 1]`` on
the token itself), ``A_log``, ``dt_bias``, ``D``, ``norm_scale``; ``*``:
``{wq,wk,wv,wo}/kernel``; ``E``: ``moe/{router,experts_up,experts_down}/
kernel`` (experts stacked over those held), ``moe/score_bias``,
``{latent_down,latent_up,shared_up,shared_down}/kernel`` —
``final_norm/scale``, ``lm_head/kernel``.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# the control's roundings, the norm and the head in column blocks are the
# other expert reference's own: one definition of what a control is
from benchmark.reference.latent_moe import (HIGHEST, _head_stats, _matmul,
                                            _rms)

#: queries a block of attention takes against all the keys
QUERY_BLOCK = 512


def _relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def _eps(cfg: Dict[str, Any]) -> float:
    return float(cfg["layer_norm_epsilon"])


def recurrence(x, dt, a, b, c, d, per_group: int):
    """``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t + D
    x_t``, a token at a time from a zero state. ``x`` (t, H, P), ``dt`` (t,
    H), ``a``, ``d`` (H,), ``b``, ``c`` (t, G, N). Returns (t, H, P)."""
    def step(s, xs):
        x_t, dt_t, b_t, c_t = xs
        bh = jnp.repeat(b_t, per_group, axis=0)               # (H, N)
        ch = jnp.repeat(c_t, per_group, axis=0)
        s = jnp.exp(dt_t * a)[:, None, None] * s \
            + (dt_t[:, None] * x_t)[:, :, None] * bh[:, None, :]
        return s, jnp.sum(s * ch[:, None, :], -1) + d[:, None] * x_t

    s0 = jnp.zeros(x.shape[1:] + (b.shape[-1],), jnp.float32)
    return jax.lax.scan(step, s0, (x, dt, b, c))[1]


def mamba(p: Dict[str, Any], h, cfg: Dict[str, Any], quant):
    t = h.shape[0]
    nh, hd = int(cfg["mamba_num_heads"]), int(cfg["mamba_head_dim"])
    g, n = int(cfg["n_groups"]), int(cfg["ssm_state_size"])
    width = int(cfg["conv_kernel"])
    d_in, gn = nh * hd, g * n
    zxd = _matmul(h, p["in_proj"]["kernel"], quant)
    z, xbc, dt = (zxd[:, :d_in], zxd[:, d_in:2 * d_in + 2 * gn],
                  zxd[:, 2 * d_in + 2 * gn:])
    w = p["conv1d"]["kernel"].astype(jnp.float32)             # (K, channels)
    padded = jnp.pad(xbc, ((width - 1, 0), (0, 0)))
    xbc = sum(padded[j:j + t] * w[j] for j in range(width)) \
        + p["conv1d"]["bias"].astype(jnp.float32)
    xbc = jax.nn.silu(xbc)
    x = xbc[:, :d_in].reshape(t, nh, hd)
    b = xbc[:, d_in:d_in + gn].reshape(t, g, n)
    c = xbc[:, d_in + gn:].reshape(t, g, n)
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(jnp.float32))
    a = -jnp.exp(p["A_log"].astype(jnp.float32))
    y = recurrence(x, dt, a, b, c, p["D"].astype(jnp.float32), nh // g)
    y = (y.reshape(t, d_in) * jax.nn.silu(z)).reshape(t, g, d_in // g)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + _eps(cfg))
    y = y.reshape(t, d_in) * p["norm_scale"].astype(jnp.float32)
    return _matmul(y, p["out_proj"]["kernel"], quant)


def attention(p: Dict[str, Any], h, cfg: Dict[str, Any], quant):
    t = h.shape[0]
    nh, nkv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    dh = int(cfg["head_dim"])
    q = _matmul(h, p["wq"]["kernel"], quant).reshape(t, nh, dh)
    k = _matmul(h, p["wk"]["kernel"], quant).reshape(t, nkv, dh)
    v = _matmul(h, p["wv"]["kernel"], quant).reshape(t, nkv, dh)
    k, v = (jnp.repeat(u, nh // nkv, axis=1) for u in (k, v))
    block = min(QUERY_BLOCK, t)
    assert t % block == 0, (t, block)
    pos = jnp.arange(t)

    def one(j):
        qj = jax.lax.dynamic_slice_in_dim(q, j * block, block, axis=0)
        scores = jnp.einsum("qhd,khd->hqk", qj, k, precision=HIGHEST) \
            * dh ** -0.5
        seen = pos[None, :] <= (j * block + jnp.arange(block))[:, None]
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", probs, v, precision=HIGHEST)

    o = jax.lax.map(one, jnp.arange(t // block)).reshape(t, nh * dh)
    return _matmul(o, p["wo"]["kernel"], quant)


def route(p: Dict[str, Any], h, cfg: Dict[str, Any]):
    """(gates, expert ids), both (t, k), over ALL the router's experts."""
    k = int(cfg["num_experts_per_tok"])
    scores = jax.nn.sigmoid(jnp.matmul(
        h, p["router"]["kernel"].astype(jnp.float32), precision=HIGHEST))
    _, chosen = jax.lax.top_k(
        scores + p["score_bias"].astype(jnp.float32), k)
    gates = jnp.take_along_axis(scores, chosen, axis=-1)
    if cfg.get("norm_topk_prob", True):
        gates = gates / jnp.sum(gates, -1, keepdims=True)
    return gates * float(cfg.get("routed_scaling_factor", 1.0)), chosen


def experts(p: Dict[str, Any], h, cfg: Dict[str, Any], quant,
            held: Optional[Tuple[int, int]], shared: bool = True):
    """The ``E`` layer's feed-forward part: the routed sum over the
    experts ``held`` = (first id, count) whose stacked kernels ``p`` holds,
    through the latent, plus (``shared``) the shared expert."""
    gates, chosen = route(p["moe"], h, cfg)
    u = _matmul(h, p["latent_down"]["kernel"], quant)
    first = held[0] if held else 0
    n = p["moe"]["experts_up"]["kernel"].shape[0]

    def one(acc, xs):
        e, w1, w2 = xs  # ONE expert raised to float32 at a time
        gate = jnp.sum(jnp.where(chosen == first + e, gates, 0.0), -1)
        y = _matmul(_relu2(_matmul(u, w1, quant)), w2, quant)
        return acc + gate[:, None] * y, None

    r, _ = jax.lax.scan(one, jnp.zeros_like(u), (
        jnp.arange(n), p["moe"]["experts_up"]["kernel"],
        p["moe"]["experts_down"]["kernel"]))
    y = _matmul(r, p["latent_up"]["kernel"], quant)
    if shared:
        y = y + _matmul(_relu2(_matmul(h, p["shared_up"]["kernel"], quant)),
                        p["shared_down"]["kernel"], quant)
    return y


def held_experts(cfg: Dict[str, Any]) -> Optional[Tuple[int, int]]:
    dep = cfg.get("deployment")
    if not dep:
        return None
    return int(dep.get("experts_held_first", 0)), int(cfg["n_routed_experts"])


def layer(kind: str, p: Dict[str, Any], x, cfg: Dict[str, Any], quant=None,
          held: Optional[Tuple[int, int]] = None):
    h = _rms(x, p["norm"]["scale"], _eps(cfg))
    if kind == "M":
        return x + mamba(p["mixer"], h, cfg, quant)
    if kind == "*":
        return x + attention(p["mixer"], h, cfg, quant)
    if kind == "E":
        return x + experts(p["mixer"], h, cfg, quant, held)
    raise ValueError(f"a layer is 'M', 'E' or '*', not {kind!r}")


def hidden(params: Dict[str, Any], ids: jnp.ndarray, cfg: Dict[str, Any],
           quant: Optional[str] = None) -> jnp.ndarray:
    """Final-norm activations (t, d), float32, of ONE sequence ``ids``
    (t,) under a causal mask."""
    held = held_experts(cfg)
    x = params["tok_embed"]["embedding"][ids].astype(jnp.float32)
    for i, kind in enumerate(cfg["hybrid_override_pattern"]):
        x = layer(kind, params[f"block_{i}"], x, cfg, quant, held)
    return _rms(x, params["final_norm"]["scale"], _eps(cfg))


def forward(params: Dict[str, Any], ids: jnp.ndarray, cfg: Dict[str, Any],
            quant: Optional[str] = None) -> jnp.ndarray:
    """Logits (t, vocab), float32: for small sizes (tests)."""
    return _matmul(hidden(params, ids, cfg, quant),
                   params["lm_head"]["kernel"], quant)


def served_token_gaps(params, cfg, prompt: np.ndarray, served: np.ndarray,
                      pad_to: int, quant: Optional[str] = None,
                      _jit_cache: Dict = {}) -> Dict[str, Any]:
    """Teacher-force ``prompt + served`` through the reference and read,
    at every position that produced a served token, how far that token's
    logit lies below the reference's best (0 where they agree). With
    ``quant`` the gap read is that of the token the CONTROL (the same
    positions in that precision) puts first. Same contract as
    ``reference/llama.py``'s. The padding FOLLOWS the sequence, and every
    layer here is causal, so it changes nothing before it."""
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
