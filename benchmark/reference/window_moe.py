"""Plain reference of a decoder whose layers mix SLIDING-WINDOW and FULL
attention by ``layer_types`` — each kind with a rotary table of its own
(``rope_parameters[kind]``) — and whose feed-forward part, in every layer,
is a routed SwiGLU expert layer with no shared expert; written from the
published configuration in straightforward ``jax.numpy``: float32, matmuls
at ``highest``, no cache, no kernels, no batching, and nothing of the
program imported.

One layer ``i``, for ``x`` of (t, d), RMSNorm with ``rms_norm_eps``, no bias
on any linear:

1. ``h = norm(x)``; ``q = h W_q`` -> ``num_attention_heads`` heads of
   ``head_dim``, ``k = h W_k``, ``v = h W_v`` -> ``num_key_value_heads``
   heads (query head ``j`` uses kv head ``j // (heads / kv heads)``).
2. Rotary on q and k, HALF-SPLIT pairs ``(j, j + head_dim / 2)``, by the
   frequencies of ``rope_parameters[layer_types[i]]``: ``theta^(-2j/dim)``
   (``rope_type`` ``default``), or YaRN's blend of those and the same over
   ``factor`` (``yarn``) with cos and sin times ``attention_factor`` (absent:
   ``0.1 ln(factor) + 1``).
3. Scores ``q . k * head_dim^-0.5``; the query at position ``a`` sees the
   keys ``b <= a`` (``full_attention``), or ``a - sliding_window < b <= a``
   (``sliding_attention``); softmax, times ``v``, ``W_o``, added to x.
4. ``h = norm(x)``; router logits ``h W_r`` in float32, softmax over all
   ``num_experts``; the ``num_experts_per_tok`` largest, divided by their
   sum (``norm_topk_prob``); ``y = sum_e g_e down_e(silu(gate_e h) * up_e
   h)``; added to x.
5. Final norm, untied head.

**Departures from the published description, each on purpose**: THE CHIP'S
SHARE — the sum in step 4 runs over the experts HELD
(``deployment.experts_held_first``, the configuration's ``num_experts``)
while the router stays ``published.num_experts`` wide (``held=None`` gives
the uncut layer: the shares-add-up test); the dense ``intermediate_size`` is
used by no layer (``mlp_layer_types`` is all ``sparse``: anything else is
refused); and what the configuration file lists under ``assumed``, the config
having no key for them: no per-head norm of q and k, half-split rotary
pairs, the window convention of step 3, and no multi-token-prediction head
(the main model's logits do not depend on one).

``quant`` makes it the CONTROL (as ``reference/latent_moe.py``): every
weight matmul's operands rounded to ``"fp8"``, ``"int8"`` or ``"bfloat16"``
first — except the router's, which the program computes in float32 whatever
its compute dtype. So that a 7,168-token request fits beside ~7.7 GB of
bfloat16 weights: one expert at a time is raised to float32 (a scan over the
stack), attention takes its queries in blocks of ``QUERY_BLOCK`` and, in a
sliding layer, against the keys its block can see alone, and the head is
taken in column blocks with no (t, vocab) array kept (``_matmul``, ``_rms``,
``_head_stats``, ``routed_experts`` and ``yarn_inv_freq`` are
``reference/latent_moe.py``'s: one definition of a control, of the softmax
router and of YaRN's table).

Parameters are read by name; layer ``i`` is the two entries ``block_<2i>``
(attention) and ``block_<2i + 1>`` (experts) of the program's pattern:
``tok_embed/embedding``, ``block_<n>/norm/scale``, ``block_<2i>/mixer/{wq,
wk,wv,wo}/kernel``, ``block_<2i+1>/mixer/moe/{router,experts_gate,
experts_up,experts_down}/kernel`` (experts stacked over those held),
``final_norm/scale``, ``lm_head/kernel``.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.latent_moe import (HIGHEST, _head_stats, _matmul,
                                            _rms, routed_experts,
                                            yarn_inv_freq)

#: queries a block of attention takes against the keys it can see
QUERY_BLOCK = 512


def rotary(cfg: Dict[str, Any], kind: str) -> Tuple[np.ndarray, float]:
    """(the ``head_dim / 2`` frequencies, the factor on cos and sin) of
    the layers of ``kind``."""
    rp = cfg["rope_parameters"][kind]
    factor = 1.0
    if rp.get("rope_type") == "yarn":
        factor = float(rp.get("attention_factor")
                       or 0.1 * math.log(float(rp["factor"])) + 1.0)
    return yarn_inv_freq(rp, int(cfg["head_dim"])), factor


def _rope(x, pos, inv_freq, factor: float):
    """x: (t, heads, dim); pair (j, j + dim / 2) turns by pos *
    inv_freq[j]."""
    ang = pos.astype(jnp.float32)[:, None, None] * jnp.asarray(inv_freq)
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(p: Dict[str, Any], h, pos, cfg: Dict[str, Any], kind: str,
              quant):
    t = h.shape[0]
    nh, nkv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    dh = int(cfg["head_dim"])
    if kind not in ("sliding_attention", "full_attention"):
        raise ValueError(f"layer type {kind!r} is not written down here")
    window = int(cfg["sliding_window"]) if kind == "sliding_attention" \
        else None
    inv_freq, factor = rotary(cfg, kind)
    q = _matmul(h, p["wq"]["kernel"], quant).reshape(t, nh, dh)
    k = _matmul(h, p["wk"]["kernel"], quant).reshape(t, nkv, dh)
    v = _matmul(h, p["wv"]["kernel"], quant).reshape(t, nkv, dh)
    q, k = _rope(q, pos, inv_freq, factor), _rope(k, pos, inv_freq, factor)
    k, v = (jnp.repeat(u, nh // nkv, axis=1) for u in (k, v))
    block = min(QUERY_BLOCK, t)
    assert t % block == 0, (t, block)
    # the keys a block of queries can see: all of them, or in a sliding
    # layer the block's own and the window before it (rounded up to whole
    # blocks, the sequence padded in front so that every slice is whole)
    back = 0 if window is None else min(-(-(window - 1) // block) * block, t)
    span = t if window is None else back + block
    kp, vp = (jnp.pad(u, ((back, 0), (0, 0), (0, 0))) for u in (k, v))

    def one(j):
        qj = jax.lax.dynamic_slice_in_dim(q, j * block, block, axis=0)
        start = 0 if window is None else j * block  # in the padded keys
        kj = jax.lax.dynamic_slice_in_dim(kp, start, span, axis=0)
        vj = jax.lax.dynamic_slice_in_dim(vp, start, span, axis=0)
        q_pos = (j * block + jnp.arange(block))[:, None]
        k_pos = (start - back + jnp.arange(span))[None, :]
        seen = (k_pos <= q_pos) & (k_pos >= 0)
        if window is not None:
            seen &= k_pos > q_pos - window
        scores = jnp.einsum("qhd,khd->hqk", qj, kj, precision=HIGHEST) \
            * dh ** -0.5
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", probs, vj, precision=HIGHEST)

    o = jax.lax.map(one, jnp.arange(t // block)).reshape(t, nh * dh)
    return _matmul(o, p["wo"]["kernel"], quant)


def held_experts(cfg: Dict[str, Any]) -> Optional[Tuple[int, int]]:
    dep = cfg.get("deployment")
    if not dep:
        return None
    return int(dep.get("experts_held_first", 0)), int(cfg["num_experts"])


def layer(i: int, params: Dict[str, Any], x, pos, cfg: Dict[str, Any],
          quant=None, held: Optional[Tuple[int, int]] = None):
    """Decoder layer ``i``: attention of its kind, then the routed
    experts ``held`` = (first id, count), each on the norm of the stream."""
    if cfg["mlp_layer_types"][i] != "sparse":
        raise ValueError("only sparse feed-forward layers are written down")
    eps = float(cfg["rms_norm_eps"])
    pa, pe = params[f"block_{2 * i}"], params[f"block_{2 * i + 1}"]
    x = x + attention(pa["mixer"], _rms(x, pa["norm"]["scale"], eps), pos,
                      cfg, cfg["layer_types"][i], quant)
    return x + routed_experts(pe["mixer"]["moe"],
                              _rms(x, pe["norm"]["scale"], eps), cfg, quant,
                              held)


def hidden(params: Dict[str, Any], ids: jnp.ndarray, cfg: Dict[str, Any],
           quant: Optional[str] = None) -> jnp.ndarray:
    """Final-norm activations (t, d), float32, of ONE sequence ``ids``
    (t,) under each layer's own mask."""
    pos = jnp.arange(ids.shape[0])
    held = held_experts(cfg)
    x = params["tok_embed"]["embedding"][ids].astype(jnp.float32)
    for i in range(int(cfg["num_hidden_layers"])):
        x = layer(i, params, x, pos, cfg, quant, held)
    return _rms(x, params["final_norm"]["scale"],
                float(cfg["rms_norm_eps"]))


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
