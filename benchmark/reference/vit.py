"""Plain reference of the ViT the training cells run, and of its training
step: forward, loss, gradients and the AdamW update under a one-step
linear warm-up and cosine decay — written from the published descriptions
(Dosovitskiy et al. 2020; Loshchilov & Hutter 2019) in straightforward
``jax.numpy``, float32, matmuls at ``highest``. No kernels; rows in blocks
so that a batch of 128 fits beside nothing else. Nothing of the program is
imported.

Departures from the papers, as the template builds it (noted, mirrored):
LayerNorm epsilon 1e-6, GELU in its tanh form, a learned class token and
1-D learned positions, no dropout, the head on the class token after the
final LayerNorm, inputs scaled to [-1, 1].

``quant`` makes it the CONTROL: the same step with every matmul's operands
rounded (straight-through in the backward pass) to ``"int8"`` — the nearest
precision below the bfloat16 the cells state — ``"fp8"`` (e4m3), or
``"bfloat16"`` (for the float32 rehearsal configurations).

Parameters are the benchmark's own tree (``benchmark/weights.py``) by name:
``patch_embed/{kernel,bias}``, ``cls``, ``pos_embed``,
``block_<i>/{LayerNorm_0,LayerNorm_1}/{scale,bias}``,
``block_<i>/attn/{qkv,proj}/{kernel,bias}``,
``block_<i>/{Dense_0,Dense_1}/{kernel,bias}``, ``final_norm``, ``head``.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
LN_EPS = 1e-6
ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8


def _ste(x, q):
    return x + jax.lax.stop_gradient(q - x)  # straight-through


def _fake_int8(x, axis):
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True),
                    1e-12) / 127.0
    return _ste(x, jnp.clip(jnp.round(x / s), -127, 127) * s)


def _fake_fp8(x, axis):
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True),
                    1e-12) / 448.0  # e4m3's largest finite value
    return _ste(x, (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32)
                * s)


def _matmul(x, w, quant):
    if quant == "int8":
        x, w = _fake_int8(x, -1), _fake_int8(w, 0)
    elif quant == "fp8":
        x, w = _fake_fp8(x, -1), _fake_fp8(w, 0)
    elif quant == "bfloat16":
        x = _ste(x, x.astype(jnp.bfloat16).astype(jnp.float32))
        w = _ste(w, w.astype(jnp.bfloat16).astype(jnp.float32))
    elif quant is not None:
        raise ValueError(f"unknown control precision {quant!r}")
    return jnp.matmul(x, w, precision=HIGHEST)


def _dense(x, p, quant):
    return _matmul(x, p["kernel"], quant) + p["bias"]


def _ln(x, p):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * p["scale"] + p["bias"]


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        np.sqrt(2.0 / np.pi) * (x + 0.044715 * x ** 3)))


def prep(images_u8: np.ndarray) -> np.ndarray:
    return images_u8.astype(np.float32) / 127.5 - 1.0


def forward(params, images, cfg: Dict[str, Any], quant=None):
    """Logits (b, classes) of float32 images (b, H, W, C) in [-1, 1]."""
    p = int(cfg["patch_size"])
    heads = int(cfg["num_attention_heads"])
    b, hh, ww, c = images.shape
    x = images.reshape(b, hh // p, p, ww // p, p, c).transpose(
        0, 1, 3, 2, 4, 5).reshape(b, (hh // p) * (ww // p), p * p * c)
    x = _dense(x, params["patch_embed"], quant)
    d = x.shape[-1]
    x = jnp.concatenate([jnp.broadcast_to(params["cls"], (b, 1, d)), x], 1)
    x = x + params["pos_embed"]
    s, dh = x.shape[1], d // heads
    for i in range(int(cfg["num_hidden_layers"])):
        blk = params[f"block_{i}"]
        h = _ln(x, blk["LayerNorm_0"])
        qkv = _dense(h, blk["attn"]["qkv"], quant)
        q, k, v = [t.reshape(b, s, heads, dh)
                   for t in jnp.split(qkv, 3, axis=-1)]
        a = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) \
            / np.sqrt(dh)
        o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(a, -1), v,
                       precision=HIGHEST).reshape(b, s, d)
        x = x + _dense(o, blk["attn"]["proj"], quant)
        h = _ln(x, blk["LayerNorm_1"])
        h = _gelu_tanh(_dense(h, blk["Dense_0"], quant))
        x = x + _dense(h, blk["Dense_1"], quant)
    x = _ln(x, params["final_norm"])
    return _dense(x[:, 0], params["head"], None)


def _sum_loss(params, images, labels, cfg, quant):
    logits = forward(params, images, cfg, quant)
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[:, None], 1))


def loss_and_grads(params, images: np.ndarray, labels: np.ndarray,
                   cfg, quant=None, block: int = 32,
                   _jit: Dict = {}) -> Tuple[float, Any]:
    """Mean cross-entropy over the batch and its gradients, the rows in
    blocks of ``block`` (sums added up, divided once)."""
    key = (id(cfg), quant, images.shape[1:], min(block, len(images)))
    if key not in _jit:
        _jit[key] = jax.jit(jax.value_and_grad(
            lambda p, x, y: _sum_loss(p, x, y, cfg, quant)))
    n = len(images)
    tot, grads = 0.0, None
    for lo in range(0, n, block):
        l, g = _jit[key](params, jnp.asarray(images[lo:lo + block]),
                         jnp.asarray(labels[lo:lo + block]))
        tot = tot + l
        grads = g if grads is None else _tree_add(grads, g)
    scale = 1.0 / n
    return float(tot) * scale, _tree_scale(grads, scale)


# one program for the whole tree, not one for every leaf: every run of
# every check pays the reference's compilations
_tree_add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b))
_tree_scale = jax.jit(lambda a, c: jax.tree_util.tree_map(
    lambda x: x * c, a))
_zeros = jax.jit(lambda a: jax.tree_util.tree_map(jnp.zeros_like, a))
tree_sub = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.subtract, a, b))


def learning_rate(step: int, peak: float, warmup_steps: int,
                  total_steps: int) -> float:
    """Linear from 0 to ``peak`` over ``warmup_steps``, then half a cosine
    down to 0 at ``total_steps`` (step counts from 0)."""
    if step < warmup_steps:
        return peak * step / warmup_steps
    frac = min((step - warmup_steps) / max(total_steps - warmup_steps, 1),
               1.0)
    return peak * 0.5 * (1.0 + np.cos(np.pi * frac))


@jax.jit
def _adamw(params, grads, m, v, c1, c2, lr, weight_decay):
    tm = jax.tree_util.tree_map
    m = tm(lambda a, g: ADAM_B1 * a + (1 - ADAM_B1) * g, m, grads)
    v = tm(lambda a, g: ADAM_B2 * a + (1 - ADAM_B2) * g * g, v, grads)
    params = tm(lambda p, a, b: p - lr * (
        (a / c1) / (jnp.sqrt(b / c2) + ADAM_EPS) + weight_decay * p),
        params, m, v)
    return params, m, v


def adamw_step(params, grads, m, v, t: int, lr: float, weight_decay: float):
    """Update ``t`` (from 1): decoupled weight decay on every leaf."""
    return _adamw(params, grads, m, v, np.float32(1 - ADAM_B1 ** t),
                  np.float32(1 - ADAM_B2 ** t), np.float32(lr),
                  np.float32(weight_decay))


def train_steps(params0, batches: List[Tuple[np.ndarray, np.ndarray]],
                cfg, hyper: Dict[str, float], quant=None) -> Dict[str, Any]:
    """Follow the first ``len(batches)`` steps: every step's loss, the
    first step's gradients, the parameters after the last."""
    tm = jax.tree_util.tree_map
    params = params0
    m, v = _zeros(params0), _zeros(params0)
    losses, first_grads = [], None
    for t, (x, y) in enumerate(batches):
        loss, grads = loss_and_grads(params, prep(x), y.astype(np.int32),
                                     cfg, quant)
        if t == 0:
            first_grads = grads
        lr = learning_rate(t, hyper["learning_rate"],
                           int(hyper["warmup_steps"]),
                           int(hyper["total_steps"]))
        params, m, v = adamw_step(params, grads, m, v, t + 1, lr,
                                  hyper["weight_decay"])
        losses.append(loss)
    return {"losses": losses, "first_grads": first_grads, "params": params}


@jax.jit
def _leaf_norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(l.astype(jnp.float32))))
                      for l in jax.tree_util.tree_leaves(tree)])


def leaf_norms(tree) -> np.ndarray:
    return np.asarray(_leaf_norms(tree), np.float64)


@jax.jit
def _leaf_rms(tree):
    return jnp.stack([jnp.sqrt(jnp.mean(jnp.square(l)))
                      for l in jax.tree_util.tree_leaves(tree)])


def leaf_rms(tree) -> np.ndarray:
    """The root mean square of each leaf's elements."""
    return np.asarray(_leaf_rms(tree), np.float64)


@jax.jit
def _masked(ref_grads, ref_change, change, thr):
    tm = jax.tree_util.tree_map
    mask = tm(lambda g: (jnp.abs(g) >= thr).astype(jnp.float32), ref_grads)
    left_out = sum(jnp.sum(1.0 - m) for m in jax.tree_util.tree_leaves(mask))
    return (_leaf_norms(tm(jnp.multiply, ref_change, mask)),
            _leaf_norms(tm(jnp.multiply, change, mask)), left_out)


def masked_change_norms(ref_grads, ref_change, change, thr: float):
    """Leaf norms of the reference's and the other side's change of the
    parameters over the elements whose reference gradient is at least
    ``thr`` in size, and how many elements that leaves out."""
    d_ref, d_other, left_out = _masked(ref_grads, ref_change, change,
                                       np.float32(thr))
    return (np.asarray(d_ref, np.float64), np.asarray(d_other, np.float64),
            int(left_out))


def worst_leaf_gap(prog: np.ndarray, ref: np.ndarray
                   ) -> Tuple[float, int]:
    """The widest gap between the program's norm and the reference's, leaf
    by leaf, against the reference's norm of that leaf or of the median
    leaf, whichever is larger."""
    denom = np.maximum(ref, np.median(ref))
    gaps = np.abs(prog - ref) / np.maximum(denom, 1e-30)
    i = int(np.argmax(gaps))
    return float(gaps[i]), i
