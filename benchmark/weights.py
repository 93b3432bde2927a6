"""The benchmark's weights: random, from ``--seed``, drawn on the device in
ONE jitted call, in the type they are served or trained in (float32, as
the templates store them).

The tree's STRUCTURE (names, shapes) comes from the program's module by
``jax.eval_shape`` — nothing is allocated for it. The VALUES are the
benchmark's own, by a rule on each leaf's name, so that no part of the model
is a no-op in the comparison (``lora_b`` is zero after the program's
``init``; a LayerNorm bias of zero hides a missing bias).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np


def _leaf_rule(path: str, shape) -> tuple:
    """(kind, std) of the normal draw for one leaf."""
    name = path.split("/")[-1]
    if name == "kernel":
        fan_in = int(np.prod(shape[:-1])) if len(shape) > 1 else shape[0]
        return "normal", 1.0 / np.sqrt(max(fan_in, 1))
    if name == "embedding":
        return "normal", 1.0
    if name == "scale":
        return "one_plus", 0.1
    if name in ("lora_a", "lora_b", "bias", "cls", "pos_embed"):
        return "normal", 0.02
    raise ValueError(f"no rule for the weight leaf {path!r}")


def _paths(tree: Any) -> list:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return ["/".join(str(getattr(k, "key", k)) for k in kp)
            for kp, _ in flat]


def make_weights(abstract: Any, seed: int) -> Any:
    """A float32 tree shaped like ``abstract`` (a tree of
    ``ShapeDtypeStruct``), every leaf drawn from ``seed``."""
    paths = _paths(abstract)
    leaves, treedef = jax.tree_util.tree_flatten(abstract)
    rules = [_leaf_rule(p, l.shape) for p, l in zip(paths, leaves)]
    shapes = [tuple(l.shape) for l in leaves]

    @jax.jit
    def draw(key):
        out = []
        for i, (shape, (kind, std)) in enumerate(zip(shapes, rules)):
            z = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32) * np.float32(std)
            out.append(1.0 + z if kind == "one_plus" else z)
        return out

    # a seed is any whole number up to a little over 2**31: fold it into
    # a key through two 31-bit halves, never through int32
    seed = int(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                             (seed >> 31) & 0x7FFFFFFF)
    return jax.tree_util.tree_unflatten(treedef, draw(key))
