"""Weights for configurations STORED in a lower precision than float32
(``assumed.param_dtype``), such as a bfloat16 checkpoint served as it is:
random, from ``--seed``, drawn on the device in ONE jitted call and
handed out in that dtype — no float32 tree is ever kept beside them.

The rules are ``weights.py``'s, by each leaf's name, with one more: a
STACKED kernel (``(experts, d, f)``) is ``experts`` kernels of fan-in
``d``, its second-to-last dim, not one of fan-in ``experts * d`` — under
that rule an expert's product would shrink by sqrt(experts) and the
logits' spread with it.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.weights import _leaf_rule, _paths


def leaf_rule(path: str, shape) -> tuple:
    if path.split("/")[-1] == "kernel" and len(shape) == 3:
        return "normal", 1.0 / np.sqrt(shape[-2])
    return _leaf_rule(path, shape)


def make_weights(abstract: Any, seed: int, dtype: Any = jnp.bfloat16
                 ) -> Any:
    """A tree shaped like ``abstract`` (``ShapeDtypeStruct`` leaves),
    every leaf drawn in float32 from ``seed`` and rounded to ``dtype``
    inside the one jit."""
    paths = _paths(abstract)
    leaves, treedef = jax.tree_util.tree_flatten(abstract)
    rules = [leaf_rule(p, l.shape) for p, l in zip(paths, leaves)]
    shapes = [tuple(l.shape) for l in leaves]

    @jax.jit
    def draw(key):
        out = []
        for i, (shape, (kind, std)) in enumerate(zip(shapes, rules)):
            z = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32) * np.float32(std)
            out.append((1.0 + z if kind == "one_plus" else z).astype(dtype))
        return out

    # as weights.py: a seed may exceed 2**31, so two 31-bit halves
    seed = int(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                             (seed >> 31) & 0x7FFFFFFF)
    return jax.tree_util.tree_unflatten(treedef, draw(key))
