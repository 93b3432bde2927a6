"""Weights for ``kind: serve_hybrid_ssm``: random, from ``--seed``, drawn on
the device in ONE jitted call and handed out in the dtype the configuration
stores them in (``assumed.param_dtype``) — no float32 tree beside them.

By each leaf's name. Kernels, embedding and norm scales as
``weights_latent_moe.py`` has them (a stacked kernel's fan-in is its
second-to-last dim). The state-space layers' own leaves follow the Mamba-2
conventions, so that the recurrence CARRIES history (a decay drawn like a
kernel would forget within a token or never):

- ``A_log`` = log U(1, 16): the decay ``exp(dt A)`` with ``A = -exp(A_log)``;
- ``dt_bias`` = the inverse softplus of ``dt`` ~ logU(``time_step_min``,
  ``time_step_max``) floored at ``time_step_floor``;
- ``D`` = 1; ``conv1d`` kernel and bias U(-1/2, 1/2) (a depthwise
  convolution of width 4: fan-in 4);
- ``score_bias`` (the router's correction bias) U(-0.01, 0.01): non-zero,
  so that what is selected and what a gate weighs differ, and SMALL beside
  the scores' own spread (sigmoid of N(0, 1) logits: ~0.2), as a bias that
  exists to balance the experts' load is. ISSUE 35's U(-0.1, 0.1) moved an
  expert's chance of being chosen from 0.2% to 14% by its draw alone: a
  third of the held experts idle at every step, and a window's bytes — so
  its tokens a second, by 1.8% — the luck of which experts the seed favoured
  (PERF.md section 6, PR 35).

Those four small vectors a layer stay float32 whatever ``param_dtype`` (they
feed an exponential or a comparison of near-tied scores).
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.weights import _paths
from benchmark.weights_latent_moe import leaf_rule

#: leaves kept in float32
FLOAT32_LEAVES = ("A_log", "dt_bias", "D", "score_bias")


def _draw(name: str, path: str, key, shape, cfg: Dict[str, Any]):
    """One float32 leaf."""
    uniform = jax.random.uniform
    if name == "A_log":
        return jnp.log(uniform(key, shape, jnp.float32, 1.0, 16.0))
    if name == "dt_bias":
        lo, hi = float(cfg["time_step_min"]), float(cfg["time_step_max"])
        dt = jnp.exp(uniform(key, shape, jnp.float32, np.log(lo),
                             np.log(hi)))
        dt = jnp.maximum(dt, float(cfg["time_step_floor"]))
        return dt + jnp.log(-jnp.expm1(-dt))  # softplus^-1
    if name == "D":
        return jnp.ones(shape, jnp.float32)
    if name == "score_bias":
        return uniform(key, shape, jnp.float32, -0.01, 0.01)
    if path.split("/")[-2:-1] == ["conv1d"]:
        return uniform(key, shape, jnp.float32, -0.5, 0.5)
    # the gated norm's scale is a norm's scale under another name
    kind, std = ("one_plus", 0.1) if name == "norm_scale" \
        else leaf_rule(path, shape)
    z = jax.random.normal(key, shape, jnp.float32) * np.float32(std)
    return 1.0 + z if kind == "one_plus" else z


def make_weights(abstract: Any, seed: int, cfg: Dict[str, Any],
                 dtype: Any = jnp.bfloat16) -> Any:
    """A tree shaped like ``abstract`` (``ShapeDtypeStruct`` leaves),
    every leaf drawn in float32 from ``seed`` and rounded to ``dtype``
    (:data:`FLOAT32_LEAVES` excepted) inside the one jit."""
    paths = _paths(abstract)
    leaves, treedef = jax.tree_util.tree_flatten(abstract)
    shapes = [tuple(leaf.shape) for leaf in leaves]

    @jax.jit
    def draw(key):
        out = []
        for i, (path, shape) in enumerate(zip(paths, shapes)):
            name = path.split("/")[-1]
            leaf = _draw(name, path, jax.random.fold_in(key, i), shape, cfg)
            out.append(leaf if name in FLOAT32_LEAVES
                       else leaf.astype(dtype))
        return out

    # as weights.py: a seed may exceed 2**31, so two 31-bit halves
    seed = int(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                             (seed >> 31) & 0x7FFFFFFF)
    return jax.tree_util.tree_unflatten(treedef, draw(key))
