"""Mixture-of-Experts layer with expert parallelism — the ``ep`` leg.

The reference stack has no MoE; a TPU framework needs one because
expert parallelism is how modern LMs scale parameter count without
scaling per-token FLOPs, and its sharding story is TPU-shaped: experts
live sharded across the mesh and tokens travel to their experts over
ICI. Design (the Shazeer/GShard recipe, XLA-first):

- **Static shapes via capacity.** Each expert processes exactly
  ``capacity = ceil(tokens/E · capacity_factor)`` slots per batch.
  Routing builds DISPATCH/COMBINE tensors (one-hot over (expert,
  slot)), so expert selection is two einsums on the MXU — no gather/
  scatter, no dynamic shapes, nothing XLA can't tile. Overflowing
  tokens are dropped (combine weight 0 → they pass through the
  residual stream untouched), the standard capacity trade.
- **Top-k routing** (k=1 Switch default, k=2 GShard/Mixtral-style with
  pair-renormalized gates) with the load-balancing auxiliary loss from
  the Switch Transformer: ``E · Σ_e fraction_e · prob_e``, minimized at
  uniform routing. The aux loss is returned via a flax
  ``"losses"`` collection so any host module can pick it up with
  ``mutable=["losses"]``.
- **Expert parallelism by annotation:** expert weights are stacked
  ``(E, …)`` arrays; shard dim 0 over the mesh's ``model`` axis
  (``TP_RULES``-style rules match ``"experts"``) and XLA partitions
  the dispatch einsums into the all-to-all + local-expert-compute
  schedule — the same "annotate, let the compiler insert collectives"
  contract every other layer here uses.
- Router math in f32 regardless of compute dtype (softmax over logits
  is precision-sensitive; standard practice).
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from rafiki_tpu.ops.common import use_xla_fallback
from rafiki_tpu.ops.grouped_matmul import group_visits, grouped_matmul

#: standard weight on the load-balancing aux loss in the train
#: objective (the Switch Transformer default) — one definition so the
#: template, dryrun, and benches can't drift
MOE_AUX_COEF = 0.01


def router_dispatch(logits: jnp.ndarray, capacity: int, top_k: int = 1
                    ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Top-k capacity routing from ``(T, E)`` router logits
    (``top_k=1`` = Switch, ``top_k=2`` = GShard/Mixtral-style).

    Returns ``(dispatch, combine, aux)``:
    - ``dispatch``: (T, E, C) one-hot — token t occupies slot c of
      expert e (0 rows for dropped/overflow choices);
    - ``combine``: (T, E, C) — dispatch scaled by the token's gate for
      that expert (router probs renormalized over its top-k choices —
      the gradient path back into the router);
    - ``aux``: scalar load-balancing loss (Switch form, over top-1
      assignments).

    Choices fill capacity in priority order (all first choices, then
    all second choices), each within arrival order — deterministic,
    static shapes, one-hot matmul/cumsum math only (MXU/VPU friendly:
    no sorts over the vocab of experts, no dynamic shapes).
    """
    t, e = logits.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)  # (T, E)
    top_vals, top_idx = jax.lax.top_k(probs, top_k)              # (T, k)
    # gates: Switch (k=1) uses the RAW router prob — renormalizing a
    # single choice would always give 1.0 and cut the router's gradient
    # signal; GShard-style k>1 renormalizes over the chosen set
    if top_k == 1:
        gates = top_vals                                         # (T, 1)
    else:
        gates = top_vals / jnp.maximum(
            jnp.sum(top_vals, axis=-1, keepdims=True), 1e-9)

    dispatch = jnp.zeros((t, e, capacity), jnp.float32)
    combine = jnp.zeros((t, e, capacity), jnp.float32)
    filled = jnp.zeros((e,), jnp.float32)  # slots consumed per expert
    for j in range(top_k):  # static, tiny
        onehot = jax.nn.one_hot(top_idx[:, j], e, dtype=jnp.float32)
        # slot index = earlier same-choice tokens + slots already
        # consumed by higher-priority choices
        position = (jnp.cumsum(onehot, axis=0) - onehot
                    + filled[None, :]) * onehot
        keep = (position < capacity)
        kept = onehot * keep
        slot = jax.nn.one_hot(position.astype(jnp.int32), capacity,
                              dtype=jnp.float32)                 # (T,E,C)
        d_j = kept[..., None] * slot
        dispatch = dispatch + d_j
        combine = combine + d_j * gates[:, j, None, None]
        filled = filled + jnp.sum(kept, axis=0)

    # load balance: fraction of tokens whose TOP choice is e × mean
    # router prob for e, scaled by E — 1 at perfectly uniform routing
    top1 = jax.nn.one_hot(top_idx[:, 0], e, dtype=jnp.float32)
    aux = e * jnp.sum(jnp.mean(top1, axis=0) * jnp.mean(probs, axis=0))
    return dispatch, combine, aux


class MoEFeedForward(nn.Module):
    """MoE FFN: top-k routed SwiGLU experts (``router_top_k``: 1 =
    Switch, 2 = GShard/Mixtral-style).

    Drop-in for a dense FFN over ``(B, S, D)`` activations. Expert
    weights are stacked ``(E, ...)``; shard dim 0 over the mesh's
    ``model`` axis for expert parallelism (``"experts"`` matches the
    Llama ``TP_RULES`` naming contract). Aux loss lands in the
    ``"losses"`` collection under ``"moe_aux"``.
    """

    n_experts: int
    mlp_dim: int
    capacity_factor: float = 1.25
    #: experts per token: 1 = Switch, 2 = GShard/Mixtral-style (gates
    #: renormalized over the chosen pair)
    router_top_k: int = 1
    dtype: Any = None

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        b, s, d = x.shape
        e, h = self.n_experts, self.mlp_dim
        t = b * s
        capacity = max(1, int(-(-t * self.router_top_k
                                * self.capacity_factor // e)))
        xf = x.reshape(t, d)

        # router in f32 (precision-sensitive softmax over logits)
        wr = self.param("router", nn.initializers.normal(0.02), (d, e))
        logits = xf.astype(jnp.float32) @ wr.astype(jnp.float32)
        dispatch, combine, aux = router_dispatch(
            logits, capacity, top_k=self.router_top_k)
        self.sow("losses", "moe_aux", aux)

        # stacked expert SwiGLU weights — dim 0 is the EXPERT axis the
        # mesh shards (expert parallelism): XLA turns the dispatch
        # einsums into all-to-all + per-device expert compute
        init = nn.initializers.lecun_normal()
        w_gate = self.param("experts_gate", init, (e, d, h))
        w_up = self.param("experts_up", init, (e, d, h))
        w_down = self.param("experts_down", init, (e, h, d))

        cdt = x.dtype if self.dtype is None else self.dtype
        # tokens → expert slots (one-hot matmul, not scatter)
        slots = jnp.einsum("td,tec->ecd", xf.astype(jnp.float32),
                           dispatch).astype(cdt)          # (E, C, D)
        gate = jnp.einsum("ecd,edh->ech", slots, w_gate.astype(cdt))
        up = jnp.einsum("ecd,edh->ech", slots, w_up.astype(cdt))
        out = jnp.einsum("ech,ehd->ecd", nn.silu(gate) * up,
                         w_down.astype(cdt))              # (E, C, D)
        # expert slots → tokens, weighted by router prob; dropped
        # tokens get exact zeros (residual stream carries them)
        y = jnp.einsum("ecd,tec->td", out.astype(jnp.float32),
                       combine)
        return y.reshape(b, s, d).astype(x.dtype)


def moe_aux_loss(mutated_collections: dict) -> jnp.ndarray:
    """Sum every sown ``moe_aux`` scalar from a ``mutable=["losses"]``
    apply — the term the train loss adds (scaled by ~1e-2)."""
    total = jnp.asarray(0.0, jnp.float32)
    losses = mutated_collections.get("losses", {})

    def visit(node):
        nonlocal total
        if isinstance(node, dict):
            for k, v in node.items():
                if k == "moe_aux":
                    for leaf in jax.tree_util.tree_leaves(v):
                        total = total + jnp.asarray(leaf, jnp.float32)
                else:
                    visit(v)

    visit(losses)
    return total


# ------------------------------------------------------------ serving
#: what a serving expert layer counts on the device, in the order of the
#: int32 vector it sows into the ``"counters"`` collection
MOE_COUNTERS = ("moe_assignments", "moe_assignments_held",
                "moe_expert_slots", "moe_experts_touched",
                # of those, in single-token (decode step) calls alone:
                # what the grouped products of a step stream and compute
                "moe_step_assignments_held", "moe_step_experts_touched",
                # (expert, row tile) pairs the narrow-tile grouped kernel
                # visited in those calls; 0 off the TPU (``ragged_dot``)
                "moe_step_row_tiles")


def book_moe_counters(stats: Any, counts: Any) -> None:
    """Add one pulled :data:`MOE_COUNTERS` vector to a ``StatsMap``
    (the engine's ``stats``), each count under its own name — spelled
    out, not zipped from the tuple: the metric-catalog lint reads the
    names a program publishes from the literals of its ``inc`` calls."""
    stats.inc("moe_assignments", int(counts[0]))
    stats.inc("moe_assignments_held", int(counts[1]))
    stats.inc("moe_expert_slots", int(counts[2]))
    stats.inc("moe_experts_touched", int(counts[3]))
    stats.inc("moe_step_assignments_held", int(counts[4]))
    stats.inc("moe_step_experts_touched", int(counts[5]))
    stats.inc("moe_step_row_tiles", int(counts[6]))


def sown_counters(tree: Any, name: str, width: int) -> jnp.ndarray:
    """The sum of every vector sown under ``name`` in a ``"counters"``
    collection (zeros where no layer sowed one): how a module whose
    layers sow vectors of several kinds folds them into the one vector
    the engine carries (its ``fold_device_counters``)."""
    total = jnp.zeros((width,), jnp.int32)

    def visit(node: Any) -> None:
        nonlocal total
        for key, sub in node.items():
            if key == name:
                total = total + sum(jax.tree_util.tree_leaves(sub))
            elif isinstance(sub, dict):
                visit(sub)

    visit(tree)
    return total


def route_top_k(logits: jnp.ndarray, top_k: int, renormalize: bool = True,
                scaling: float = 1.0,
                score_bias: Optional[jnp.ndarray] = None
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``(T, E)`` f32 router logits -> ``(gates, experts)``, both
    ``(T, k)``: the ``top_k`` largest of ``softmax(logits)`` with their
    expert ids, the gates divided by their sum (``renormalize``) and
    multiplied by ``scaling``. No capacity: every choice stands.

    With ``score_bias`` (E,) the second rule: scores ``s =
    sigmoid(logits)``, the ``top_k`` largest of ``s + score_bias``
    chosen, and the gates the chosen ``s`` themselves — the bias steers
    the selection and never weighs a result."""
    if score_bias is not None:
        scores = jax.nn.sigmoid(logits.astype(jnp.float32))
        _, experts = jax.lax.top_k(
            scores + score_bias.astype(jnp.float32), top_k)
        gates = jnp.take_along_axis(scores, experts, axis=-1)
    else:
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        gates, experts = jax.lax.top_k(probs, top_k)
    if renormalize:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return gates * scaling, experts


def narrow_row_tile(assignments: int, n: int) -> int:
    """The row tile of the grouped products for a call of ``assignments``
    (row, choice) pairs over ``n`` held experts, from those two static
    numbers alone: the rows an expert would get if every assignment were
    held, rounded up to a power of two, within 64 (below it a tile costs
    the same — the weights' own passage through the MXU — and more
    groups straddle a boundary) and 256 (the best at every larger shape
    measured; 512 lost everywhere). The chip was asked at 8 rows an
    expert (a decode step), 32 (a prefill call), 128, 256, 512, 1,024
    and 2,048: the kernel took 0.60 / 0.43 / 0.46 / 0.50 / 0.54 / 0.60 /
    0.69 of ``jax.lax.ragged_dot``'s time for one product, so no shape
    the engine can make is handed back to XLA's kernel (PERF.md §6,
    PR 34)."""
    rows = -(-assignments // n)
    return min(256, max(64, 1 << (rows - 1).bit_length()))


def grouped_experts(x: jnp.ndarray, gates: jnp.ndarray,
                    experts: jnp.ndarray, w_gate: Optional[jnp.ndarray],
                    w_up: jnp.ndarray, w_down: jnp.ndarray,
                    first: int = 0, interpret: Optional[bool] = None
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The routed part of an expert layer over the experts HELD here,
    without a dropped token: SwiGLU experts of three kernels, or with
    ``w_gate=None`` experts of two, ``relu(x w_up)^2 w_down``.

    ``x`` (T, d) rows; ``gates`` / ``experts`` (T, k) from
    :func:`route_top_k` over ALL the router's experts; ``w_*`` the
    stacked kernels of the ``n`` experts held, ids ``first .. first + n
    - 1``. The (row, choice) assignments are sorted by expert, those of
    absent experts last and in no group, and the three products are
    GROUPED matmuls: each group's rows against its own expert's kernel
    and nothing for rows in no group — no capacity, no one-hot dispatch,
    no multiplication by zero afterwards. What runs them:

    - on the TPU ``ops/grouped_matmul.py``, the repo's Pallas kernel
      (``moe_grouped_matmul`` in a profile), on a row tile sized to the
      groups from the call's shapes (:func:`narrow_row_tile`: 64 for a
      decode step and a prefill call of 8 x 32 rows, up to 256) and
      weight tiles of megabytes read where the stacked kernels lie; an
      expert with no row is not read at all. Gate and up are ONE call
      (``silu(x w_gate) * (x w_up)`` in f32, rounded once; for experts
      of two kernels ``relu(x w_up)^2`` likewise), down another;
    - off the TPU (``interpret=None``) ``jax.lax.ragged_dot`` through
      XLA, which is also the kernel's oracle; ``interpret=True`` runs
      the Pallas kernel in the interpreter, for the tests.

    Returns ``(y, counts)``: ``y`` (T, d) f32, the sum over each row's
    HELD choices of ``gate * expert(x)`` (zero for a row with none), and
    int32 ``[assignments, of them on held experts, experts held, of them
    with at least one row, (expert, row tile) pairs the narrow-tile
    kernel visited, 0 on the ``ragged_dot`` route]`` — the first four of
    :data:`MOE_COUNTERS` and, for a single-token call, the last.
    """
    t, k = experts.shape
    n = w_up.shape[0]
    local = experts.reshape(t * k) - first
    held = (local >= 0) & (local < n)
    group = jnp.where(held, local, n)  # absent experts sort last
    order = jnp.argsort(group, stable=True)
    sizes = jnp.sum(jax.nn.one_hot(group, n, dtype=jnp.int32), axis=0)
    xs = jnp.take(x, order // k, axis=0)  # (T k, d), grouped by expert
    w_in = tuple(w.astype(x.dtype) for w in (w_gate, w_up)
                 if w is not None)
    w_down = w_down.astype(x.dtype)
    if use_xla_fallback(interpret):
        up = jax.lax.ragged_dot(xs, w_in[-1], sizes)
        if w_gate is None:
            hidden = jnp.square(nn.relu(up.astype(jnp.float32))
                                ).astype(x.dtype)
        else:
            hidden = nn.silu(jax.lax.ragged_dot(xs, w_in[0], sizes)) * up
        out = jax.lax.ragged_dot(hidden, w_down, sizes)
        row_tiles = jnp.int32(0)
    else:
        row_tile = narrow_row_tile(t * k, n)
        visits = group_visits(sizes, t * k, row_tile)
        hidden = grouped_matmul(xs, w_in, visits, row_tile,
                                interpret=interpret,
                                relu2=w_gate is None)
        out = grouped_matmul(hidden, (w_down,), visits, row_tile,
                             interpret=interpret)
        row_tiles = visits.count[0]
    # back to (row, choice) order by a gather (the inverse permutation),
    # each choice times its gate. Rows past the last group belong to no
    # expert: whatever the product left there is not read
    back = out[jnp.argsort(order)].astype(jnp.float32).reshape(t, k, -1)
    weight = jnp.where(held.reshape(t, k), gates, 0.0)[..., None]
    y = jnp.sum(jnp.where(weight > 0, back * weight, 0.0), axis=1)
    counts = jnp.stack([
        jnp.int32(t * k), jnp.sum(held, dtype=jnp.int32), jnp.int32(n),
        jnp.sum(sizes > 0, dtype=jnp.int32), row_tiles])
    return y, counts


class ExpertShare(nn.Module):
    """One chip's share of a routed expert layer, for serving: SwiGLU
    experts of three kernels (``gated``), or experts of two with
    ``relu(.)^2`` between. The router keeps its published width
    (``n_experts``) and its ``top_k`` experts a token; the layer is TOLD
    which experts it holds (``held = (first id, count)``; count 0 =
    all), routes over all of them and adds up its own experts' part of
    the result. What the absent experts would have added is left out —
    there is no exchange here and nothing stands in for one. Dropless
    (see :func:`grouped_experts`): a row's output does not depend on
    who shares its batch. On the TPU the products are two calls of the
    repo's narrow-tile Pallas kernel (``moe_grouped_matmul``: gate and
    up together — or up with its ``relu^2`` — then down); off the TPU
    ``jax.lax.ragged_dot`` through XLA.

    Two rules of routing (:func:`route_top_k`): the ``top_k`` largest of
    a softmax, or with ``sigmoid_scores`` the ``top_k`` largest of
    ``sigmoid + score_bias`` weighed by their sigmoids alone. The
    router may read other rows than the experts compute on (``route_on``
    of the call): a layer whose experts work in a latent narrower than
    the model routes on the model's activations.

    Parameters: ``router/kernel`` (router's input width, n_experts),
    ``score_bias`` (n_experts,) with ``sigmoid_scores``, and
    ``experts_{gate,up,down}/kernel`` stacked over the experts HELD (no
    ``experts_gate`` unless ``gated``). The router's product and scores
    run in f32 whatever the compute dtype. Counts land in the
    ``"counters"`` collection (``moe``), when the caller makes it
    mutable.
    """

    n_experts: int
    top_k: int
    mlp_dim: int
    held: Tuple[int, int] = (0, 0)
    renormalize: bool = True
    scaling: float = 1.0
    gated: bool = True
    sigmoid_scores: bool = False

    @nn.compact
    def __call__(self, x: jnp.ndarray,
                 route_on: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        lead, d = x.shape[:-1], x.shape[-1]
        first, n = self.held if self.held[1] else (0, self.n_experts)
        if first < 0 or first + n > self.n_experts:
            raise ValueError(f"experts held {self.held} lie outside the "
                             f"router's {self.n_experts}")
        init = nn.initializers.lecun_normal()

        def kernel(name, shape):
            return KernelLeaf(shape, init, name=name)()

        xf = x.reshape(-1, d)
        rf = xf if route_on is None else route_on.reshape(
            -1, route_on.shape[-1])
        logits = jnp.matmul(
            rf.astype(jnp.float32),
            kernel("router", (rf.shape[-1], self.n_experts)
                   ).astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST)
        bias = {"score_bias": self.param(
            "score_bias", nn.initializers.zeros, (self.n_experts,))
        } if self.sigmoid_scores else {}
        gates, experts = route_top_k(logits, self.top_k, self.renormalize,
                                     self.scaling, **bias)
        y, counts = grouped_experts(
            xf, gates, experts,
            kernel("experts_gate", (n, d, self.mlp_dim))
            if self.gated else None,
            kernel("experts_up", (n, d, self.mlp_dim)),
            kernel("experts_down", (n, self.mlp_dim, d)), first)
        step = counts[jnp.array([1, 3, 4])] * int(x.ndim == 3
                                                  and x.shape[1] == 1)
        self.sow("counters", "moe", jnp.concatenate([counts[:4], step]),
                 init_fn=lambda: jnp.zeros((len(MOE_COUNTERS),), jnp.int32),
                 reduce_fn=lambda a, b: a + b)
        return y.reshape(lead + (d,)).astype(x.dtype)


class KernelLeaf(nn.Module):
    """A bare ``kernel`` leaf under a name of its own, so that the
    serving form of the weights and the benchmark's draws find stacked
    expert kernels where they find every other: ``<site>/kernel``."""

    shape: Tuple[int, ...]
    init: Any

    @nn.compact
    def __call__(self) -> jnp.ndarray:
        return self.param("kernel", self.init, self.shape)
