"""Shared training epoch driver for the zoo templates.

Every template's epoch loop wants the same TPU-side plumbing:
double-buffered host→HBM prefetch (transfer of batch k+1 overlaps the
compiled step on batch k), device-scalar loss collection with a bounded
run-ahead sync (no per-step ``float()`` serialization, no unbounded
dispatch queue holding every in-flight batch in HBM), and a mean loss
materialized once at epoch end. One implementation here instead of a
per-template copy.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import (Any, Callable, Dict, Iterator, Optional, Sequence,
                    Tuple)

import numpy as np

from ..data.loader import prefetch_to_device
from ..obs.trace import SPANS

#: steps between jax.block_until_ready syncs: full overlap, bounded
#: number of in-flight batches resident in HBM
SYNC_EVERY = 8


def train_epoch(step: Callable[[Any, dict], Tuple[Any, Any]],
                state: Any, host_batches: Iterator[dict],
                sharding: Optional[Any] = None,
                sync_every: int = SYNC_EVERY) -> Tuple[Any, float]:
    """Thread ``state`` through ``step(state, batch) -> (state, loss)``
    over one epoch of batches.

    With ``sharding`` the host batches are prefetched to device under it
    (each dict leaf placed with the same NamedSharding). ``step`` is the
    template's adapter around its jitted (usually donated) train_step.
    Returns (final state, mean loss as float).
    """
    import jax

    # phase spans (obs.SPANS, docs/observability.md "Phase spans"): the
    # loop pulls each batch with an explicit next() so the feed's time —
    # the iterator's slicing plus prefetch_to_device's device_put — is a
    # span of its own and not hidden in a ``for`` header
    with SPANS.span("train.epoch") as epoch:
        batches = iter(prefetch_to_device(host_batches, sharding=sharding)
                       if sharding is not None else host_batches)
        losses = []
        while True:
            with SPANS.span("train.feed") as feed:
                batch = next(batches, None)
                if batch is None:
                    # the pull that finds the iterator exhausted is the
                    # epoch's end, not a feed: N batches are N feeds. The
                    # reduction waits here for the last steps' losses
                    feed.name = "train.epoch_end"
                    mean = (float(np.mean([float(l) for l in losses]))
                            if losses else float("nan"))
                    break
            with SPANS.span("train.dispatch"):
                state, loss = step(state, batch)
            losses.append(loss)
            if sync_every and len(losses) % sync_every == 0:
                with SPANS.span("train.sync"):
                    jax.block_until_ready(loss)
        epoch.set(steps=len(losses))
    return state, mean


@dataclass
class GangSpec:
    """A template's *functional* training recipe — the contract the
    gang-compiled tuning engine (``rafiki_tpu/tuning``) drives.

    The ordinary :meth:`BaseModel.train` is imperative: it owns its epoch
    loop and bakes every knob into Python. A gang spec factors the same
    computation into pure functions over an explicit per-lane ``state``
    pytree, with the template's *traceable* knobs arriving as a dict of
    traced scalars (``hp``). The engine vmaps these functions over K
    lanes (lane = trial) so K configurations train inside ONE compiled
    step; all non-traceable knobs were already burned in when the
    template built the spec (one spec per static bucket —
    :func:`rafiki_tpu.model.knob.static_signature`).

    Templates opt in via ``make_gang_spec(knobs, train_path, val_path)``
    (a classmethod returning one of these) plus ``gang_epochs(knobs,
    budget_scale)``; the engine falls back to per-trial sequential
    execution for templates that don't.

    Semantics contract (checked by tier-1 equivalence tests): driving a
    1-lane gang through ``init_lane``/``train_step``/``eval_lane`` must
    reproduce the template's sequential ``train()``/``evaluate()``
    bit-for-bit on the same dataset and knob assignment.
    """

    #: traceable knob names, in the axis order the engine packs per-lane
    #: hp arrays (use ``traceable_knobs(get_knob_config())``)
    hp_names: Sequence[str]
    #: ``(rng, hp) -> state`` — build ONE lane's state (params + opt);
    #: must not depend on hp for pytree STRUCTURE (values only)
    init_lane: Callable[[Any, Dict[str, Any]], Any]
    #: ``(state, hp, batch) -> (state, loss)`` — pure; vmapped over
    #: state, hp AND batch (in_axes=(0, 0, 0)) and jitted with the
    #: state donated. The batch axis is per-lane because each lane
    #: follows its OWN epoch schedule (a refilled lane restarts at
    #: epoch 0), so lane i's batch at any step is exactly what its
    #: sequential twin would see — do not assume lanes share data
    train_step: Callable[[Any, Dict[str, Any], Dict[str, Any]],
                         Tuple[Any, Any]]
    #: ``(epoch) -> iterator of host batch dicts`` (static shapes; the
    #: same batches the template's sequential loop sees at that epoch —
    #: the engine stacks one batch per lane from per-lane iterators)
    epoch_batches: Callable[[int], Iterator[Dict[str, np.ndarray]]]
    #: scoring contract per ``score_kind``: "accuracy" → ``(state, hp,
    #: xb) -> predicted class ids [B]`` (engine computes masked accuracy
    #: over ``eval_batches``); "lm" → ``(state, hp, batch) ->
    #: (loss_sum, valid_count)`` scalars (engine accumulates and scores
    #: ``exp(-sum/count)``, the LM template's inverse perplexity)
    eval_lane: Callable[[Any, Dict[str, Any], Any], Any]
    #: ``() -> iterator of host eval batches`` ("accuracy": ``{"x", "y",
    #: "mask"}``; "lm": whatever ``eval_lane`` consumes — the SAME
    #: padded batch stream the template's ``evaluate()`` walks)
    eval_batches: Callable[[], Iterator[Dict[str, np.ndarray]]]
    #: ``(lane_state, hp) -> blob`` — a ``dump_parameters()``-shaped
    #: blob for the ParamStore / TuneResult (host numpy). ``hp`` holds
    #: the lane's traceable knob values as floats so value-folding
    #: exports (e.g. LoRA rank-scale folded into ``lora_b``) see them
    export_blob: Callable[[Any, Dict[str, float]], Dict[str, Any]]
    #: ``(fresh_state, parent_blob) -> state`` — warm-start a lane from a
    #: completed trial's blob (params from the blob, optimizer fresh —
    #: exactly what the sequential warm-start path does)
    warm_lane: Callable[[Any, Dict[str, Any]], Any]
    #: name of the template's SHARE_PARAMS policy knob, if any: the
    #: engine only applies a proposal's warm start when this knob is
    #: truthy in its assignment (mirrors the sequential gate)
    share_params_knob: Optional[str] = None
    #: how the engine scores lanes over ``eval_batches``: "accuracy"
    #: (classification zoo) or "lm" (inverse perplexity — see
    #: ``eval_lane``)
    score_kind: str = "accuracy"
    #: tokens one real training sample contributes per step (LM
    #: templates: max_len). Feeds the engine's per-lane tokens/s
    #: gauges; 0 disables token accounting
    tokens_per_sample: int = 0
    #: parameter count of ONE lane's full forward (broadcast base +
    #: adapters) — the engine's per-lane est-MFU gauge uses the
    #: 6·N·tokens/s approximation; 0 disables the gauge
    lane_param_count: int = 0
    #: XLA compiler options for the gang's jitted step (e.g. the
    #: ``overlap_collectives`` schedule knob —
    #: :func:`rafiki_tpu.parallel.sharding.overlap_compiler_options`);
    #: None compiles with defaults. Static by construction: the knob is
    #: non-traceable, so each option set is its own compile bucket
    compiler_options: Optional[Dict[str, Any]] = None
    #: optional ``(lane_state, hp, batch) -> eval terms`` running ONE
    #: lane on the template's *sequential* ``evaluate()`` graph (e.g.
    #: value-folding knobs applied eagerly, then the same jitted
    #: forward ``evaluate()`` compiles). When set, the engine scores
    #: lanes through this instead of vmapping ``eval_lane`` — scoring
    #: is where the bit-exactness contract is settled, and a vmapped
    #: (or differently fused) eval graph can drift in the low bits on
    #: large forwards even though the math is identical
    eval_seq: Optional[Callable[[Any, Dict[str, Any], Any], Any]] = None
