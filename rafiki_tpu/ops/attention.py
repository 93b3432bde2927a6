"""Fused multi-head attention (flash-style) as Pallas TPU kernels.

Replaces the cuDNN fused attention the reference's templates get for free
inside TF/PyTorch (SURVEY.md §2.1: the rebuild's native obligation is
XLA/Pallas kernels; ViT attention is the named target). Design:

- Online-softmax streaming over key blocks (never materializes the S×S
  score matrix in HBM): for each query block the kernel keeps running
  (max, sum, weighted-V accumulator) in f32 and rescales as new key blocks
  arrive — the flash-attention recurrence.
- Backward pass: fused Pallas kernels too. The forward saves each row's
  logsumexp (LSE); backward runs two kernels — dQ (grid over query blocks,
  streaming keys) and dK/dV (grid over key blocks, streaming queries) —
  with ``delta = rowsum(dO · O)`` precomputed in XLA. HBM stays O(S·d)
  per (batch, head); the S×S matrix is never materialized.
- Per-row scalars (LSE, delta) are stored replicated across a 128-lane
  trailing dim so every kernel touches only native (sublane, lane) tiles —
  no 1-D refs, no in-kernel transposes (Mosaic-restricted patterns).
- Variable-length batches: ``kv_lens`` rides in as a scalar-prefetch
  operand (SMEM), read per grid row to bound the key loop and mask pads.
- Block sizes default to 128 to match MXU tiling; inputs are padded to
  block multiples by the wrapper. f32 accumulation regardless of input
  dtype (bf16 in, bf16 out, f32 math). Off-TPU the default dispatch uses
  the equivalent pure-XLA path (fast on CPU); the kernel-equivalence
  tests force the kernels through the Pallas interpreter with
  ``interpret=True``.
"""

from __future__ import annotations

import functools
import math
import os
from typing import Optional

import jax
import jax.numpy as jnp

from rafiki_tpu.ops.common import use_xla_fallback

NEG_INF = -1e30
# LSE written for rows whose every key is masked: exp(s - 1e30) == 0 for
# any finite score, so such rows contribute exactly zero gradient.
LSE_MASKED = 1e30
# Per-row scalars are replicated across this many lanes (one f32 vreg lane
# dim) so kernels only ever see (sublane, lane)-tiled 2-D blocks.
LANES = 128
# Auto-dispatch (interpret=None) routes sequences at or below this length
# to the pure-XLA path EVEN ON TPU: measured on a v5e chip (ViT-B/16
# train step, seq 197 → padded 256, bs 64), XLA's fused attention beats
# the Pallas kernels 811 vs 578 samples/s — at short seq the O(S²) score
# matrix the flash recurrence exists to avoid fits easily in
# VMEM-friendly fusions, and the kernel's grid/loop overhead dominates.
# The default stays at the measured crossover region (256); above it the
# kernels run, since the XLA path materializes (B, H, S, S) f32
# scores and an unmeasured win is not worth an OOM regression. Override
# with RAFIKI_XLA_SHORT_SEQ (0 disables the short-seq route entirely);
# explicit interpret=False always forces Mosaic lowering.
XLA_SHORT_SEQ = int(os.environ.get("RAFIKI_XLA_SHORT_SEQ", "256"))
# Fleet-applicable default for flash_attention's block_h (multi-head-
# per-program forward): callers that don't pass block_h explicitly pick
# this up, so a sweep won on the chip (none has run: ROADMAP D7) can
# be applied to every template without code edits — e.g.
# RAFIKI_ATTN_BLOCK_H=4 flips ViT/BERT onto the mh kernels (and, per
# the dispatch rule below, off the short-seq XLA route). Default 1 =
# per-head programs, today's measured-best configuration.
ATTN_BLOCK_H = max(1, int(os.environ.get("RAFIKI_ATTN_BLOCK_H", "1")))

# (block_h, heads) combos already warned about by the env-default
# divisibility fallback below — warn once per shape, not per call
_ENV_BLOCK_H_WARNED = set()


def _env_block_h(heads: int) -> int:
    """Resolve the env-derived block_h default against this call's
    LOCAL head count. The fleet default is tuned on whole models, but
    ulysses/ring inner calls see heads/tp/sp — a value that doesn't
    divide the local count must degrade to per-head programs (with one
    warning per shape), not hard-fail a template that never asked for
    head tiling. An EXPLICIT block_h keeps the hard ValueError: that is
    a deliberate kernel-tuning choice whose silent fallback would
    invalidate a sweep."""
    block_h = ATTN_BLOCK_H
    if block_h > 1 and heads % block_h:
        key = (block_h, heads)
        if key not in _ENV_BLOCK_H_WARNED:
            _ENV_BLOCK_H_WARNED.add(key)
            import logging

            logging.getLogger(__name__).warning(
                "RAFIKI_ATTN_BLOCK_H=%d does not divide the local head "
                "count (%d); falling back to block_h=1 for this shape",
                block_h, heads)
        return 1
    return block_h


def _attn_fwd_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, *lse_refs,
                     sm_scale: float, causal: bool, block_q: int,
                     block_k: int, n_kv_blocks: int):
    from jax.experimental import pallas as pl

    bh = pl.program_id(0)
    qb = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * sm_scale  # (block_q, d)
    kv_len = len_ref[bh]  # this example's valid key count (pads masked out)

    m0 = jnp.full((block_q, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc0 = jnp.zeros((block_q, q.shape[-1]), jnp.float32)

    q_pos = qb * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)

    def body(kb, carry):
        m, l, acc = carry
        k_blk = k_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # (block_q, block_k)

        k_pos = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = k_pos < kv_len
        if causal:
            mask = jnp.logical_and(mask, k_pos <= q_pos)
        s = jnp.where(mask, s, NEG_INF)

        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + jax.lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    # skip key blocks that are fully masked: past this example's kv_len,
    # and (causal) strictly after this query block
    n_blocks = jnp.minimum(
        jnp.asarray(n_kv_blocks, jnp.int32),
        (kv_len + block_k - 1) // block_k)
    if causal:
        n_blocks = jnp.minimum(
            n_blocks, (qb * block_q + block_q + block_k - 1) // block_k)
    m, l, acc = jax.lax.fori_loop(0, n_blocks, body, (m0, l0, acc0))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    if lse_refs:  # training path only; serving skips the residual write
        lse = jnp.where(l > 0, m + jnp.log(jnp.maximum(l, 1e-30)),
                        LSE_MASKED)
        lse_refs[0][0] = jax.lax.broadcast_in_dim(
            lse, (block_q, LANES), (0, 1))


def _attn_fwd_mh_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, *lse_refs,
                        sm_scale: float, causal: bool, block_h: int,
                        block_q: int, block_k: int, n_kv_blocks: int):
    """Multi-head-per-program forward: each grid step owns ``block_h``
    consecutive (batch, head) rows — batched MXU matmuls amortize the
    per-program grid/DMA overhead that dominates at SHORT sequences,
    where the single-head grid runs thousands of tiny programs (the
    VERDICT r4 seq<=256 regime). All rows in a tile belong to one
    example (callers enforce ``h % block_h == 0``), so they share one
    ``kv_len``. Math is identical to :func:`_attn_fwd_kernel` with a
    leading head-tile dim."""
    from jax.experimental import pallas as pl

    bh = pl.program_id(0)
    qb = pl.program_id(1)
    q = q_ref[...].astype(jnp.float32) * sm_scale  # (block_h, bq, d)
    kv_len = len_ref[bh * block_h]  # whole tile = one example's heads

    m0 = jnp.full((block_h, block_q, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_h, block_q, 1), jnp.float32)
    acc0 = jnp.zeros((block_h, block_q, q.shape[-1]), jnp.float32)

    q_pos = qb * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)

    def body(kb, carry):
        m, l, acc = carry
        k_blk = k_ref[:, pl.ds(kb * block_k, block_k), :].astype(
            jnp.float32)
        v_blk = v_ref[:, pl.ds(kb * block_k, block_k), :].astype(
            jnp.float32)
        s = jax.lax.dot_general(
            q, k_blk, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)  # (bh, bq, bk)

        k_pos = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = k_pos < kv_len
        if causal:
            mask = jnp.logical_and(mask, k_pos <= q_pos)
        s = jnp.where(mask[None, :, :], s, NEG_INF)

        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + jax.lax.dot_general(
            p, v_blk, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    n_blocks = jnp.minimum(
        jnp.asarray(n_kv_blocks, jnp.int32),
        (kv_len + block_k - 1) // block_k)
    if causal:
        n_blocks = jnp.minimum(
            n_blocks, (qb * block_q + block_q + block_k - 1) // block_k)
    m, l, acc = jax.lax.fori_loop(0, n_blocks, body, (m0, l0, acc0))
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    if lse_refs:  # training path only; serving skips the residual write
        lse = jnp.where(l > 0, m + jnp.log(jnp.maximum(l, 1e-30)),
                        LSE_MASKED)
        lse_refs[0][...] = jnp.broadcast_to(
            lse, (block_h, block_q, LANES))


def _attn_bwd_dq_kernel(len_ref, q_ref, g_ref, lse_ref, delta_ref, k_ref,
                        v_ref, dq_ref, *, sm_scale: float, causal: bool,
                        block_q: int, block_k: int, n_kv_blocks: int):
    """dQ for one query block: stream key blocks, accumulate ds·K.

    Requires ``block_k == LANES`` so the lane-replicated LSE/delta tiles
    line up elementwise with the (block_q, block_k) score tile.
    """
    from jax.experimental import pallas as pl

    bh = pl.program_id(0)
    qb = pl.program_id(1)
    kv_len = len_ref[bh]
    q = q_ref[0].astype(jnp.float32)      # (block_q, d)
    g = g_ref[0].astype(jnp.float32)      # (block_q, d)
    lse = lse_ref[0]                      # (block_q, LANES) f32
    delta = delta_ref[0]                  # (block_q, LANES) f32

    q_pos = qb * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)

    def body(kb, acc):
        k_blk = k_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        k_pos = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = k_pos < kv_len
        if causal:
            mask = jnp.logical_and(mask, k_pos <= q_pos)
        s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse)                                # (bq, bk)
        dp = jax.lax.dot_general(
            g, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)             # (bq, bk)
        ds = p * (dp - delta) * sm_scale
        return acc + jax.lax.dot_general(
            ds, k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # (bq, d)

    n_blocks = jnp.minimum(
        jnp.asarray(n_kv_blocks, jnp.int32),
        (kv_len + block_k - 1) // block_k)
    if causal:
        n_blocks = jnp.minimum(
            n_blocks, (qb * block_q + block_q + block_k - 1) // block_k)
    acc0 = jnp.zeros((block_q, q.shape[-1]), jnp.float32)
    acc = jax.lax.fori_loop(0, n_blocks, body, acc0)
    dq_ref[0] = acc.astype(dq_ref.dtype)


def _attn_bwd_dkv_kernel(len_ref, q_ref, g_ref, lse_ref, delta_ref, k_ref,
                         v_ref, dk_ref, dv_ref, *, sm_scale: float,
                         causal: bool, block_q: int, block_k: int,
                         n_q_blocks: int):
    """dK/dV for one key block: stream query blocks, accumulate pᵀ·dO and
    dsᵀ·Q. Causal skips query blocks strictly above the diagonal."""
    from jax.experimental import pallas as pl

    bh = pl.program_id(0)
    kb = pl.program_id(1)
    kv_len = len_ref[bh]
    k_blk = k_ref[0].astype(jnp.float32)  # (block_k, d)
    v_blk = v_ref[0].astype(jnp.float32)  # (block_k, d)

    k_pos = kb * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)

    def body(qb, carry):
        dk, dv = carry
        q_blk = q_ref[0, pl.ds(qb * block_q, block_q), :].astype(jnp.float32)
        g_blk = g_ref[0, pl.ds(qb * block_q, block_q), :].astype(jnp.float32)
        lse = lse_ref[0, pl.ds(qb * block_q, block_q), :]    # (bq, LANES)
        delta = delta_ref[0, pl.ds(qb * block_q, block_q), :]
        s = jax.lax.dot_general(
            q_blk, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale   # (bq, bk)
        q_pos = qb * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        mask = k_pos < kv_len
        if causal:
            mask = jnp.logical_and(mask, k_pos <= q_pos)
        s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse)
        dv = dv + jax.lax.dot_general(
            p, g_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # (bk, d)
        dp = jax.lax.dot_general(
            g_blk, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)              # (bq, bk)
        ds = p * (dp - delta) * sm_scale
        dk = dk + jax.lax.dot_general(
            ds, q_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # (bk, d)
        return dk, dv

    # causal: the first query row that can see key kb*block_k is that same
    # position, so start at its query block
    start = (kb * block_k) // block_q if causal else 0
    # key block entirely past kv_len → every p underflows to zero; skip
    # the whole query loop instead of multiplying zeros on the MXU
    stop = jnp.where(kb * block_k < kv_len,
                     jnp.asarray(n_q_blocks, jnp.int32),
                     jnp.asarray(start, jnp.int32))
    z = jnp.zeros((block_k, k_blk.shape[-1]), jnp.float32)
    dk, dv = jax.lax.fori_loop(start, stop, body, (z, z))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _pad_to(x: jnp.ndarray, axis: int, mult: int) -> jnp.ndarray:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _resolve_interpret(interpret: Optional[bool]) -> bool:
    return jax.default_backend() != "tpu" if interpret is None else interpret


def _prep_lens(kv_lens, b: int, h: int, s_kv: int) -> jnp.ndarray:
    """(b,) valid-key counts → (b*h,) int32 scalar-prefetch operand."""
    if kv_lens is None:
        lens = jnp.full((b,), s_kv, jnp.int32)
    else:
        lens = jnp.minimum(jnp.asarray(kv_lens, jnp.int32), s_kv)
    return jnp.repeat(lens, h)


def _flash_attention_fwd_impl(q, k, v, kv_lens, sm_scale: float,
                              causal: bool, block_q: int, block_k: int,
                              interpret: Optional[bool], *,
                              with_lse: bool = False, block_h: int = 1):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, s_q, d = q.shape
    s_kv = k.shape[2]
    interpret = _resolve_interpret(interpret)
    if block_h < 1:
        raise ValueError(f"block_h={block_h} must be >= 1")
    if block_h > 1 and h % block_h:
        raise ValueError(
            f"block_h={block_h} must divide heads ({h}): a head tile "
            "spanning two examples would mix their kv_lens")

    qp = _pad_to(q, 2, block_q)
    kp = _pad_to(k, 2, block_k)
    vp = _pad_to(v, 2, block_k)
    sq_p, skv_p = qp.shape[2], kp.shape[2]
    n_q_blocks = sq_p // block_q
    n_kv_blocks = skv_p // block_k

    qp = qp.reshape(b * h, sq_p, d)
    kp = kp.reshape(b * h, skv_p, d)
    vp = vp.reshape(b * h, skv_p, d)
    lens = _prep_lens(kv_lens, b, h, s_kv)

    if block_h > 1:
        kernel = functools.partial(
            _attn_fwd_mh_kernel, sm_scale=sm_scale, causal=causal,
            block_h=block_h, block_q=block_q, block_k=block_k,
            n_kv_blocks=n_kv_blocks)
    else:
        kernel = functools.partial(
            _attn_fwd_kernel, sm_scale=sm_scale, causal=causal,
            block_q=block_q, block_k=block_k, n_kv_blocks=n_kv_blocks)
    out_specs = [
        pl.BlockSpec((block_h, block_q, d),
                     lambda bh, qb, lens: (bh, qb, 0)),
    ]
    out_shape = [jax.ShapeDtypeStruct((b * h, sq_p, d), q.dtype)]
    if with_lse:  # residual for the fused backward (training path only)
        out_specs.append(pl.BlockSpec((block_h, block_q, LANES),
                                      lambda bh, qb, lens: (bh, qb, 0)))
        out_shape.append(
            jax.ShapeDtypeStruct((b * h, sq_p, LANES), jnp.float32))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b * h // block_h, n_q_blocks),
        in_specs=[
            pl.BlockSpec((block_h, block_q, d),
                         lambda bh, qb, lens: (bh, qb, 0)),
            pl.BlockSpec((block_h, skv_p, d),
                         lambda bh, qb, lens: (bh, 0, 0)),
            pl.BlockSpec((block_h, skv_p, d),
                         lambda bh, qb, lens: (bh, 0, 0)),
        ],
        out_specs=out_specs,
    )
    res = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(lens, qp, kp, vp)
    out = res[0].reshape(b, h, sq_p, d)[:, :, :s_q, :]
    if with_lse:
        return out, res[1]  # lse stays padded/lane-replicated for the bwd
    return out


def _flash_attention_bwd_impl(q, k, v, kv_lens, o, lse, g, sm_scale: float,
                              causal: bool, block_q: int, block_k: int,
                              interpret: Optional[bool], g_lse=None):
    """Fused dq/dk/dv. ``lse`` is the (b*h, sq_padded, LANES) residual.

    ``g_lse`` (optional, (b, h, s_q) f32) is the cotangent of the LSE
    output when the caller consumed :func:`flash_attention_lse`. It folds
    into the existing kernels for free: with p = exp(s − lse),
    ∂lse/∂s = p, so ds = p·(dp − delta + g_lse) — i.e. the kernels run
    unchanged with delta' = delta − g_lse. (dV has no lse term.)"""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    # The backward always tiles keys at LANES so the lane-replicated
    # LSE/delta tiles line up elementwise with the (block_q, block_k)
    # score tile — the caller's block_k only shapes the forward. block_q
    # must stay the forward's: the saved lse is padded at its granularity.
    block_k = LANES
    b, h, s_q, d = q.shape
    s_kv = k.shape[2]
    interpret = _resolve_interpret(interpret)

    qp = _pad_to(q, 2, block_q).reshape(b * h, -1, d)
    kp = _pad_to(k, 2, block_k).reshape(b * h, -1, d)
    vp = _pad_to(v, 2, block_k).reshape(b * h, -1, d)
    gp = _pad_to(g, 2, block_q).reshape(b * h, -1, d)
    op = _pad_to(o, 2, block_q).reshape(b * h, -1, d)
    sq_p, skv_p = qp.shape[1], kp.shape[1]
    n_q_blocks = sq_p // block_q
    n_kv_blocks = skv_p // block_k
    lens = _prep_lens(kv_lens, b, h, s_kv)

    # delta_i = Σ_d dO_id · O_id, lane-replicated like the LSE
    delta = jnp.sum(gp.astype(jnp.float32) * op.astype(jnp.float32),
                    axis=-1, keepdims=True)
    if g_lse is not None:
        glp = _pad_to(g_lse.astype(jnp.float32).reshape(b * h, s_q, 1),
                      1, block_q)
        delta = delta - glp
    delta = jnp.broadcast_to(delta, (b * h, sq_p, LANES))

    dq_kernel = functools.partial(
        _attn_bwd_dq_kernel, sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_k=block_k, n_kv_blocks=n_kv_blocks)
    dq_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b * h, n_q_blocks),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qb, lens: (bh, qb, 0)),
            pl.BlockSpec((1, block_q, d), lambda bh, qb, lens: (bh, qb, 0)),
            pl.BlockSpec((1, block_q, LANES),
                         lambda bh, qb, lens: (bh, qb, 0)),
            pl.BlockSpec((1, block_q, LANES),
                         lambda bh, qb, lens: (bh, qb, 0)),
            pl.BlockSpec((1, skv_p, d), lambda bh, qb, lens: (bh, 0, 0)),
            pl.BlockSpec((1, skv_p, d), lambda bh, qb, lens: (bh, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d),
                               lambda bh, qb, lens: (bh, qb, 0)),
    )
    dq = pl.pallas_call(
        dq_kernel, grid_spec=dq_spec,
        out_shape=jax.ShapeDtypeStruct((b * h, sq_p, d), q.dtype),
        interpret=interpret,
    )(lens, qp, gp, lse, delta, kp, vp)

    dkv_kernel = functools.partial(
        _attn_bwd_dkv_kernel, sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_k=block_k, n_q_blocks=n_q_blocks)
    dkv_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b * h, n_kv_blocks),
        in_specs=[
            pl.BlockSpec((1, sq_p, d), lambda bh, kb, lens: (bh, 0, 0)),
            pl.BlockSpec((1, sq_p, d), lambda bh, kb, lens: (bh, 0, 0)),
            pl.BlockSpec((1, sq_p, LANES), lambda bh, kb, lens: (bh, 0, 0)),
            pl.BlockSpec((1, sq_p, LANES), lambda bh, kb, lens: (bh, 0, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, kb, lens: (bh, kb, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, kb, lens: (bh, kb, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda bh, kb, lens: (bh, kb, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, kb, lens: (bh, kb, 0)),
        ],
    )
    dk, dv = pl.pallas_call(
        dkv_kernel, grid_spec=dkv_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b * h, skv_p, d), k.dtype),
            jax.ShapeDtypeStruct((b * h, skv_p, d), v.dtype),
        ],
        interpret=interpret,
    )(lens, qp, gp, lse, delta, kp, vp)

    dq = dq.reshape(b, h, sq_p, d)[:, :, :s_q, :]
    dk = dk.reshape(b, h, skv_p, d)[:, :, :s_kv, :]
    dv = dv.reshape(b, h, skv_p, d)[:, :, :s_kv, :]
    return dq, dk, dv


def _attention_reference(q, k, v, sm_scale: float, causal: bool,
                         kv_lens=None):
    """Pure-XLA attention (correctness oracle AND the off-TPU fast path).

    Matches the kernels bit-for-behavior on fully masked rows too: a row
    whose every key is masked (kv_len == 0) outputs exact zeros with zero
    gradient, like the kernels' ``LSE_MASKED`` path — not softmax's
    uniform-weights answer.
    """
    if kv_lens is None:  # one oracle: the lse twin owns the shared math
        out, _ = _attention_reference_lse(q, k, v, sm_scale, causal)
        return out
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale
    s_q, s_k = s.shape[-2], s.shape[-1]
    if causal:
        mask = (jax.lax.broadcasted_iota(jnp.int32, (s_q, s_k), 0)
                >= jax.lax.broadcasted_iota(jnp.int32, (s_q, s_k), 1))
        s = jnp.where(mask, s, NEG_INF)
    k_pos = jnp.arange(s_k)[None, None, None, :]
    s = jnp.where(k_pos < jnp.asarray(kv_lens)[:, None, None, None],
                  s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    nonempty = (jnp.asarray(kv_lens) > 0)[:, None, None, None]
    p = jnp.where(nonempty, p, 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


def flash_attention(q, k, v, sm_scale: Optional[float] = None,
                    causal: bool = False, block_q: int = 128,
                    block_k: int = 128, interpret: Optional[bool] = None,
                    kv_lens=None,
                    block_h: Optional[int] = None) -> jnp.ndarray:
    """Fused attention over (batch, heads, seq, head_dim) tensors.

    ``kv_lens`` (optional int32 [batch]) masks each example's keys past its
    valid length — the padding mask for BERT-style batches and bucketed
    continuous-batch serving. Differentiable end-to-end via the fused
    Pallas backward kernels.

    ``block_h`` (>1) runs the multi-head-per-program FORWARD kernel:
    each grid step owns that many consecutive heads of one example
    (``heads % block_h == 0``), batching their matmuls in one program —
    the short-sequence lever (VERDICT r4 item 3), where the per-head
    grid's thousands of tiny programs pay more in grid/DMA overhead
    than compute. Because that is exactly the regime the
    ``XLA_SHORT_SEQ`` route covers, an explicit ``block_h>1``
    DISABLES the short-seq XLA route (on TPU) rather than being
    silently dropped by it. The backward keeps the per-head kernels
    (its grids are fewer and larger). Never swept on the chip
    (ROADMAP S5, D7).

    Dispatch: with ``interpret=None`` (the default used by every model
    template) the Pallas kernels run only on a real TPU backend AND at
    sequence lengths above ``XLA_SHORT_SEQ`` — short sequences measure
    faster through XLA's own fusions even on TPU (see the constant's
    note), and off-TPU the pure-XLA path is orders of magnitude faster
    than the Pallas interpreter. Pass ``interpret=True`` to force the
    kernels through the interpreter (the kernel-equivalence tests do),
    or ``interpret=False`` for Mosaic lowering.
    """
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(q.shape[-1])
    if block_h is None:  # env-tunable fleet default (RAFIKI_ATTN_BLOCK_H)
        block_h = _env_block_h(q.shape[1])
    # an explicit block_h>1 is a deliberate kernel-tuning choice FOR the
    # short-seq regime — it must not be silently dropped by the
    # short-seq XLA route (off-TPU fallback still applies)
    short = (interpret is None and block_h == 1
             and max(q.shape[2], k.shape[2]) <= XLA_SHORT_SEQ)
    if short or use_xla_fallback(interpret):
        lens = None if kv_lens is None else jnp.asarray(kv_lens, jnp.int32)
        return _attention_reference(q, k, v, scale, causal, lens)
    if kv_lens is None:
        return _flash_attention_full(q, k, v, scale, causal, block_q,
                                     block_k, interpret, block_h)
    return _flash_attention_varlen(q, k, v, jnp.asarray(kv_lens, jnp.int32),
                                   scale, causal, block_q, block_k,
                                   interpret, block_h)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_attention_full(q, k, v, sm_scale, causal, block_q, block_k,
                          interpret, block_h):
    return _flash_attention_fwd_impl(q, k, v, None, sm_scale, causal,
                                     block_q, block_k, interpret,
                                     block_h=block_h)


def _fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret, block_h):
    out, lse = _flash_attention_fwd_impl(
        q, k, v, None, sm_scale, causal, block_q, block_k, interpret,
        with_lse=True, block_h=block_h)
    return out, (q, k, v, out, lse)


def _bwd(sm_scale, causal, block_q, block_k, interpret, block_h,
         residuals, g):
    q, k, v, o, lse = residuals
    return _flash_attention_bwd_impl(q, k, v, None, o, lse, g, sm_scale,
                                     causal, block_q, block_k, interpret)


_flash_attention_full.defvjp(_fwd, _bwd)


def _attention_reference_lse(q, k, v, sm_scale: float, causal: bool):
    """XLA twin of :func:`flash_attention_lse` (off-TPU dispatch). Plain
    jnp math, so autodiff handles the LSE cotangent natively."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale
    if causal:
        s_q, s_k = s.shape[-2], s.shape[-1]
        mask = (jax.lax.broadcasted_iota(jnp.int32, (s_q, s_k), 0)
                >= jax.lax.broadcasted_iota(jnp.int32, (s_q, s_k), 1))
        s = jnp.where(mask, s, NEG_INF)
    lse = jax.scipy.special.logsumexp(s, axis=-1)
    p = jnp.exp(s - lse[..., None])
    out = jnp.einsum("bhqk,bhkd->bhqd", p,
                     v.astype(jnp.float32)).astype(q.dtype)
    return out, lse


def _lse_rows(lse_pad, q_shape):
    """(b*h, sq_padded, LANES) lane-replicated residual → (b, h, s_q)."""
    b, h, s_q, _ = q_shape
    return lse_pad[:, :s_q, 0].reshape(b, h, s_q)


def flash_attention_lse(q, k, v, sm_scale: Optional[float] = None,
                        causal: bool = False, block_q: int = 128,
                        block_k: int = 128,
                        interpret: Optional[bool] = None):
    """Like :func:`flash_attention` but returns ``(out, lse)`` where
    ``lse[b, h, i]`` is the log-sum-exp of row i's (scaled, masked)
    scores — the residual blockwise consumers (ring attention) need to
    combine per-block outputs exactly: out = Σ_blocks e^{lse_s − m}·out_s
    normalized. Differentiable in ``out`` AND ``lse``. Dispatch: Pallas
    on TPU at ANY length, XLA twin off-TPU — unlike
    :func:`flash_attention` there is NO short-seq XLA routing here: the
    callers (ring attention) hold long sequences by construction, and
    their per-block lse/combine math must come from one code path.
    No ``kv_lens`` support: a fully-masked row's LSE sentinel
    (+``LSE_MASKED``) would poison a cross-block max-combine."""
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(q.shape[-1])
    if use_xla_fallback(interpret):
        return _attention_reference_lse(q, k, v, scale, causal)
    return _flash_attention_full_lse(q, k, v, scale, causal, block_q,
                                     block_k, interpret)


def flash_attention_block_bwd(q, k, v, o, lse, g, sm_scale: float,
                              causal: bool = False, block_q: int = 128,
                              block_k: int = 128,
                              interpret: Optional[bool] = None):
    """One block's contribution to the GLOBAL attention backward.

    For blockwise/ring consumers: given this block's q/k/v, the globally
    combined output ``o`` and row log-sum-exp ``lse`` (b, h, s_q) over
    ALL blocks, and the output cotangent ``g``, returns (dq, dk, dv) for
    this block — ``p = exp(s − lse)`` are the block's columns of the
    global attention matrix, so summing dq over blocks and routing each
    dk/dv to its block reconstructs the exact full backward. Dispatch
    matches :func:`flash_attention_lse` (Pallas on TPU at any length,
    XLA twin off-TPU — no short-seq routing; the lse/combine math must
    come from one code path). f32 outputs (callers accumulate across
    blocks)."""
    if use_xla_fallback(interpret):
        s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                       k.astype(jnp.float32)) * sm_scale
        if causal:
            s_q, s_k = s.shape[-2], s.shape[-1]
            mask = (jax.lax.broadcasted_iota(jnp.int32, (s_q, s_k), 0)
                    >= jax.lax.broadcasted_iota(jnp.int32, (s_q, s_k), 1))
            s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse[..., None])
        gf = g.astype(jnp.float32)
        dv = jnp.einsum("bhqk,bhqd->bhkd", p, gf)
        dp = jnp.einsum("bhqd,bhkd->bhqk", gf, v.astype(jnp.float32))
        delta = jnp.sum(gf * o.astype(jnp.float32), axis=-1)
        ds = p * (dp - delta[..., None]) * sm_scale
        dq = jnp.einsum("bhqk,bhkd->bhqd", ds, k.astype(jnp.float32))
        dk = jnp.einsum("bhqk,bhqd->bhkd", ds, q.astype(jnp.float32))
        return dq, dk, dv
    b, h, s_q, _ = q.shape
    lse_pad = _pad_to(
        jnp.broadcast_to(lse.astype(jnp.float32).reshape(b * h, s_q, 1),
                         (b * h, s_q, LANES)), 1, block_q)
    dq, dk, dv = _flash_attention_bwd_impl(
        q, k, v, None, o, lse_pad, g, sm_scale, causal, block_q, block_k,
        interpret)
    return (dq.astype(jnp.float32), dk.astype(jnp.float32),
            dv.astype(jnp.float32))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_attention_full_lse(q, k, v, sm_scale, causal, block_q, block_k,
                              interpret):
    out, lse_pad = _flash_attention_fwd_impl(
        q, k, v, None, sm_scale, causal, block_q, block_k, interpret,
        with_lse=True)
    return out, _lse_rows(lse_pad, q.shape)


def _lse_fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret):
    out, lse_pad = _flash_attention_fwd_impl(
        q, k, v, None, sm_scale, causal, block_q, block_k, interpret,
        with_lse=True)
    return (out, _lse_rows(lse_pad, q.shape)), (q, k, v, out, lse_pad)


def _lse_bwd(sm_scale, causal, block_q, block_k, interpret, residuals, gs):
    q, k, v, o, lse_pad = residuals
    g_out, g_lse = gs
    return _flash_attention_bwd_impl(q, k, v, None, o, lse_pad, g_out,
                                     sm_scale, causal, block_q, block_k,
                                     interpret, g_lse=g_lse)


_flash_attention_full_lse.defvjp(_lse_fwd, _lse_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash_attention_varlen(q, k, v, kv_lens, sm_scale, causal, block_q,
                            block_k, interpret, block_h):
    return _flash_attention_fwd_impl(q, k, v, kv_lens, sm_scale, causal,
                                     block_q, block_k, interpret,
                                     block_h=block_h)


def _vfwd(q, k, v, kv_lens, sm_scale, causal, block_q, block_k, interpret,
          block_h):
    out, lse = _flash_attention_fwd_impl(
        q, k, v, kv_lens, sm_scale, causal, block_q, block_k, interpret,
        with_lse=True, block_h=block_h)
    return out, (q, k, v, kv_lens, out, lse)


def _vbwd(sm_scale, causal, block_q, block_k, interpret, block_h,
          residuals, g):
    import numpy as np

    q, k, v, kv_lens, o, lse = residuals
    dq, dk, dv = _flash_attention_bwd_impl(
        q, k, v, kv_lens, o, lse, g, sm_scale, causal, block_q, block_k,
        interpret)
    # integer primal → symbolic-zero cotangent (float0)
    d_lens = np.zeros(kv_lens.shape, jax.dtypes.float0)
    return dq, dk, dv, d_lens


_flash_attention_varlen.defvjp(_vfwd, _vbwd)


def mha(x_q, x_kv, params: dict, n_heads: int, causal: bool = False,
        interpret: Optional[bool] = None) -> jnp.ndarray:
    """Full multi-head attention layer over packed projection params.

    ``params`` carries ``wq, wk, wv`` (D, H*Dh) / ``wo`` (H*Dh, D) and
    biases; the core runs through :func:`flash_attention`.
    """
    b, s_q, d_model = x_q.shape
    s_kv = x_kv.shape[1]
    dh = params["wq"].shape[-1] // n_heads

    def proj(x, w, bias):
        y = jnp.einsum("bsd,df->bsf", x, w) + bias
        return y.reshape(b, -1, n_heads, dh).transpose(0, 2, 1, 3)

    q = proj(x_q, params["wq"], params["bq"])
    k = proj(x_kv, params["wk"], params["bk"])
    v = proj(x_kv, params["wv"], params["bv"])
    o = flash_attention(q, k, v, None, causal, 128, 128, interpret)
    o = o.transpose(0, 2, 1, 3).reshape(b, s_q, n_heads * dh)
    return jnp.einsum("bsf,fd->bsd", o, params["wo"]) + params["bo"]
