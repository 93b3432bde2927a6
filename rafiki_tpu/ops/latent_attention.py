"""Attention over a paged pool of LATENTS (multi-head latent attention's
cache): one ``(n_pages, page_size, r + dr)`` pool a layer, each row the
token's normalised key/value latent (``r`` wide) followed by its ONE
rotary key (``dr`` wide, shared by every head). Keys and values are
never expanded: the up-projection is absorbed into the query and the
output, so a head's score against a row is ``q_cat . row`` with
``q_cat = [q_nope W_k | q_rope]`` and its output is ``probs . row[:r]``
— multi-query attention with one "kv head" whose value is the first
``r`` lanes of its key. A row is read ONCE for both.

- :func:`latent_decode_attention` — the single-token step as a Pallas
  TPU kernel. Grid ``(slot, chunk)``; a chunk is ``pages_per_step``
  pool pages, fetched by handing the pool to the call that many times,
  each copy with an index map of its own that walks the block table
  (``tabs[b, chunk * pages_per_step + j]``): the pipeline streams all
  of a chunk's pages side by side and a grid step computes over
  ``pages_per_step * page_size`` rows, not one page's. Dead pages
  collapse onto pool page 0 (the engine's scratch page), whose repeated
  fetch the pipeline elides, and dead chunks skip their compute. The
  partial softmax of each chunk is folded into running f32 state in
  VMEM scratch, as in ``ops/paged_attention.py``.
- :func:`latent_gather_attention` — windows (chunked prefill) and every
  call off the TPU: gather the slot's pages into logical order and run
  the masked softmax in latent space through XLA. Same math, same
  operands; also the kernel's oracle.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from rafiki_tpu.ops.attention import NEG_INF, _resolve_interpret
from rafiki_tpu.ops.paged_attention import _partitioner_shield

#: pool rows one grid step of the step kernel computes over
ROWS_PER_STEP = 256


def _latent_step_kernel(t_ref, tab_ref, q_ref, *rest, rank: int,
                        page_size: int, pages_per_step: int,
                        n_chunks: int):
    from jax.experimental import pallas as pl

    page_refs = rest[:pages_per_step]
    o_ref, m_scr, l_scr, acc_scr = rest[pages_per_step:]
    bi = pl.program_id(0)
    ck = pl.program_id(1)
    t = t_ref[bi]  # this slot's query position (rows k_pos <= t live)
    rows = pages_per_step * page_size
    n_live = t // rows + 1  # live CHUNKS

    @pl.when(ck == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(ck < n_live)
    def _partial():
        # the chunk's pages, in logical order: (rows, r + dr)
        kv = jnp.concatenate([p[0] for p in page_refs], axis=0)
        q = q_ref[0]  # (heads, r + dr), already scaled
        s = jax.lax.dot_general(
            q, kv, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # (heads, rows)
        k_pos = ck * rows + jax.lax.broadcasted_iota(
            jnp.int32, (1, rows), 1)
        s = jnp.where(k_pos <= t, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, -1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(kv.dtype), kv[:, :rank], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)  # (heads, r)
        m_scr[...] = m_new

    @pl.when(ck == n_chunks - 1)
    def _finish():  # position 0 is always live, so l > 0
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                    ).astype(o_ref.dtype)


def latent_decode_attention(q_cat, pool, page_tables, positions,
                            rank: int,
                            pages_per_step: Optional[int] = None,
                            interpret: Optional[bool] = None
                            ) -> jnp.ndarray:
    """Single-token attention in latent space straight off the pool.

    - ``q_cat``: (b, heads, r + dr) — ``[q_nope W_k | q_rope]`` of this
      step's token, ALREADY multiplied by the softmax scale.
    - ``pool``: (n_pages, page_size, r + dr), ``rank`` = r.
    - ``page_tables``: (b, n_tables) int32, dead entries on pool page 0;
      the engine's live-width slice is welcome.
    - ``positions``: (b,) int32; rows ``k_pos <= positions[i]`` are live.

    Returns (b, heads, r): ``softmax(q_cat . rows) . rows[:, :r]``, to be
    taken through the value half of the up-projection by the caller.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, n_heads, width = q_cat.shape
    n_pages, page_size, width_p = pool.shape
    if width_p != width or not 0 < rank < width:
        raise ValueError(f"q_cat is {width} wide, the pool {width_p}, "
                         f"rank {rank}")
    n_tables = page_tables.shape[1]
    if pages_per_step is None:
        pages_per_step = max(1, ROWS_PER_STEP // page_size)
    pages_per_step = min(pages_per_step, n_tables)
    if n_tables % pages_per_step:
        raise ValueError(f"pages_per_step {pages_per_step} must divide "
                         f"the table width {n_tables}")
    n_chunks = n_tables // pages_per_step
    rows = pages_per_step * page_size
    interpret = _resolve_interpret(interpret)
    t = jnp.asarray(positions, jnp.int32)
    tabs = jnp.asarray(page_tables, jnp.int32)

    def q_map(bi, ck, t_ref, tab_ref):
        return (bi, 0, 0)

    def page_map(j):
        def index(bi, ck, t_ref, tab_ref):
            # the block-table walk: dead pages (past the slot's last
            # live one) collapse onto the scratch page
            pg = ck * pages_per_step + j
            live = pg <= t_ref[bi] // page_size
            return (jnp.where(live, tab_ref[bi, pg], 0), 0, 0)
        return index

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, n_chunks),
        in_specs=[pl.BlockSpec((1, n_heads, width), q_map)] + [
            pl.BlockSpec((1, page_size, width), page_map(j))
            for j in range(pages_per_step)],
        out_specs=pl.BlockSpec((1, n_heads, rank), q_map),
        scratch_shapes=[
            pltpu.VMEM((n_heads, 1), jnp.float32),     # running max
            pltpu.VMEM((n_heads, 1), jnp.float32),     # running sum
            pltpu.VMEM((n_heads, rank), jnp.float32),  # weighted latents
        ],
    )
    kernel = functools.partial(
        _latent_step_kernel, rank=rank, page_size=page_size,
        pages_per_step=pages_per_step, n_chunks=n_chunks)
    call = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, n_heads, rank), q_cat.dtype),
        interpret=interpret,
        name="latent_attn_step",  # what a profile calls the kernel
    )
    operands = (t, tabs, q_cat) + (pool,) * pages_per_step
    if interpret and jax.device_count() > 1:
        return _partitioner_shield(call, *operands)
    return call(*operands)


def latent_gather_attention(q_cat, rows, positions, rank: int
                            ) -> jnp.ndarray:
    """Window attention in latent space over rows in LOGICAL order.

    - ``q_cat``: (b, s, heads, r + dr), already scaled.
    - ``rows``: (b, length, r + dr) — the slot's pages gathered back
      (``pool[page_tables]`` reshaped), or a contiguous cache.
    - ``positions``: (b, s); window token i sees ``k_pos <=
      positions[b, i]`` (causal inside the window too).

    Returns (b, s, heads, r). The softmax runs in f32.
    """
    scores = jnp.einsum("bqhw,bkw->bhqk", q_cat, rows,
                        preferred_element_type=jnp.float32)
    k_pos = jnp.arange(rows.shape[1])[None, None, None, :]
    scores = jnp.where(k_pos <= positions[:, None, :, None], scores,
                       NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkr->bqhr", probs.astype(rows.dtype),
                      rows[..., :rank])
