"""Attention over a paged pool of LATENTS (multi-head latent attention's
cache). A cached token is its normalised key/value latent (``r`` wide)
and its ONE rotary key (``dr`` wide, shared by every head), kept a layer
in two leaves whose minor dimensions fill whole 128-lane tiles at the
published widths, so that a kernel can slice them where they lie in HBM:

- ``latents``: ``(n_pages, page_size, r)`` — row ``p`` of a page is the
  page's position ``p``;
- ``keys``: ``(n_pages, page_size / 2, 2 * dr)`` — TWO positions a row:
  position ``p`` of a page lies in row ``p % (page_size / 2)``, lanes
  ``[p // (page_size / 2) * dr, ... + dr)`` (:func:`packed_key_write`,
  :func:`packed_key_rows`). 256 + 64 values a position, none padded.

Keys and values are never expanded: the up-projection is absorbed into
the query and the output, so a head's score against a position is
``q_lat . latent + q_rope . key`` (two f32 products, ``q_lat = q_nope
W_k``) and its output is ``probs . latent`` — multi-query attention with
one "kv head" whose value is its key's first ``r`` values. A row is read
ONCE for both.

- :func:`latent_decode_attention` — the single-token step as a Pallas
  TPU kernel. Grid ``(slot, block)``. Pool pages are computed a STEP at
  a time (``pages_per_step`` of them), in two HALVES: the first half
  pages of the step's pages and then the second ones — the order the
  packed keys lie in — with the position mask and the value rows in
  that same order; each step's partial softmax is folded into running
  f32 state in VMEM scratch, as in ``ops/paged_attention.py``. Who
  fetches the pages follows from the leaves' shapes
  (:func:`copies_own_pages`):

  - **the kernel's own copies** where both minor dimensions fill the
    lanes: the leaves stay in HBM (``pl.ANY``), a grid step covers a
    BLOCK of ``ROWS_PER_BLOCK`` positions (a slot's whole context at
    the served lengths) and starts one DMA a leaf for every LIVE page
    of the NEXT live block (this slot's next block, else block 0 of the
    next slot) before it waits for its own, two VMEM buffers toggled
    across grid steps. A page lands in the buffer where the halves'
    order wants it, so the products run on the buffers as they stand,
    over the block's live steps alone. A dead page costs no descriptor,
    a dead step no product and a dead block no fetch.
  - **the BlockSpec pipeline** otherwise (narrow test widths, which
    Mosaic could not slice in HBM): a grid step is one step, the leaves
    handed to the call once a page of it, each copy with an index map
    of its own that walks the block table. Dead pages collapse onto
    pool page 0 (the engine's scratch page), whose repeated fetch the
    pipeline elides; every page of the table still costs its
    bookkeeping.
- :func:`latent_gather_attention` — windows (chunked prefill) and every
  call off the TPU: the slot's pages gathered into logical order and the
  masked softmax in latent space through XLA. Same math, same operands;
  also the kernel's oracle.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from rafiki_tpu.ops.attention import NEG_INF, _resolve_interpret
from rafiki_tpu.ops.paged_attention import _partitioner_shield

#: pool rows the step kernel computes over at a time where it copies its
#: own pages: the products' fixed cost (32 query rows meet every tile of
#: a step's rows as MXU weights) is paid once for 1,024 rows — 512 read
#: 10% slower on the chip, 2,048 waste a half-dead step's work
ROWS_PER_STEP = 1024
#: the same on the BlockSpec pipeline, whose grid step fetches what it
#: computes: an operand a page and leaf, so a shorter step
PIPELINE_ROWS_PER_STEP = 256
#: pool rows a grid step of the kernel's own copies fetches and covers
#: (a BLOCK: several steps' worth, or the whole table where it is
#: narrower): a slot's context is then one or two grid steps, fetched
#: while the slot before it is computed
ROWS_PER_BLOCK = 2048


# ------------------------------------------------- the packed rotary keys
def packed_key_write(keys, pages, offsets, values,
                     interpret: Optional[bool] = None):
    """Write rotary keys into the packed leaf: ``values[b, i]`` (``dr``
    wide) becomes position ``offsets[b, i]`` of pool page ``pages[b, i]``
    — ``dr`` lanes of one row, the row's other half left as it is. Whole
    rows are read, changed and scattered back in place (the scatter of
    whole rows XLA fuses, as ``kv_cache_write``'s; a scatter of half
    rows it expands into a loop over a copy of the leaf), one half of
    the positions after the other: two positions of a call may share a
    row, never two of one half. A single-token call holds one position a
    slot, so one pass. Under a multi-device interpreter mesh it runs
    inside the partitioner shield, as ``kv_cache_write`` does and why."""
    if _resolve_interpret(interpret) and jax.device_count() > 1:
        return _partitioner_shield(_write_packed, keys, pages, offsets,
                                   values)
    return _write_packed(keys, pages, offsets, values)


@jax.jit  # traced once a program, not once a layer; the caller's
def _write_packed(keys, pg, off, v):  # program donates the leaf
    half_rows, dr = keys.shape[1], keys.shape[2] // 2
    row, second = off % half_rows, (off >= half_rows)[..., None]
    v = jnp.concatenate([v, v], -1).astype(keys.dtype)
    lane_second = jnp.arange(2 * dr) >= dr
    if v.shape[1] == 1:
        return keys.at[pg, row].set(
            jnp.where(lane_second == second, v, keys[pg, row]))
    out = keys
    for h in (False, True):
        # the other half's positions are aimed past the pool and dropped
        at = jnp.where(second[..., 0] == h, pg, keys.shape[0])
        out = out.at[at, row].set(
            jnp.where(lane_second == h, v, out[pg, row]), mode="drop")
    return out


def packed_key_rows(keys, page_tables):
    """A batch's rotary keys gathered back into LOGICAL order: ``(b,
    n_tables * page_size, dr)`` from the packed leaf and the slots' block
    tables — the window path's ``pool[page_tables]``, unpacked."""
    b, n_tables = page_tables.shape
    half_rows, dr = keys.shape[1], keys.shape[2] // 2
    rows = keys[page_tables].reshape(b, n_tables, half_rows, 2, dr)
    return rows.swapaxes(2, 3).reshape(b, n_tables * 2 * half_rows, dr)


def copies_own_pages(latents, keys) -> bool:
    """Whether the step kernel fetches pages with its own DMAs (the
    leaves stay in HBM) or leaves them to the BlockSpec pipeline, one
    operand a page and leaf. Its own copies cost a descriptor a LIVE
    page; the pipeline's bookkeeping costs more than that for every page
    of the table, live or dead — but Mosaic slices an HBM ref only where
    its minor dimension fills the 128 lanes and a half page fills whole
    sublane tiles (8 rows of 32 bits), so narrower or shorter leaves
    keep the pipeline. Read off the operands' shapes alone."""
    tile_rows = 8 * 4 // latents.dtype.itemsize
    return (latents.shape[-1] % 128 == 0 and keys.shape[-1] % 128 == 0
            and keys.shape[1] % tile_rows == 0)


# ---------------------------------------------------------- the step kernel
def _latent_step_kernel(t_ref, tab_ref, ql_ref, qr_ref, *rest,
                        page_size: int, pages: int, steps: int,
                        n_blocks: int, own_copies: bool):
    """``pages`` pool pages are computed at a time; a block — what one
    grid step fetches and covers — is ``steps`` of those (1 on the
    pipeline)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    # operands: the latents and the keys (own copies: the two leaves in
    # HBM; else a block ref a page of each)
    n_refs = 1 if own_copies else pages
    lat_refs, key_refs = rest[:n_refs], rest[n_refs:2 * n_refs]
    o_ref, m_scr, l_scr, acc_scr, *copy_scr = rest[2 * n_refs:]
    bi, blk = pl.program_id(0), pl.program_id(1)
    t = t_ref[bi]  # this slot's query position (rows k_pos <= t live)
    half = page_size // 2  # positions of a page in one half
    rows = pages * half  # buffer rows (of either half) a step computes
    span = steps * pages * page_size  # positions a block covers
    n_heads = ql_ref.shape[1]

    def contract(a, b, dims):
        return jax.lax.dot_general(a, b, (dims, ((), ())),
                                   preferred_element_type=jnp.float32)

    def fold(first, halves, keys):
        """Fold ``pages`` pages from position ``first`` on into the
        running softmax: ``halves`` their first and second half pages
        side by side, (rows, r) each, ``keys`` (rows, 2 dr)."""
        # the rotary scores of BOTH halves in one product: the query
        # arrives twice, beside zeros, ``[q_rope | 0]`` over ``[0 |
        # q_rope]``, so rows [:heads] meet the keys' first lanes (the
        # first half pages) and rows [heads:] their second
        s_rope = contract(qr_ref[0], keys, ((1,), (1,)))
        q_lat = ql_ref[0]  # (heads, r), already scaled
        col = jax.lax.broadcasted_iota(jnp.int32, (1, rows), 1)
        k_pos = first + col // half * page_size + col % half
        s = []
        for h, lat in enumerate(halves):
            s_h = contract(q_lat, lat, ((1,), (1,))) + s_rope[
                h * n_heads:(h + 1) * n_heads]  # (heads, rows)
            # masks the dead pages of a partly live step and the last
            # live page's tail
            s.append(jnp.where(k_pos + h * half <= t, s_h, NEG_INF))
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.maximum(
            jnp.max(s[0], -1, keepdims=True),
            jnp.max(s[1], -1, keepdims=True)))
        alpha = jnp.exp(m_prev - m_new)
        p = [jnp.exp(s_h - m_new) for s_h in s]
        l_scr[...] = l_scr[...] * alpha + (
            jnp.sum(p[0], -1, keepdims=True)
            + jnp.sum(p[1], -1, keepdims=True))
        acc_scr[...] = acc_scr[...] * alpha + sum(
            contract(p_h.astype(lat.dtype), lat, ((1,), (0,)))
            for p_h, lat in zip(p, halves))  # (heads, r)
        m_scr[...] = m_new

    if own_copies:
        lat_buf, key_buf, sems, slot_scr = copy_scr
        n_slots = pl.num_programs(0)

        def live_pages(b_, blk_):
            return jnp.clip(t_ref[b_] // page_size + 1
                            - blk_ * steps * pages, 0, steps * pages)

        def rows_at(i, size):  # buffer rows [i * size, (i + 1) * size)
            return pl.ds(pl.multiple_of(i * size, size), size)

        def copies(slot, at, src=None):
            """The two DMAs — latents, packed keys — into buffer rows
            ``at`` of ``slot``. Without a source: a descriptor of the
            same bytes, to wait with."""
            dst = (lat_buf.at[slot, :, at], key_buf.at[slot, at])
            return [pltpu.make_async_copy(s_, d, sems.at[slot, i])
                    for i, (s_, d) in enumerate(zip(src or dst, dst))]

        def start_block(b_, blk_, slot):
            """Start the copies of a block's LIVE pages into buffer
            ``slot``: every page up to the slot's last live one, its
            latents as two half pages to where the halves' order wants
            them and its packed keys as they lie, each ONE DMA straight
            out of the leaf by the block table."""
            def page(j, carry):
                entry = tab_ref[b_, blk_ * steps * pages + j]
                for copy in copies(slot, rows_at(j, half), (
                        lat_refs[0].at[entry], key_refs[0].at[entry])):
                    copy.start()
                return carry

            jax.lax.fori_loop(0, live_pages(b_, blk_), page, 0)

        def wait_block(b_, blk_, slot):
            """Wait for ALL of them — a semaphore counts bytes, so a
            whole step's pages are waited for at once and the last, partly
            live step's one by one; nothing is read before the last
            wait, when every copy has landed."""
            n_live = live_pages(b_, blk_)

            def wait(size):
                def body(i, carry):
                    for copy in copies(slot, rows_at(i, size)):
                        copy.wait()
                    return carry
                return body

            jax.lax.fori_loop(0, n_live // pages, wait(rows), 0)
            jax.lax.fori_loop(n_live // pages * pages, n_live, wait(half), 0)

        @pl.when((bi == 0) & (blk == 0))
        def _prime():  # nothing fetched the call's first block yet.
            # A partly live step's unfetched pages meet probabilities
            # of exactly 0: what the buffers hold there must be finite
            lat_buf[...] = jnp.zeros_like(lat_buf)
            key_buf[...] = jnp.zeros_like(key_buf)
            slot_scr[0] = 0
            start_block(bi, blk, 0)

    @pl.when(blk == 0)
    def _init():  # a fresh slot: reset the running state
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(blk * span <= t)
    def _partial():  # dead blocks: no compute, and nothing fetched
        if not own_copies:
            # (pages * half, r) a half and (pages * half, 2 dr): the
            # block's pages side by side, the halves' order
            return fold(
                blk * span,
                [jnp.concatenate([ref[0, h] for ref in lat_refs], axis=0)
                 for h in range(2)],
                jnp.concatenate([ref[0] for ref in key_refs], axis=0))
        slot = slot_scr[0]
        # double buffering across grid steps: start the NEXT live
        # block's copies (this slot's next block, else block 0 of the
        # next slot, which is always live), then wait for ours
        more = (blk + 1) * span <= t
        nxt = (jnp.where(more, bi, bi + 1), jnp.where(more, blk + 1, 0))

        @pl.when(nxt[0] < n_slots)
        def _prefetch():
            start_block(*nxt, 1 - slot)

        wait_block(bi, blk, slot)
        slot_scr[0] = 1 - slot

        def step(i, carry):  # the live steps of the block alone
            at = rows_at(i, rows)
            fold(blk * span + i * pages * page_size,
                 [lat_buf[slot, h, at] for h in range(2)],
                 key_buf[slot, at])
            return carry

        jax.lax.fori_loop(
            0, (live_pages(bi, blk) + pages - 1) // pages, step, 0)

    @pl.when(blk == n_blocks - 1)
    def _finish():  # position 0 is always live, so l > 0
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                    ).astype(o_ref.dtype)


def latent_decode_attention(q_lat, q_rope, latents, keys, page_tables,
                            positions,
                            pages_per_step: Optional[int] = None,
                            interpret: Optional[bool] = None
                            ) -> jnp.ndarray:
    """Single-token attention in latent space straight off the pool.

    - ``q_lat``: (b, heads, r) — ``q_nope W_k`` of this step's token —
      and ``q_rope``: (b, heads, dr), both ALREADY multiplied by the
      softmax scale.
    - ``latents``: (n_pages, page_size, r); ``keys``: (n_pages,
      page_size / 2, 2 * dr), packed (see the module's docstring).
    - ``page_tables``: (b, n_tables) int32, dead entries on pool page 0;
      the engine's live-width slice is welcome, of any width.
    - ``positions``: (b,) int32; rows ``k_pos <= positions[i]`` are
      live. Held to ``[0, n_tables * page_size)``.

    Who fetches the pages (:func:`copies_own_pages`), how many are
    computed at a time (``pages_per_step``; by default ``ROWS_PER_STEP``
    rows, ``PIPELINE_ROWS_PER_STEP`` on the pipeline, or the whole table
    where it is narrower) and how many a grid step covers follow from
    the shapes of the call.

    Returns (b, heads, r): ``softmax(q_lat . latents + q_rope . keys) .
    latents``, to be taken through the value half of the up-projection
    by the caller.
    """
    rank = q_lat.shape[-1]
    n_pages, page_size, rank_p = latents.shape
    dr = q_rope.shape[-1]
    half = page_size // 2
    if (rank_p != rank or keys.shape != (n_pages, half, 2 * dr)
            or page_size % 2):
        raise ValueError(
            f"queries {rank} + {dr} wide over latents {latents.shape} "
            f"and packed keys {keys.shape}: the keys must be "
            f"(pages, page_size / 2, 2 x {dr})")
    n_tables = page_tables.shape[1]
    own_copies = copies_own_pages(latents, keys)
    if pages_per_step is None:
        pages_per_step = max(1, (ROWS_PER_STEP if own_copies else
                                 PIPELINE_ROWS_PER_STEP) // page_size)
    pages = min(pages_per_step, n_tables)
    # steps a block: its pages are copied while the block before it is
    # computed; the pipeline fetches what a step computes
    steps = min(max(1, ROWS_PER_BLOCK // (pages * page_size)),
                -(-n_tables // pages)) if own_copies else 1
    return _latent_step(q_lat, q_rope, latents, keys, page_tables, positions,
                        pages=pages, steps=steps, own_copies=own_copies,
                        interpret=_resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("pages", "steps", "own_copies",
                                             "interpret"))
def _latent_step(q_lat, q_rope, latents, keys, page_tables, positions, *,
                 pages: int, steps: int, own_copies: bool, interpret: bool):
    """:func:`latent_decode_attention` once its shapes have decided the
    step, the block and the fetch. A jit of its own: a decoder's layers
    make the same call, and the kernel is traced and lowered once a
    program, not once a layer (0.2-0.4 s of host time each: a serving
    process meets a dozen step programs before its first token)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, n_heads, rank = q_lat.shape
    n_pages, page_size, _ = latents.shape
    dr, half, n_tables = q_rope.shape[-1], page_size // 2, page_tables.shape[1]
    n_blocks = -(-n_tables // (pages * steps))
    # position 0 is always live and no position lies past the table:
    # held here, so that the kernel's own copies always pair a start
    # with a wait whatever the caller passes
    t = jnp.clip(jnp.asarray(positions, jnp.int32), 0,
                 n_tables * page_size - 1)
    tabs = jnp.asarray(page_tables, jnp.int32)
    # the rotary query twice, beside zeros (the kernel's one product
    # over the packed keys), and a page of latents as its two halves:
    # the same bytes, (page_size, r) row-major
    zero = jnp.zeros_like(q_rope)
    q_pair = jnp.concatenate(
        [jnp.concatenate([q_rope, zero], -1),
         jnp.concatenate([zero, q_rope], -1)], axis=1)
    lat_halves = latents.reshape(n_pages, 2, half, rank)

    def q_map(bi, blk, t_ref, tab_ref):
        return (bi, 0, 0)

    def page_specs(block_shape):
        """The BlockSpec pipeline's fetch of a block: pages of a slot
        are not contiguous in the pool, so a leaf is handed to the call
        ``pages`` times, every copy with an index map of its own that
        walks the block table. Live pages come from the table; dead ones
        (past the slot's last live page, or past the table in a ragged
        last block) collapse onto the scratch page."""
        def spec(j):
            def index(bi, blk, t_ref, tab_ref):
                pg = blk * pages + j
                live = pg <= t_ref[bi] // page_size
                page = jnp.where(
                    live, tab_ref[bi, jnp.minimum(pg, n_tables - 1)], 0)
                return (page,) + (0,) * (len(block_shape) - 1)
            return pl.BlockSpec(block_shape, index)
        return [spec(j) for j in range(pages)]

    scratch_shapes = [
        pltpu.VMEM((n_heads, 1), jnp.float32),     # running max
        pltpu.VMEM((n_heads, 1), jnp.float32),     # running sum
        pltpu.VMEM((n_heads, rank), jnp.float32),  # weighted latents
    ]
    in_specs = [pl.BlockSpec((1, n_heads, rank), q_map),
                pl.BlockSpec((1, 2 * n_heads, 2 * dr), q_map)]
    if own_copies:
        in_specs += [pl.BlockSpec(memory_space=pl.ANY)] * 2
        operands = [lat_halves, keys]
        scratch_shapes += [
            # a block of latents (its two halves) and of packed keys,
            # double-buffered
            pltpu.VMEM((2, 2, steps * pages * half, rank), latents.dtype),
            pltpu.VMEM((2, steps * pages * half, 2 * dr), keys.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),  # (buffer, latents or keys)
            pltpu.SMEM((1,), jnp.int32),      # the buffer in use
        ]
    else:
        in_specs += (page_specs((1, 2, half, rank))
                     + page_specs((1, half, 2 * dr)))
        operands = [lat_halves] * pages + [keys] * pages

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, n_blocks),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, n_heads, rank), q_map),
        scratch_shapes=scratch_shapes,
    )
    kernel = functools.partial(
        _latent_step_kernel, page_size=page_size, pages=pages,
        steps=steps, n_blocks=n_blocks, own_copies=own_copies)
    call = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, n_heads, rank), q_lat.dtype),
        interpret=interpret,
        name="latent_attn_step",  # what a profile calls the kernel
    )
    operands = (t, tabs, q_lat, q_pair, *operands)
    if interpret and jax.device_count() > 1:
        return _partitioner_shield(call, *operands)
    return call(*operands)


def latent_gather_attention(q_lat, q_rope, latents, keys, positions
                            ) -> jnp.ndarray:
    """Window attention in latent space over rows in LOGICAL order.

    - ``q_lat``: (b, s, heads, r) and ``q_rope``: (b, s, heads, dr),
      already scaled.
    - ``latents``: (b, length, r) and ``keys``: (b, length, dr) — the
      slot's pages gathered back (``latents[page_tables]`` reshaped,
      :func:`packed_key_rows`), or a contiguous cache.
    - ``positions``: (b, s); window token i sees ``k_pos <=
      positions[b, i]`` (causal inside the window too).

    Returns (b, s, heads, r). The softmax runs in f32.
    """
    scores = (jnp.einsum("bqhr,bkr->bhqk", q_lat, latents,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bqhd,bkd->bhqk", q_rope, keys,
                           preferred_element_type=jnp.float32))
    k_pos = jnp.arange(latents.shape[1])[None, None, None, :]
    scores = jnp.where(k_pos <= positions[:, None, :, None], scores,
                       NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkr->bqhr", probs.astype(latents.dtype),
                      latents)
