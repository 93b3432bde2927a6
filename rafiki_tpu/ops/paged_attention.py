"""Paged-native flash decode attention as a Pallas TPU kernel.

The paged KV pool (serving PR 5) cut cache HBM 2.56x but the decode hot
loop paid the win back: every step ``_DecoderAttention`` gathered all of
a slot's pages back into logical ``(b, max_len, heads, dh)`` order
before the masked softmax, re-materializing the whole logical KV per
generated token. This kernel consumes the pool **directly**:

- **Grid over (slot tile, kv-head tile, blocks of pages).** A grid
  step consumes a BLOCK of pool pages — ``BLOCK_KEYS`` key positions,
  fewer where many wide heads would not fit VMEM (``BLOCK_BYTES``), or
  the whole table where it is narrower — for each slot of a TILE of
  slots (``_slots_per_tile``: up to ``TILE_SLOTS`` whose blocks fit
  ``SLOT_TILE_BYTES``, read off the call's shapes), so a step moves
  megabytes and the per-step costs are paid once a tile. Pages of a
  slot are not contiguous in the pool, so a block is fetched page by
  page off the scalar-prefetched block table; the page gather never
  materializes in HBM.
- **The kernel copies its own pages** where heads fill the 128 lanes
  (``_copies_own_pages``): the pools stay in HBM and a step starts one
  DMA a LIVE page of each slot of the NEXT tile-step where any of them
  has a key left, while it computes this one (two VMEM buffers,
  toggled across grid steps). A float pool is handed to the kernel as
  ``(pages, page_size * n_kv, dh)`` — a free view, the (key, head)
  rows of a page in the pool's own order — so a page lands in VMEM as
  the products read it (``_copies_page_rows``). An int8 pool and a
  head tile narrower than the kv axis copy ``(page_size, block_h,
  dh)`` blocks, one slot a step. Narrower heads, and an int8 pool's
  scale rows, leave the fetch to the BlockSpec pipeline, one slot a
  step, the pool handed to the call once a page of the block with an
  index map of its own (``tabs[b, block * pages + j]``) — legal at
  every shape, at a bookkeeping cost for every page of the table.
- **Every kv head in one product, every slot of the tile in one
  batch.** A block's pages are one 2-D matrix ``(pages * page_size *
  heads, dh)`` — rows are key x head in the pool's own order, no
  per-head slice — and all of a slot's query rows meet its block's
  rows in ONE ``QK^T`` and ONE ``PV`` product, batched over the tile's
  slots. The columns of other kv heads are masked with the dead keys,
  so their probabilities are exactly 0 and heads never mix; the MXU,
  idle in a bandwidth-bound kernel, pays the extra flops.
- **LSE-merged partial softmax.** Per block the program computes a
  partial (max, sum, weighted-V accumulator) and folds it into running
  f32 state in VMEM scratch, a slot's own rows — the same
  online-softmax recurrence ``_attn_fwd_kernel`` streams key blocks
  with, here streamed across grid steps (TPU grids execute
  sequentially per core; the block axis is minor, so a (slot tile,
  head-tile) row sees its blocks back to back and the last block's
  step writes the normalized output).
- **Live pages only.** A slot at position ``t`` owns ``t // page_size
  + 1`` live pages. A block where no slot of the tile has a key skips
  its compute via ``pl.when`` and fetches nothing; a slot with no key
  left in a live block copies nothing and its masked product leaves
  its state as it was; in a partly live block the kernel's own copies
  skip the dead pages, and the pipeline's index maps collapse them
  onto pool page 0 (the engine's scratch page — dead table entries
  already point there), whose repeated fetch is elided. Per-step HBM
  traffic scales with LIVE tokens, not ``max_len``.
- **Fused int8-KV dequant.** Quantized pools pass their f32 absmax
  scale rows (same pool geometry, same table walk); the kernel
  dequantizes each page in registers before the product — the scale
  multiply fuses into the f32 attention math and no dequantized cache
  ever exists.
- **GQA without the repeat.** Query rows arrive grouped per kv head
  tile (``rep = n_heads / n_kv_heads`` rows a kv head), so the
  ``jnp.repeat`` the gather path pays per step never happens.
  ``block_h`` tiles kv heads per program exactly like
  ``flash_attention``'s head tiling — but defaulting to the WHOLE kv
  axis, the one tile Mosaic accepts at every head count (see
  ``_resolve_block_h``).

``paged_window_attention`` is the (s >= 1) **query window** form, so
chunked prefill and speculative-verify calls run paged-native too. Its
grid is (batch, kv-head tile, query tile, pages): ``block_q`` window
tokens per program (a prefill chunk's worth, up to ``TILE_QUERIES``,
so that a row of a prefill call is one tile and walks its pages
once), ONE ``(page_size, block_h, dh)`` K/V block a grid
step through a BlockSpec whose index map walks the block table, a
static unroll over the tile's heads, the same LSE-merge recurrence
streamed across pages per query tile, and a causal mask per ROW: window token i at absolute
position ``positions[b, i]`` sees keys ``k_pos <= positions[b, i]``
(the s==1 "last token sees everything" rule is the degenerate case).
Window positions must be NONDECREASING along each row — exactly what
the engine's prefill/verify windows provide (idle and overhang rows
repeat the last real entry) — so a query tile's last row bounds its
live pages and dead-page skipping carries over per tile.

Both kernels take a static ``window``: a query then sees only the last
``window`` keys, and the grid's last axis walks only the blocks (step)
or pages (query window) that can hold them, from the one with the
window's first key — what lies behind the window is neither fetched nor
read. They are then ``window_attn_step`` / ``window_attn_prefill`` in a
profile, and ``ops/window_attention.py`` calls them over per-slot rings;
``window=None`` lowers to the program it was.

Dispatch policy (mirrors ``ops/attention.py``): the decode path runs
the kernel on TPU by default and falls back to the page gather off-TPU
(``resolve_paged_kernel``); multi-token windows additionally honor the
``RAFIKI_PAGED_KERNEL_WINDOWS`` escape hatch
(``resolve_paged_window_kernel``), which drops the engine back to
step-only kernel mode without touching the s==1 hot loop.
``interpret=True`` forces the kernel through the Pallas interpreter,
which is how the CPU tier-1 equivalence tests run it. Numerics: f32
accumulation regardless of pool dtype; the online softmax is the
associativity-reordered twin of the gather path's masked softmax, so
outputs agree to f32 roundoff (token-exact in practice — proven per
decode mode in ``tests/test_paged_kv.py``).
"""

from __future__ import annotations

import functools
import math
import os
from typing import Optional

import jax
import jax.numpy as jnp

from rafiki_tpu.ops.attention import NEG_INF, _resolve_interpret
from rafiki_tpu.ops.common import gqa_repeat_factor


def resolve_paged_kernel(flag: Optional[bool]) -> bool:
    """The serving dispatch rule for the ``paged_kernel`` flag:
    ``None`` (auto, the fleet default) runs the kernel only on a real
    TPU backend — off-TPU the page gather through XLA is orders of
    magnitude faster than the Pallas interpreter. An explicit
    ``True``/``False`` wins either way (tests force ``True`` and ride
    the interpreter via ``_resolve_interpret``)."""
    if flag is None:
        return jax.default_backend() == "tpu"
    return bool(flag)


def resolve_paged_window_kernel(flag: Optional[bool]) -> bool:
    """Dispatch rule for the MULTI-TOKEN window legs (chunked prefill,
    speculative verify). Windows ride the same tri-state ``paged_kernel``
    flag as the s==1 step, with one extra operational escape hatch:
    ``RAFIKI_PAGED_KERNEL_WINDOWS=0`` (or ``false``/``off``) forces the
    window legs back onto the gather fallback — step-only kernel mode —
    without touching the single-token hot loop. Default is enabled, so
    wherever ``resolve_paged_kernel`` says kernel, windows go kernel
    too."""
    if os.environ.get("RAFIKI_PAGED_KERNEL_WINDOWS", "1").lower() in (
            "0", "false", "off"):
        return False
    return resolve_paged_kernel(flag)


def _partitioner_shield(call, *operands):
    """Run a pallas call as a fully-replicated ``shard_map`` manual
    region when the Pallas INTERPRETER executes under a multi-device
    backend (the CPU tier-1 test mesh).

    Interpret mode lowers the kernel to an ordinary XLA while-loop, and
    the auto-SPMD partitioner is free to slice its internals across
    devices. Empirically that choice leaks OUT of the kernel: with the
    loop in the program, the partitioner re-shards the surrounding
    cache-update scatter into an add-combined form that applies every
    update once PER REPLICA GROUP — the KV pool comes back exactly
    doubled (reproduced under the 8-device CPU mesh; the gather-only
    twin of the same program is correct). Marking the kernel region
    manual with every operand replicated keeps the partitioner out of
    the interpreter loop entirely, and the surrounding program then
    partitions exactly as the gather path does. Real-TPU programs
    (``interpret=False``) never take this wrapper: there the kernel is
    an opaque custom call and partitions as it always has.
    """
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from rafiki_tpu.ops.common import shard_map_kernels

    mesh = Mesh(np.asarray(jax.devices()), ("_pk_replica",))
    spec = PartitionSpec()
    # materialize TRUE replicas first: an operand may reach this point
    # as a pending partial-sum (the partitioner splitting an upstream
    # contraction), and the unchecked map would hand each device its
    # partial as if it were the whole value. The explicit constraint
    # forces the all-reduce BEFORE the manual region.
    replicated = NamedSharding(mesh, spec)
    operands = tuple(
        jax.lax.with_sharding_constraint(o, replicated)
        for o in operands)
    return shard_map_kernels(
        call, mesh=mesh, in_specs=(spec,) * len(operands),
        out_specs=spec)(*operands)


def kv_cache_write(cache, idx0, idx1, values,
                   interpret: Optional[bool] = None):
    """Scatter a decode window's K/V (or scale) rows into the KV cache:
    ``cache[idx0[b, i], idx1[b, i]] = values[b, i]`` — ``(pool page,
    page slot)`` indices for the paged layout, ``(batch row, position)``
    for the contiguous one.

    Semantically this is nothing but ``cache.at[idx0, idx1].set(values)``
    — and that is exactly what runs on real TPU and on a single-device
    CPU. Under a MULTI-device interpret mesh it detours through the
    partitioner shield instead, because the auto-SPMD partitioner
    re-lowers the inline set-scatter in a way that lets the cache
    replicas diverge and then reconciles them ADDITIVELY: the rope'd K
    projection reaches the scatter as a pending partial-sum, each
    replica group writes its partial, and the stored K comes back
    exactly DOUBLED (reproduced on the 8-device CPU test mesh against
    a single-device ground truth; V, whose updates happen to reach the
    scatter fully reduced, survives). The corruption was invisible
    while every decode program shared it — token parity held between
    equally-wrong twins — and surfaced the moment one path stopped
    being wrong. Routing the write through the replicated manual
    region (see :func:`_partitioner_shield`) pins the single-device
    lowering everywhere the interpreter runs.
    """
    def write(c, i0, i1, v):
        return c.at[i0, i1].set(v)

    if _resolve_interpret(interpret) and jax.device_count() > 1:
        return _partitioner_shield(write, cache, idx0, idx1, values)
    return write(cache, idx0, idx1, values)


def _resolve_block_h(block_h: Optional[int], n_kv: int) -> int:
    """The kv-head tile of both paged kernels. The pool keeps heads in
    its second-to-last dim, and Mosaic takes a block there only when it
    is the whole axis or a multiple of 8 ("the last two dimensions of
    your block shape [must be] divisible by 8 and 128 … or equal to the
    respective dimensions of the overall array"). So the default is the
    whole kv axis — legal at every head count — and never the
    ``flash_attention`` fleet default, whose per-head tile (1) is
    exactly what the compiler refuses here. An explicit ``block_h``
    must divide the kv head count; whether it is a legal tile is the
    compiler's call, made loudly at lowering."""
    if block_h is None:
        return n_kv
    if block_h < 1 or n_kv % block_h:
        raise ValueError(f"block_h={block_h} must be >= 1 and divide "
                         f"the kv head count ({n_kv})")
    return block_h


def _tile_first_head(block_h: int, s_ref):
    """First kv head of this program's tile, as a column of the scale
    block ``s_ref`` (``None`` for an unquantized pool). Scale blocks
    always span the WHOLE kv axis: heads are their LAST dim, where
    Mosaic takes only the full axis or a multiple of 128 as a block.
    Static 0 when the tile is the whole axis (the default); off the
    head-tile program id otherwise — read here, at the kernel's top
    level, because the interpreter has no ``program_id`` inside a
    ``pl.when`` body."""
    from jax.experimental import pallas as pl

    if s_ref is None or block_h == s_ref.shape[-1]:
        return 0
    return pl.program_id(1) * block_h


def _head_scale(s_ref, head):
    """The (page_size, 1) dequant scales of kv head ``head``. A static
    head is a static column. A traced one (a tile narrower than the kv
    axis) is picked with a lane mask — Mosaic indexes lanes only
    statically ("cannot statically prove that index in dimension 2 is
    a multiple of 128") — which is exact: every other term of the sum
    is 0."""
    if isinstance(head, int):
        return s_ref[0, :, head][:, None]
    scales = s_ref[0]  # (page_size, n_kv)
    lane = jax.lax.broadcasted_iota(jnp.int32, scales.shape, 1)
    return jnp.sum(jnp.where(lane == head, scales, 0.0), -1, keepdims=True)


#: key positions one grid step of the step kernel spans (a BLOCK of
#: pool pages): wide enough that a step moves hundreds of KB and feeds
#: the MXU whole tiles, narrow enough that a half-dead block wastes
#: little — the kernel's time follows the live KV bytes, not a count
#: of pages
BLOCK_KEYS = 256
#: bytes a block of ONE pool may take in VMEM: K and V, each
#: double-buffered, and the products' temporaries beside them stay
#: inside the 16 MiB Mosaic gives a kernel (many wide heads take a
#: shorter block)
BLOCK_BYTES = 2 << 20


def _pages_per_block(page_size: int, n_tables: int, page_bytes: int) -> int:
    """Pool pages one grid step of the step kernel consumes: a block of
    about ``BLOCK_KEYS`` key positions that fits ``BLOCK_BYTES``, and a
    table narrower than that is ONE block — all read off the shapes the
    call is made with."""
    return max(1, min(n_tables, BLOCK_KEYS // page_size,
                      BLOCK_BYTES // page_bytes))


def _copies_own_pages(dh: int) -> bool:
    """Whether the step kernel fetches K/V pages with its own DMAs (the
    pools stay in HBM) or leaves them to the BlockSpec pipeline, one
    operand a page. Its own copies cost a descriptor a LIVE page; the
    pipeline's bookkeeping costs twice that for every page of the table,
    live or dead — but Mosaic slices an HBM ref only where its minor dim
    fills the 128 lanes, so narrower heads keep the pipeline."""
    return dh % 128 == 0


def _copies_page_rows(dh: int, n_kv: int, block_h: int, pool_dtype) -> bool:
    """Whether the step kernel copies a page as its ``(page_size *
    n_kv, dh)`` (key, head) rows — a free view of the pool whose rows
    lie in VMEM as they lie in HBM, ready for the products — and takes
    a TILE of slots a grid step (``_slots_per_tile``): where it copies
    its own pages of a float pool over the whole kv axis. A narrower
    head tile, and an int8 pool (its pages dequantised head by head by
    scale rows that the BlockSpec pipeline fetches a slot at a time),
    copy (page_size, block_h, dh) blocks, one slot a grid step."""
    return (_copies_own_pages(dh) and block_h == n_kv
            and jnp.dtype(pool_dtype).itemsize > 1)


def _window_start(t, window: int):
    """The first key position a query at ``t`` sees through a window of
    ``window`` keys: ``t - window + 1``, held to 0."""
    return jnp.maximum(t - (window - 1), 0)


def _window_blocks(window: int, span: int, n_blocks: int) -> int:
    """Blocks of ``span`` key positions a window of ``window`` keys can
    touch wherever it starts, within the table's ``n_blocks``."""
    return min(n_blocks, -(-(window - 1) // span) + 1)


def _tile_scales(s_ref, h0, block_h: int):
    """The (page_size, block_h) dequant scales of this program's head
    tile: the whole block when the tile is the kv axis (``h0`` static
    0), one lane-masked column a head otherwise (``_head_scale``)."""
    if isinstance(h0, int):
        return s_ref[0]
    return jnp.concatenate(
        [_head_scale(s_ref, h0 + hh) for hh in range(block_h)], axis=1)


def _paged_decode_kernel(t_ref, tab_ref, q_ref, *rest,
                         sm_scale: float, page_size: int, block_h: int,
                         rep: int, pages: int, n_blocks: int, tile: int,
                         quantized: bool, own_copies: bool,
                         window: Optional[int] = None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    # operands: K and V (own copies: the two pools in HBM; else a block
    # ref a page), then the scale blocks of an int8 pool, a page each
    n_kv_refs = 1 if own_copies else pages
    k_refs, v_refs = rest[:n_kv_refs], rest[n_kv_refs:2 * n_kv_refs]
    rest = rest[2 * n_kv_refs:]
    ks_refs = vs_refs = (None,) * pages
    if quantized:
        ks_refs, vs_refs, rest = (rest[:pages], rest[pages:2 * pages],
                                  rest[2 * pages:])
    o_ref, *rest = rest
    count_fetched = window is not None
    if count_fetched:  # a second result: pages fetched, slot by slot
        cnt_ref, *rest = rest
    m_scr, l_scr, acc_scr, *copy_scr = rest
    # own copies of a float pool's page rows (``_copies_page_rows``):
    # the pools arrive as (pages, page_size * n_kv, dh)
    page_rows = own_copies and len(k_refs[0].shape) == 3
    gi, kh, blk = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    h0 = _tile_first_head(block_h, ks_refs[0])
    span = pages * page_size  # key positions a block covers

    def walked(b_, blk_):
        """The block of the TABLE that step ``blk_`` of slot ``b_``'s
        walk covers: with a ``window`` the walk starts at the block that
        holds the window's first key (``_window_start``) and is only as
        long as a window can span."""
        if window is None:
            return blk_
        return _window_start(t_ref[b_], window) // span + blk_

    def live_pages(b_, blk_):
        """Of step ``blk_`` of slot ``b_``'s walk: its first page in the
        TABLE, and the pages ``[below, n_live)`` of it that hold a key
        the slot's query sees — none where the slot has no key left."""
        first = walked(b_, blk_) * pages
        below = 0
        if window is not None:  # the pages below the window's first
            below = jnp.clip(_window_start(t_ref[b_], window) // page_size
                             - first, 0, pages)
        n_live = jnp.clip(t_ref[b_] // page_size + 1 - first, 0, pages)
        return first, below, n_live

    # the tile's slots, each at its own position and its own table block
    slots = [gi * tile + g for g in range(tile)]
    t = [t_ref[b_] for b_ in slots]  # keys k_pos <= t live
    lblk = [walked(b_, blk) for b_ in slots]
    any_live = functools.reduce(jnp.logical_or, [
        lb * span <= tg for lb, tg in zip(lblk, t)])

    if count_fetched:
        @pl.when((gi == 0) & (kh == 0) & (blk == 0))
        def _zero():
            def zero(i, carry):
                cnt_ref[i] = 0
                return carry

            jax.lax.fori_loop(0, cnt_ref.shape[0], zero, 0)

    if own_copies:
        k_buf, v_buf, sems, slot_scr = copy_scr
        n_groups, n_tiles = pl.num_programs(0), pl.num_programs(1)

        def block_copies(gi_, kh_, blk_, slot, act: str):
            """``start`` (or ``wait`` for) the copies of a tile-step's
            LIVE pages into buffer ``slot``: for every slot of the tile,
            K and V of each page of its walk's step up to its last live
            one, ONE DMA a page straight out of the pool by the slot's
            own table row — none for a slot that has no key left there.
            A page of a float pool is its (page_size * n_kv, dh) rows,
            which the buffer holds as they lie; an int8 pool's is the
            (page_size, block_h, dh) block of the head tile."""
            def slot_pages(g, carry):
                b_ = gi_ * tile + g
                first, below, n_live = live_pages(b_, blk_)
                if count_fetched and act == "start":  # counted where copied
                    cnt_ref[b_] += jnp.maximum(n_live - below, 0)

                def page(j, carry):
                    entry = tab_ref[b_, first + j]
                    for pool, buf, sem in (
                            (k_refs[0], k_buf, sems.at[slot, 0]),
                            (v_refs[0], v_buf, sems.at[slot, 1])):
                        src = pool.at[entry] if page_rows else pool.at[
                            entry, :, pl.ds(kh_ * block_h, block_h), :]
                        getattr(pltpu.make_async_copy(
                            src, buf.at[slot, g, j], sem), act)()
                    return carry

                return jax.lax.fori_loop(below, n_live, page, carry)

            jax.lax.fori_loop(0, tile, slot_pages, 0)

        @pl.when((gi == 0) & (kh == 0) & (blk == 0))
        def _prime():  # nothing fetched the call's first block yet.
            # A partly live block's unfetched pages, and a slot's whole
            # block where it has no key left, meet probabilities of
            # exactly 0: what the buffers hold there must be finite
            k_buf[...] = jnp.zeros_like(k_buf)
            v_buf[...] = jnp.zeros_like(v_buf)
            slot_scr[0] = 0
            block_copies(gi, kh, blk, 0, "start")

    @pl.when(blk == 0)
    def _init():  # fresh (slot tile, head tile) row: reset the state
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(any_live)
    def _partial():  # dead tile-steps: no compute, and nothing fetched
        # (own copies skip them; the pipeline's index maps collapse
        # their pages onto the scratch page and elide the fetch)
        n_q, dh = q_ref.shape[-2:]
        rows = page_size * block_h  # a page's (key, head) rows
        cols = pages * rows
        if own_copies:
            slot = slot_scr[0]
            # double buffering across grid steps: start the NEXT live
            # tile-step's copies (this row's next block where any slot
            # of the tile has a key left, else block 0 of the next row,
            # which is live for every slot), then wait for this one's
            more = functools.reduce(jnp.logical_or, [
                (lb + 1) * span <= tg for lb, tg in zip(lblk, t)])
            row_end = kh == n_tiles - 1
            nxt = (jnp.where(more | ~row_end, gi, gi + 1),
                   jnp.where(more, kh, jnp.where(row_end, 0, kh + 1)),
                   jnp.where(more, blk + 1, 0))

            @pl.when(nxt[0] < n_groups)
            def _prefetch():
                block_copies(*nxt, 1 - slot, "start")

            block_copies(gi, kh, blk, slot, "wait")
            slot_scr[0] = 1 - slot
            if page_rows:  # the tile's blocks as they lie
                k = k_buf[slot].reshape(tile, cols, dh)
                v = v_buf[slot].reshape(tile, cols, dh)
            else:
                k_pages = [k_buf.at[slot, 0, j] for j in range(pages)]
                v_pages = [v_buf.at[slot, 0, j] for j in range(pages)]
        else:
            k_pages = [ref.at[0] for ref in k_refs]
            v_pages = [ref.at[0] for ref in v_refs]
            if count_fetched:  # what the index maps did not collapse
                _, below, n_live = live_pages(slots[0], blk)
                cnt_ref[slots[0]] += jnp.maximum(n_live - below, 0)

        def block(page_refs, scale_refs):
            """One slot's block of (page_size, block_h, dh) pages as
            ONE (1, cols, dh) matrix: row = key x head in the pool's own
            order."""
            mats = []
            for ref, s_ref in zip(page_refs, scale_refs):
                page = ref[...]  # (page_size, block_h, dh)
                if quantized:  # dequant in registers, before the product
                    page = page.astype(jnp.float32) * _tile_scales(
                        s_ref, h0, block_h)[:, :, None]
                mats.append(page.reshape(rows, dh))
            return jnp.concatenate(mats, axis=0)[None]

        if not page_rows:
            k, v = block(k_pages, ks_refs), block(v_pages, vs_refs)
        # each slot's query rows against every (key, head) row of ITS
        # block, in one product batched over the tile's slots; the
        # columns of other kv heads are masked with the dead keys, so
        # their probabilities are exactly 0 and heads never mix
        s = jax.lax.dot_general(  # (tile, n_q, cols)
            q_ref[:, 0].astype(k.dtype), k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * sm_scale
        # a slot's position and table block, as (tile, 1, 1) vectors
        slot_of = jax.lax.broadcasted_iota(jnp.int32, (tile, 1, 1), 0)
        t_v = sum(jnp.where(slot_of == g, t[g], 0) for g in range(tile))
        lblk_v = sum(jnp.where(slot_of == g, lblk[g], 0) for g in range(tile))
        col = jax.lax.broadcasted_iota(jnp.int32, (1, 1, cols), 2)
        row = jax.lax.broadcasted_iota(jnp.int32, (1, n_q, 1), 1)
        # masks the dead pages of a partly live block, the last live
        # page's tail, a slot's whole block where it has no key left,
        # AND any speculative-overwrite rows above t
        k_pos = lblk_v * span + col // block_h
        seen = (k_pos <= t_v) & (col % block_h == row // rep)
        if window is not None:  # and the keys behind the window
            seen &= k_pos > t_v - window
        s = jnp.where(seen, s, NEG_INF)

        # a slot with no key left here changes nothing: its running max
        # is finite since block 0, so alpha is 1 and p is 0
        m_prev = m_scr[...]  # (tile, n_q, 1) running max
        m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, -1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)  # (tile, n_q, dh)
        m_scr[...] = m_new

    @pl.when(blk == n_blocks - 1)
    def _finish():  # position 0 is always live, so l > 0 on every row
        o_ref[:, 0] = (acc_scr[...] /
                       jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


#: most slots one grid step of the step kernel takes (a tile), their
#: products batched over the tile: the largest measured on a TPU v5e,
#: where it was the fastest or within 1% of it at every served call
TILE_SLOTS = 8
#: bytes a tile of slots may take in VMEM: each slot's K and V blocks,
#: double-buffered, and its products' float32 scores and probabilities,
#: inside the 16 MiB Mosaic gives a kernel
SLOT_TILE_BYTES = 12 << 20


def _slots_per_tile(b: int, block_bytes: int, n_q: int, cols: int) -> int:
    """Slots one grid step of the step kernel takes: the largest divisor
    of ``b``, at most ``TILE_SLOTS``, whose blocks (``block_bytes`` of K
    and of V a slot, double-buffered) and score tiles (``n_q`` x
    ``cols`` float32, twice) fit ``SLOT_TILE_BYTES`` — read off the
    call's shapes."""
    per_slot = 2 * 2 * block_bytes + 2 * n_q * cols * 4
    for d in range(min(b, TILE_SLOTS), 0, -1):
        if b % d == 0 and d * per_slot <= SLOT_TILE_BYTES:
            return d
    return 1


def _decode_grid(q, n_kv: int, page_size: int, n_tables: int, pool_dtype,
                 window: Optional[int], block_h: Optional[int]):
    """``(block_h, pages, tile, grid)`` of a step-kernel call: the block
    of pages a grid step takes (``_pages_per_block``), the slots it takes
    them for (``_slots_per_tile``, where the kernel copies its own pages
    out of a float pool; else 1 — an int8 pool's scale rows ride the
    BlockSpec pipeline a slot at a time), and the grid (slot tiles,
    kv-head tiles, blocks walked): the table's blocks, or with
    ``window`` as many as a window can span."""
    b, n_heads, dh = q.shape
    rep = gqa_repeat_factor(n_heads, n_kv)
    block_h = _resolve_block_h(block_h, n_kv)
    itemsize = jnp.dtype(pool_dtype).itemsize
    # a page of one pool in VMEM; an int8 page is priced at the bf16
    # it would be: its f32 dequantised copy is the larger tenant
    page_bytes = page_size * block_h * dh * max(itemsize, 2)
    pages = _pages_per_block(page_size, n_tables, page_bytes)
    n_blocks = -(-n_tables // pages)
    if window is not None:
        if window < 1:
            raise ValueError(f"window={window} must be >= 1")
        n_blocks = _window_blocks(window, pages * page_size, n_blocks)
    tile = 1
    if _copies_page_rows(dh, n_kv, block_h, pool_dtype):
        tile = _slots_per_tile(b, pages * page_bytes, block_h * rep,
                               pages * page_size * block_h)
    return block_h, pages, tile, (b // tile, n_kv // block_h, n_blocks)


def paged_decode_grid_steps(q, n_kv: int, page_size: int, n_tables: int,
                            window: Optional[int] = None) -> int:
    """Grid steps ``paged_decode_attention`` takes for queries ``q``
    (b, n_heads, dh) over a pool of ``q``'s dtype, ``n_kv`` heads in
    pages of ``page_size``, and a table ``n_tables`` wide: a Python int
    at trace time, what a caller counts its step work by."""
    return math.prod(_decode_grid(q, n_kv, page_size, n_tables, q.dtype,
                                  window, None)[3])


def paged_decode_live_blocks(q, n_kv: int, page_size: int, n_tables: int,
                             positions, window: Optional[int] = None):
    """(b,) int32: the blocks of its walk in which slot i of that call
    (as :func:`paged_decode_grid_steps` has it) has a key it sees —
    ``positions[i] // span + 1``, or with ``window`` from the block of
    the window's first key."""
    pages = _decode_grid(q, n_kv, page_size, n_tables, q.dtype, window,
                         None)[1]
    span = pages * page_size
    t = jnp.clip(jnp.asarray(positions, jnp.int32), 0,
                 n_tables * page_size - 1)
    lo = 0 if window is None else _window_start(t, window)
    return t // span - lo // span + 1


def paged_decode_attention(q, k_pool, v_pool, page_tables, positions,
                           sm_scale: float,
                           k_scale=None, v_scale=None,
                           block_h: Optional[int] = None,
                           interpret: Optional[bool] = None,
                           window: Optional[int] = None):
    """Single-token decode attention straight off a paged KV pool: a
    grid step takes a TILE of slots (``_slots_per_tile``) and a BLOCK of
    each one's pool pages (``BLOCK_KEYS`` key positions; a narrower table
    is one block) over every kv head of its tile, in one masked ``QK^T``
    and one ``PV`` product batched over the tile's slots.

    - ``q``: (b, n_heads, dh) — this step's query vector per slot.
    - ``k_pool``/``v_pool``: (n_pages, page_size, n_kv_heads, dh), the
      per-layer pool (f32/bf16, or int8 with ``k_scale``/``v_scale``
      absmax rows of shape (n_pages, page_size, n_kv_heads)).
    - ``page_tables``: (b, n_tables) int32 logical→pool page map. Dead
      entries (at or past a slot's live count) must point at a valid
      pool page — the serving engine keeps them at 0, the scratch page.
      The table may be narrower than ``max_len/page_size``: it only has
      to cover every slot's live pages (the engine passes its
      live-width slice).
    - ``positions``: (b,) int32 query positions; keys ``k_pos <=
      positions[i]`` are visible to slot i (the decode-branch mask).
      Held to ``[0, n_tables * page_size)``.

    - ``window``: slot i sees only the keys ``positions[i] - window <
      k_pos <= positions[i]``, and only the blocks that hold them are
      walked (the grid's block axis is as long as a window can span,
      from the block of the window's first key): pages behind the
      window are neither fetched nor read, whatever the table holds
      there. The kernel is then ``window_attn_step`` in a profile.
      ``None`` is the kernel as it was, ``paged_attn_step``.
      With a window the call returns ``(out, keys)``: ``keys`` (b,)
      int32 the key positions whose K (and V) it FETCHED for each slot,
      counted inside the kernel — a page where its copy is started, or,
      on the pipeline, where the index maps hand a page of the table
      and not the scratch page — so that a caller can tell a kernel
      that reads the window from one that only masks to it.

    The block, the tile of slots and who fetches the pages follow from
    the shapes of the call (``_decode_grid``). Products run on the
    pool's own float type (int8 pools: dequantised to f32) with f32
    accumulation and f32 softmax state, a slot's arithmetic the same
    whatever its tile.

    Returns (b, n_heads, dh) in ``q``'s dtype. GQA queries are grouped
    per kv head internally (``jnp.repeat`` convention: q head h ↔ kv
    head ``h // rep``). ``block_h`` tiles kv heads per program
    (default: the whole kv axis — see ``_resolve_block_h``).
    """
    dh = q.shape[-1]
    if k_pool.shape[-1] != dh:
        raise ValueError(f"head_dim mismatch: q has {dh}, pool "
                         f"{k_pool.shape[-1]}")
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be passed together")
    block_h, _, tile, _ = _decode_grid(
        q, k_pool.shape[2], k_pool.shape[1], page_tables.shape[1],
        k_pool.dtype, window, block_h)
    if k_scale is not None and tile > 1:
        raise ValueError("k_scale and v_scale rows come with an int8 pool")
    return _paged_decode_call(
        q, k_pool, v_pool, page_tables, positions, k_scale, v_scale,
        sm_scale=float(sm_scale), block_h=block_h, window=window,
        interpret=_resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("sm_scale", "block_h",
                                             "window", "interpret"))
def _paged_decode_call(q, k_pool, v_pool, page_tables, positions, k_scale,
                       v_scale, *, sm_scale: float, block_h: int,
                       window: Optional[int], interpret: bool):
    """:func:`paged_decode_attention` once its arguments are checked. A
    jit of its own: a decoder's layers make the same call, and the
    kernel is traced and lowered once a program, not once a layer: each
    lowering costs ~0.1-0.2 s of host time, paid before the compile
    cache is asked, so a 28-layer step program paid it 28 times."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, n_heads, dh = q.shape
    n_pages, page_size, n_kv, _ = k_pool.shape
    rep = gqa_repeat_factor(n_heads, n_kv)
    n_tables = page_tables.shape[1]
    quantized = k_scale is not None
    _, pages, tile, grid = _decode_grid(
        q, n_kv, page_size, n_tables, k_pool.dtype, window, block_h)
    n_tiles, n_q = n_kv // block_h, block_h * rep
    own_copies = _copies_own_pages(dh)
    page_rows = _copies_page_rows(dh, n_kv, block_h, k_pool.dtype)
    # GQA query rows grouped per kv head tile: q head h <-> kv head
    # h // rep, so a tile's rows are contiguous
    qh = q.reshape(b, n_tiles, n_q, dh)
    # position 0 is always live and no position lies past the table:
    # held here, so that the kernel's own copies always pair a start
    # with a wait whatever the caller passes
    t = jnp.clip(jnp.asarray(positions, jnp.int32), 0,
                 n_tables * page_size - 1)
    tabs = jnp.asarray(page_tables, jnp.int32)

    def q_map(gi, kh, blk, t_ref, tab_ref):
        return (gi, kh, 0, 0)

    def page_specs(block_shape):
        """The BlockSpec pipeline's fetch of a block (a tile of one
        slot): pages of a slot are not contiguous in the pool, so the
        pool is handed to the call ``pages`` times, every copy with an
        index map of its own that walks the block table, and a block's
        pages stream side by side. Live pages come from the table; dead
        ones (past the slot's last live page, or past the table in a
        ragged last block) collapse onto pool page 0, so consecutive
        dead steps re-use one fetch instead of streaming garbage. K/V
        blocks (4-D) take their head tile, scale blocks the WHOLE kv
        axis (``_tile_first_head``)."""
        def spec(j):
            def index(bi, kh, blk, t_ref, tab_ref):
                pg = blk * pages + j
                if window is None:
                    live = pg <= t_ref[bi] // page_size
                else:  # from the block of the window's first key
                    lo = _window_start(t_ref[bi], window)
                    pg += lo // (pages * page_size) * pages
                    live = (pg <= t_ref[bi] // page_size) & (
                        pg >= lo // page_size)
                page = jnp.where(
                    live, tab_ref[bi, jnp.minimum(pg, n_tables - 1)], 0)
                return ((page, 0, kh, 0) if len(block_shape) == 4
                        else (page, 0, 0))
            return pl.BlockSpec(block_shape, index)
        return [spec(j) for j in range(pages)]

    scratch_shapes = [
        pltpu.VMEM((tile, n_q, 1), jnp.float32),   # running max
        pltpu.VMEM((tile, n_q, 1), jnp.float32),   # running sum
        pltpu.VMEM((tile, n_q, dh), jnp.float32),  # weighted V
    ]
    in_specs = [pl.BlockSpec((tile, 1, n_q, dh), q_map)]
    operands = [qh]
    if own_copies:
        in_specs += [pl.BlockSpec(memory_space=pl.ANY)] * 2
        page_shape = (page_size, block_h, dh)
        if page_rows:  # a free view: the rows of a page lie in order
            page_shape = (page_size * n_kv, dh)
            operands += [k_pool.reshape((n_pages,) + page_shape),
                         v_pool.reshape((n_pages,) + page_shape)]
        else:
            operands += [k_pool, v_pool]
        scratch_shapes += [
            # a block of K and of V pages a slot of the tile,
            # double-buffered
            pltpu.VMEM((2, tile, pages) + page_shape, k_pool.dtype),
            pltpu.VMEM((2, tile, pages) + page_shape, v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),  # (buffer, K or V)
            pltpu.SMEM((1,), jnp.int32),      # the buffer in use
        ]
    else:
        in_specs += 2 * page_specs((1, page_size, block_h, dh))
        operands += [k_pool] * pages + [v_pool] * pages
    if quantized:
        in_specs += 2 * page_specs((1, page_size, n_kv))
        operands += [k_scale] * pages + [v_scale] * pages

    out_specs = pl.BlockSpec((tile, 1, n_q, dh), q_map)
    out_shape = jax.ShapeDtypeStruct((b, n_tiles, n_q, dh), q.dtype)
    if window is not None:  # a counter a slot, in SMEM for the call
        out_specs = [out_specs, pl.BlockSpec(memory_space=pltpu.SMEM)]
        out_shape = [out_shape, jax.ShapeDtypeStruct((b,), jnp.int32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch_shapes,
    )
    kernel = functools.partial(
        _paged_decode_kernel, sm_scale=sm_scale,
        page_size=page_size, block_h=block_h, rep=rep, pages=pages,
        n_blocks=grid[2], tile=tile, quantized=quantized,
        own_copies=own_copies, window=window)
    call = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
        # what a profile calls the kernel
        name="paged_attn_step" if window is None else "window_attn_step",
    )
    if interpret and jax.device_count() > 1:
        out = _partitioner_shield(call, t, tabs, *operands)
    else:
        out = call(t, tabs, *operands)
    if window is None:
        return out.reshape(b, n_heads, dh)
    out, fetched = out  # pages, every head tile's counted
    return out.reshape(b, n_heads, dh), fetched // n_tiles * page_size


def _paged_window_kernel(t_ref, tab_ref, q_ref, trow_ref, k_ref, v_ref,
                         *rest, sm_scale: float, page_size: int,
                         block_h: int, block_q: int,
                         n_tables: int, quantized: bool,
                         window: Optional[int] = None):
    from jax.experimental import pallas as pl

    if quantized:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        ks_ref = vs_ref = None
        o_ref, m_scr, l_scr, acc_scr = rest
    bi = pl.program_id(0)
    h0 = _tile_first_head(block_h, ks_ref)
    qt = pl.program_id(2)
    pg = step = pl.program_id(3)
    # positions are NONDECREASING along the window (the engine repeats
    # the last real entry into idle/overhang rows), so this tile's last
    # row bounds its live pages — the per-tile twin of the step
    # kernel's n_live. A SCALAR read off the prefetch ref, the same one
    # the index maps make: Mosaic loads nothing wider from SMEM ("Can
    # only load scalars from SMEM"), so the tile's per-row positions
    # arrive as the blocked VMEM operand ``trow_ref`` instead
    n_live = t_ref[bi, qt * block_q + block_q - 1] // page_size + 1
    if window is not None:  # the page axis starts at the page of the
        # first key the tile's FIRST row sees, and ends with the grid
        pg += _window_start(t_ref[bi, qt * block_q], window) // page_size

    @pl.when(step == 0)
    def _init():  # fresh (batch, head-tile, query-tile) row
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(pg < n_live)
    def _partial():  # dead pages: no compute, fetch collapsed onto the
        # scratch page by the index map
        k_pos = pg * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, page_size), 1)  # (1, page_size)
        # per-ROW causal horizon: query row r is window token r // rep
        # and sees keys k_pos <= its own absolute position — inside the
        # window, earlier tokens do NOT see later tokens' keys
        mask = k_pos <= trow_ref[0, 0]  # (block_q*rep, page_size)
        if window is not None:  # and not the keys behind its window
            mask &= k_pos > trow_ref[0, 0] - window
        for hh in range(block_h):  # static unroll over the head tile
            # operands widened in registers: the MXU's one default pass
            # takes them at bf16 all the same — on a bf16 pool bit for
            # bit what explicit bf16 operands give on the chip, 8-25%
            # faster (Mosaic repacks a head's bf16 rows out of the page
            # block). The scale meets the f32 scores, not the queries
            # before that pass rounds them
            q = q_ref[0, hh, 0].astype(jnp.float32)  # (bq*rep, dh)
            k = k_ref[0, :, hh, :].astype(jnp.float32)  # (page_size, dh)
            v = v_ref[0, :, hh, :].astype(jnp.float32)
            if quantized:  # dequant in registers, fused into the math
                k = k * _head_scale(ks_ref, h0 + hh)
                v = v * _head_scale(vs_ref, h0 + hh)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale  # (bq*rep, psz)
            s = jnp.where(mask, s, NEG_INF)

            m_prev = m_scr[hh]  # (bq*rep, 1) running max
            m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_scr[hh] = l_scr[hh] * alpha + jnp.sum(p, -1, keepdims=True)
            acc_scr[hh] = acc_scr[hh] * alpha + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)  # (bq*rep, dh)
            m_scr[hh] = m_new

    @pl.when(step == n_tables - 1)
    def _finish():  # a row's own position is live, so l > 0 on every row
        o_ref[0, :, 0] = (acc_scr[...] / jnp.maximum(
            l_scr[...], 1e-30)).astype(o_ref.dtype)


#: window tokens one grid step of the query-window kernel takes at most:
#: a prefill chunk's worth, so that a row of the call is one query tile
TILE_QUERIES = 64
#: bytes a query tile's state may take in VMEM (``_tile_bytes``): half
#: of the 16 MiB Mosaic gives a kernel, the rest for the K / V pages and
#: the products' temporaries
TILE_BYTES = 8 << 20


def _tile_bytes(block_q: int, rep: int, block_h: int, dh: int,
                itemsize: int) -> int:
    """VMEM a query tile of ``block_q`` window tokens holds across the
    page axis: per query row and kv head of the tile the f32 accumulator,
    the running max and sum (a lane tile each), the query and output
    blocks (double-buffered), and a lane tile a row of positions
    (double-buffered)."""
    rows = block_q * rep
    return (rows * block_h * (dh * 4 + 2 * 128 * 4 + 4 * dh * itemsize)
            + 2 * rows * 128 * 4)


def _default_block_q(s: int, rep: int, block_h: int, dh: int,
                     itemsize: int) -> int:
    """The window tile of a call: the largest divisor of the window
    ``s`` that is at most ``TILE_QUERIES`` and whose state fits
    ``TILE_BYTES``, read off the call's shapes. A tile of 16 tokens or
    fewer (speculative verify) always stands."""
    for d in range(min(s, TILE_QUERIES), 0, -1):
        if s % d == 0 and (d <= 16 or _tile_bytes(
                d, rep, block_h, dh, itemsize) <= TILE_BYTES):
            return d
    return 1


def _window_grid(q, n_kv: int, page_size: int, n_tables: int,
                 window: Optional[int], block_h: Optional[int],
                 block_q: Optional[int]):
    """``(block_h, block_q, grid)`` of a query-window call: the grid is
    (rows, kv-head tiles, query tiles, pages walked) — the table's
    pages, or with ``window`` as many as the keys ``(first - window,
    last]`` of a tile can span (last - first < ``block_q``)."""
    b, s, n_heads, dh = q.shape
    rep = gqa_repeat_factor(n_heads, n_kv)
    block_h = _resolve_block_h(block_h, n_kv)
    if block_q is None:
        block_q = _default_block_q(s, rep, block_h, dh, q.dtype.itemsize)
    if block_q < 1 or s % block_q:
        raise ValueError(f"block_q={block_q} must be >= 1 and divide "
                         f"the window length ({s})")
    n_walked = n_tables
    if window is not None:
        if window < 1:
            raise ValueError(f"window={window} must be >= 1")
        n_walked = _window_blocks(window + block_q - 1, page_size,
                                  n_tables)
    return block_h, block_q, (b, n_kv // block_h, s // block_q, n_walked)


def paged_window_grid_steps(q, n_kv: int, page_size: int, n_tables: int,
                            window: Optional[int] = None) -> int:
    """Grid steps ``paged_window_attention`` takes for queries ``q``
    (b, s, n_heads, dh) over a pool of ``n_kv`` heads in pages of
    ``page_size`` and a table ``n_tables`` wide: a Python int at trace
    time, what a caller counts its prefill work by."""
    return math.prod(_window_grid(
        q, n_kv, page_size, n_tables, window, None, None)[2])


def paged_window_attention(q, k_pool, v_pool, page_tables, positions,
                           sm_scale: float,
                           k_scale=None, v_scale=None,
                           block_h: Optional[int] = None,
                           block_q: Optional[int] = None,
                           interpret: Optional[bool] = None,
                           window: Optional[int] = None
                           ) -> jnp.ndarray:
    """Multi-token window attention straight off a paged KV pool.

    The (s >= 1) generalization of ``paged_decode_attention`` serving
    chunked prefill and speculative-verify windows:

    - ``q``: (b, s, n_heads, dh) — a window of s query vectors per slot.
    - ``k_pool``/``v_pool``/``k_scale``/``v_scale``: exactly as in
      ``paged_decode_attention`` (the window's own K/V rows are already
      written into the pool before the call — the decode branch writes
      the chunk first, then attends).
    - ``page_tables``: (b, n_tables) int32, dead entries on the scratch
      page, live-width slices welcome — identical contract to the step
      kernel.
    - ``positions``: (b, s) int32, the absolute position of every window
      token; row i of the window sees keys ``k_pos <= positions[b, i]``
      (causal INSIDE the window, not just at its end). Rows must be
      NONDECREASING: the engine's windows guarantee this (prefill pads
      overhang with the last entry, verify freezes inactive slots), and
      the kernel exploits it to bound live pages per query tile.

    - ``window``: row i sees only the keys ``positions[b, i] - window <
      k_pos <= positions[b, i]``, and a query tile walks only the pages
      from its first row's first key on, as many as a window and a tile
      can span — not the table. A tile's positions are then held to be
      consecutive or repeated (last - first < ``block_q``), as the
      engine's prefill rows are. The kernel is then
      ``window_attn_prefill`` in a profile; ``None`` is the kernel as it
      was, ``paged_attn_window``.

    Returns (b, s, n_heads, dh) in ``q``'s dtype. ``block_q`` tiles the
    window (must divide s; default: a prefill chunk's worth, the largest
    divisor <= ``TILE_QUERIES`` whose state fits VMEM — see
    ``_default_block_q``), ``block_h`` tiles kv heads as in the step
    kernel. Operands are widened to f32 in registers (int8 pools:
    dequantised there); scores, accumulation and softmax state are
    f32. With s == 1 this is the same
    attention as ``paged_decode_attention`` in another summation order
    (a page and a head at a time, where the step kernel takes a block of
    pages over every head of its tile): the two agree to f32 roundoff,
    which the property tests pin.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s, n_heads, dh = q.shape
    n_pages, page_size, n_kv, dh_k = k_pool.shape
    if dh_k != dh:
        raise ValueError(f"head_dim mismatch: q has {dh}, pool {dh_k}")
    rep = gqa_repeat_factor(n_heads, n_kv)
    n_tables = page_tables.shape[1]
    block_h, block_q, grid = _window_grid(
        q, n_kv, page_size, n_tables, window, block_h, block_q)
    quantized = k_scale is not None
    if quantized != (v_scale is not None):
        raise ValueError("k_scale and v_scale must be passed together")
    interpret = _resolve_interpret(interpret)

    t = jnp.asarray(positions, jnp.int32)
    if t.shape != (b, s):
        raise ValueError(f"positions must be (b, s)=({b}, {s}), got "
                         f"{t.shape}")
    # group GQA query rows per kv head, window-major inside the head
    # tile, one (block_q * rep, dh) slab per query tile: (b, n_kv, n_qt,
    # block_q * rep, dh) — rep rows of one token stay adjacent. Tiling
    # the window HERE keeps every block's last two dims equal to the
    # array's (legal at any block_q and rep) and leaves the kernel no
    # sublane-merging reshape to do
    n_qt, rows = s // block_q, block_q * rep
    qw = (q.reshape(b, s, n_kv, rep, dh).transpose(0, 2, 1, 3, 4)
          .reshape(b, n_kv, n_qt, rows, dh))
    # per-ROW causal horizon: query row r of a tile is window token
    # r // rep and sees keys k_pos <= its own absolute position
    t_rows = jnp.repeat(t, rep, axis=1).reshape(b, n_qt, rows, 1)
    tabs = jnp.asarray(page_tables, jnp.int32)

    def q_map(bi, kh, qt, pg, t_ref, tab_ref):
        return (bi, kh, qt, 0, 0)

    def trow_map(bi, kh, qt, pg, t_ref, tab_ref):
        return (bi, qt, 0, 0)

    def live_page(bi, qt, pg, t_ref, tab_ref):
        # the block-table walk, bounded per QUERY TILE: nondecreasing
        # positions make the tile's last row its page horizon, so dead
        # pages collapse onto the scratch page exactly as in the step
        # kernel
        if window is not None:  # from the page of the tile's first key
            pg = jnp.minimum(pg + _window_start(
                t_ref[bi, qt * block_q], window) // page_size,
                n_tables - 1)
        live = pg <= t_ref[bi, qt * block_q + block_q - 1] // page_size
        return jnp.where(live, tab_ref[bi, pg], 0)

    def kv_map(bi, kh, qt, pg, t_ref, tab_ref):
        return (live_page(bi, qt, pg, t_ref, tab_ref), 0, kh, 0)

    def sc_map(bi, kh, qt, pg, t_ref, tab_ref):
        return (live_page(bi, qt, pg, t_ref, tab_ref), 0, 0)

    in_specs = [
        pl.BlockSpec((1, block_h, 1, rows, dh), q_map),
        pl.BlockSpec((1, 1, rows, 1), trow_map),
        pl.BlockSpec((1, page_size, block_h, dh), kv_map),
        pl.BlockSpec((1, page_size, block_h, dh), kv_map),
    ]
    operands = [qw, t_rows, k_pool, v_pool]
    if quantized:
        in_specs += [pl.BlockSpec((1, page_size, n_kv), sc_map),
                     pl.BlockSpec((1, page_size, n_kv), sc_map)]
        operands += [k_scale, v_scale]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, block_h, 1, rows, dh), q_map),
        scratch_shapes=[
            pltpu.VMEM((block_h, rows, 1), jnp.float32),
            pltpu.VMEM((block_h, rows, 1), jnp.float32),
            pltpu.VMEM((block_h, rows, dh), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _paged_window_kernel, sm_scale=float(sm_scale),
        page_size=page_size, block_h=block_h, block_q=block_q,
        n_tables=grid[3], quantized=quantized, window=window)
    call = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, n_kv, n_qt, rows, dh), q.dtype),
        interpret=interpret,
        # what a profile calls the kernel
        name="paged_attn_window" if window is None
        else "window_attn_prefill",
    )
    if interpret and jax.device_count() > 1:
        out = _partitioner_shield(call, t, tabs, *operands)
    else:
        out = call(t, tabs, *operands)
    return (out.reshape(b, n_kv, s, rep, dh).transpose(0, 2, 1, 3, 4)
            .reshape(b, s, n_heads, dh))


def _paged_attention_reference(q, k_pool, v_pool, page_tables, positions,
                               sm_scale: float, k_scale=None,
                               v_scale=None, window: Optional[int] = None
                               ) -> jnp.ndarray:
    """Pure-XLA oracle: gather the pages back into logical order (the
    pre-kernel serving path) and run the masked softmax in f32. The
    kernel-equivalence property tests compare against this."""
    b, n_heads, dh = q.shape
    _, page_size, n_kv, _ = k_pool.shape
    rep = gqa_repeat_factor(n_heads, n_kv)
    n_tables = page_tables.shape[1]
    length = n_tables * page_size

    def rows(pool):  # (b, length, n_kv, ...) logical view
        return pool[page_tables].reshape((b, length) + pool.shape[2:])

    k = rows(k_pool).astype(jnp.float32)
    v = rows(v_pool).astype(jnp.float32)
    if k_scale is not None:
        k = k * rows(k_scale)[..., None]
        v = v * rows(v_scale)[..., None]
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bhd,bkhd->bhk", q.astype(jnp.float32),
                   k) * sm_scale
    k_pos = jnp.arange(length)[None, None, :]
    t = jnp.asarray(positions)[:, None, None]
    seen = k_pos <= t
    if window is not None:
        seen &= k_pos > t - window
    s = jnp.where(seen, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhk,bkhd->bhd", p, v).astype(q.dtype)


def _paged_window_reference(q, k_pool, v_pool, page_tables, positions,
                            sm_scale: float, k_scale=None,
                            v_scale=None, window: Optional[int] = None
                            ) -> jnp.ndarray:
    """Pure-XLA window oracle: gather the pages back into logical order
    and run the per-row masked softmax in f32 — the same math the
    multi-token gather fallback in ``_DecoderAttention`` computes."""
    b, s, n_heads, dh = q.shape
    _, page_size, n_kv, _ = k_pool.shape
    rep = gqa_repeat_factor(n_heads, n_kv)
    n_tables = page_tables.shape[1]
    length = n_tables * page_size

    def rows(pool):  # (b, length, n_kv, ...) logical view
        return pool[page_tables].reshape((b, length) + pool.shape[2:])

    k = rows(k_pool).astype(jnp.float32)
    v = rows(v_pool).astype(jnp.float32)
    if k_scale is not None:
        k = k * rows(k_scale)[..., None]
        v = v * rows(v_scale)[..., None]
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k) * sm_scale
    k_pos = jnp.arange(length)[None, None, None, :]
    t = jnp.asarray(positions)[:, None, :, None]  # (b, 1, s, 1)
    seen = k_pos <= t
    if window is not None:
        seen &= k_pos > t - window
    scores = jnp.where(seen, scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v).astype(q.dtype)
