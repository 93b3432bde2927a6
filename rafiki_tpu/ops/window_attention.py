"""Sliding-window attention over a PER-SLOT RING of keys and values.

A layer whose query at position ``i`` sees only the keys ``i - window <
j <= i`` has no use for a slot's older pages, so it keeps none: its
cache leaves are rings, ``(slots + 1, ring, kv heads, head dim)``
indexed by SLOT (the last row scratch), and position ``p`` of a slot
lives at ``p % ring``. Nothing is allocated, freed or zeroed: a slot's
next request overwrites what the last one left, and an entry it has
not overwritten yet stands for a position above its own or below 0,
which no mask lets through.

**How long a ring is** (:func:`ring_positions`): ``window`` keys, plus
the tokens ONE call may write for a slot before any of its rows attends
(the engine deals one prompt's consecutive chunks to the rows of one
prefill call, and every layer writes the whole call's rows first), so
that the last row's keys never land on a key the first row still needs
— rounded up to whole pages, because the kernels walk the ring as pages.

**What reads it.** On the TPU the two paged kernels of
``ops/paged_attention.py`` with their ``window`` argument, under names
of their own in a profile (``window_attn_step``,
``window_attn_prefill``): the ring is a pool of ``ring / page_size``
pages a slot — a free reshape — and logical page ``n`` of slot ``s`` is
pool page ``s * pages + n % pages`` (:func:`ring_table`), so the kernels
fetch the pages that intersect ``(pos - window, pos]`` and mask inside
the two edge pages. Off the TPU (and for windows where
``RAFIKI_PAGED_KERNEL_WINDOWS=0``) the same call gathers the slot's
ring and masks it in ``jax.numpy`` (:func:`_masked_ring_attention`).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from rafiki_tpu.ops.attention import NEG_INF
from rafiki_tpu.ops.common import gqa_repeat_factor
from rafiki_tpu.ops.paged_attention import (kv_cache_write,
                                            paged_decode_attention,
                                            paged_window_attention)


def ring_positions(window: int, call_tokens: int, page_size: int) -> int:
    """Positions a slot's ring holds: the window, what one call may write
    for the slot, and the page that rounding to pages costs at most."""
    return (-(-(window + call_tokens) // page_size) + 1) * page_size


def ring_write(ring: jnp.ndarray, slots: jnp.ndarray,
               positions: jnp.ndarray, real: jnp.ndarray,
               values: jnp.ndarray) -> jnp.ndarray:
    """``ring[slots[b], positions[b, i] % ring] = values[b, i]`` for the
    tokens that are ``real``; the others (padding, a lane with nothing
    to advance) write to the scratch row, the ring's last."""
    row = jnp.where(real, slots[:, None], ring.shape[0] - 1)
    return kv_cache_write(ring, row, positions % ring.shape[1], values)


def ring_table(slots: jnp.ndarray, n_tables: int, ring_pages: int
               ) -> jnp.ndarray:
    """The page table the paged kernels walk for rows of ``slots``, the
    rings seen as one pool of ``ring_pages`` pages a slot: logical page
    ``n`` is pool page ``slot * ring_pages + n % ring_pages``."""
    return (slots.astype(jnp.int32)[:, None] * ring_pages
            + jnp.arange(n_tables, dtype=jnp.int32)[None, :] % ring_pages)


def _masked_ring_attention(q, ring_k, ring_v, slots, positions,
                           window: int, sm_scale: float) -> jnp.ndarray:
    """The oracle and the fallback: each row's whole ring, every entry
    taken for the LATEST position at or below the query's that lives
    there, masked to the window. (b, s, n_heads, dh) in ``q``'s dtype."""
    ring = ring_k.shape[1]
    rep = gqa_repeat_factor(q.shape[2], ring_k.shape[2])
    t = positions[..., None]  # (b, s, 1)
    k_pos = t - (t - jnp.arange(ring)) % ring  # (b, s, ring)
    seen = (k_pos >= 0) & (k_pos > t - window)
    k = jnp.repeat(ring_k[slots], rep, axis=2)  # (b, ring, n_heads, dh)
    v = jnp.repeat(ring_v[slots], rep, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * sm_scale
    probs = jax.nn.softmax(
        jnp.where(seen[:, None], scores, NEG_INF), -1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(q.dtype), v)


def window_ring_attention(q: jnp.ndarray, ring_k: jnp.ndarray,
                          ring_v: jnp.ndarray, slots: jnp.ndarray,
                          positions: jnp.ndarray, window: int,
                          page_size: int, max_len: int, sm_scale: float,
                          kernel: bool,
                          interpret: Optional[bool] = None
                          ) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """Attention of ``q`` (b, s, n_heads, dh) at ``positions`` (b, s)
    over the rings of ``slots`` (b,), each query seeing the ``window``
    keys that end with its own (already written). ``kernel``: the paged
    kernels (s == 1 the step kernel, else the query-window kernel);
    otherwise the masked ``jax.numpy`` form. With it, for a single-token
    call, the key positions FETCHED for each row, (b,) int32: what the
    step kernel counted where it copies, or the ring the masked form is
    handed whole; ``None`` for a window of queries."""
    single = q.shape[1] == 1
    if not kernel:
        o = _masked_ring_attention(q, ring_k, ring_v, slots, positions,
                                   window, sm_scale)
        return o, (jnp.full(slots.shape, ring_k.shape[1], jnp.int32)
                   if single else None)
    n_rows, ring = ring_k.shape[:2]
    if ring % page_size:
        raise ValueError(f"a ring of {ring} positions is not whole pages "
                         f"of {page_size}")
    pages = ring // page_size

    def pool(r):
        return r.reshape((n_rows * pages, page_size) + r.shape[2:])

    table = ring_table(slots, -(-max_len // page_size), pages)
    if single:
        o, fetched = paged_decode_attention(
            q[:, 0], pool(ring_k), pool(ring_v), table, positions[:, 0],
            sm_scale=sm_scale, window=window, interpret=interpret)
        return o[:, None], fetched
    return paged_window_attention(
        q, pool(ring_k), pool(ring_v), table, positions,
        sm_scale=sm_scale, window=window, interpret=interpret), None
