"""A decoder driven by a per-layer PATTERN over five kinds of layer —
``M`` a state-space (Mamba-2) mixer; ``*``, ``R`` and ``W`` attention
(without a position embedding; with a rotary table; with a rotary table
and a WINDOW); ``E`` routed experts, in a latent narrower than the model
or at its width — served by ``DecodeEngine`` through the call it makes
of every decoder (``ids, positions=, decode=True, page_tables=``,
mutable ``cache``) and the two operands it gives a module that declares
PER-SLOT STATE (``slot_state``): ``slot_ids`` (which slot each row of
the call belongs to) and ``row_tokens`` (how many of the row's tokens
are real; 0 = leave the slot's state alone). Every size is a field;
nothing here names a model.

A layer is ``x + f(norm(x))`` with ONE mixer or one feed-forward part
(RMSNorm, no bias on any linear):

- ``M``: ``[z | xBC | dt] = h W_in``; ``xBC = silu(conv(xBC))``, a
  depthwise causal convolution over the last ``conv_width`` positions,
  with bias; ``[x | B | C] = xBC``, ``x`` -> (heads, head dim), ``B``,
  ``C`` -> (groups, state); ``dt = softplus(dt + dt_bias)``, ``A =
  -exp(A_log)``; per head, in float32, ``S_t = exp(dt_t A) S_{t-1} + dt_t
  x_t B_t^T``, ``y_t = S_t C_t + D x_t`` (``ops/ssm.py``); ``y =
  norm_grouped(y * silu(z))``, the norm over each group's channels;
  ``y W_out``.
- ``*``: grouped-query causal attention, scale ``head_dim^-0.5``, NO
  rotary embedding; K / V through the paged pool and the two paged
  kernels of ``ops/paged_attention.py``.
- ``R``: the same with q and k turned by a rotary table first
  (half-split pairs; the table, ``rope_full``, is plain ``theta`` or
  YaRN's blend, its cos and sin times an attention factor).
- ``W``: the same with a table of its own (``rope_window``) and a
  WINDOW: the query at ``i`` sees the keys ``i - window < j <= i``. It
  keeps no pages: its K / V live in a ring of ``kv_ring`` positions a
  SLOT (``ops/window_attention.py``), read by the paged kernels'
  windowed forms, which fetch only what intersects the window.
- ``E``: router in float32 on ``h`` (``ops/moe.py`` ``ExpertShare``: the
  ``top_k`` largest of ``sigmoid + bias``, weighed by their sigmoids over
  their sum, times ``routed_scaling`` — or, ``sigmoid_scores`` off, of a
  softmax); ``u = h W_down`` into the latent (``latent_dim`` 0: ``u =
  h``); the routed experts HELD here on ``u`` (two kernels with
  ``relu(.)^2`` between, or ``experts_gated`` SwiGLU's three, by grouped
  products); ``r W_up`` back to the model's width; plus, where
  ``shared_dim``, the shared expert ``W2 relu(W1 h)^2`` at the model's
  width. ``W_up`` is linear, so the chips' ``r W_up`` add up to the
  whole; the shared expert, the router and both latent projections are
  on every chip alike.

**The cache** holds two kinds of leaves: the ``*`` and ``R`` layers'
paged pools (``k``, ``v``: (kv_pages, kv_page_size, kv heads, head dim),
indexed by page, page 0 scratch), and leaves indexed by SLOT, the last
row scratch — per ``M`` layer a recurrent state ``ssm`` (slots + 1,
heads, head dim, state) float32 and a convolution tail ``conv`` (slots +
1, conv_width - 1, channels), per ``W`` layer the rings ``ring_k``,
``ring_v`` (slots + 1, kv_ring, kv heads, head dim). A ring needs none
of the care below: a token that is not real writes to the scratch row,
and what a slot's last request left lies above the next one's positions
until it is overwritten. What makes a recurrence safe under an engine
that pads rows, keeps empty lanes stepping and deals one prompt's
consecutive chunks to the rows of one prefill call:

- a padded token has ``dt`` = 0, so it advances nothing, and writes its
  keys to the scratch page; a row with no real token reads and writes
  the scratch row;
- a row whose first position is 0 starts from a zero state and tail,
  whatever its slot held: a finished or preempted request leaves nothing
  behind for the next;
- a row of the same slot as the row before it CONTINUES that row: it
  starts from the state and tail that row ended with, and only a slot's
  last row of the call writes the slot's state.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from rafiki_tpu.models.latent_moe import yarn_inv_freq
from rafiki_tpu.models.llama_lora import (LoRADense, RMSNorm,
                                          _masked_decode_attention, rope)
from rafiki_tpu.ops.moe import (MOE_COUNTERS, ExpertShare,
                                book_moe_counters, sown_counters)
from rafiki_tpu.ops.paged_attention import (kv_cache_write,
                                            paged_decode_attention,
                                            paged_window_attention,
                                            paged_window_grid_steps,
                                            resolve_paged_kernel,
                                            resolve_paged_window_kernel)
from rafiki_tpu.ops.ssm import causal_conv, ssd_chunk_scan, ssm_state_step
from rafiki_tpu.ops.window_attention import (ring_write,
                                             window_ring_attention)

#: what the state-space layers count on the device, after the expert
#: layers' ``MOE_COUNTERS`` in the vector the engine carries: (row,
#: layer) pairs whose state a single-token call advanced, those a window
#: call advanced, and of the latter the rows that took their state from
#: the row before them
SSM_COUNTERS = ("ssm_step_rows", "ssm_prefill_rows", "ssm_rows_chained")


def book_ssm_counters(stats: Any, counts: Any) -> None:
    """Add one pulled :data:`SSM_COUNTERS` vector to a ``StatsMap`` —
    each name a literal, as ``book_moe_counters`` has it and why."""
    stats.inc("ssm_step_rows", int(counts[0]))
    stats.inc("ssm_prefill_rows", int(counts[1]))
    stats.inc("ssm_rows_chained", int(counts[2]))


#: what the rotary attention layers count on the device, after the
#: counters above in a pattern that has such layers. Over single-token
#: calls: keys live under the window of every (real row, ``W`` layer),
#: keys the ``W`` layers' step fetched for them (counted inside
#: ``window_attn_step``, a page where its copy starts; off the TPU the
#: ring the masked form is handed), and keys live under every (real row,
#: ``R`` layer). Over prefill calls: the grid steps of the layers'
#: query-window kernel calls (0 where the gather or the masked form
#: serves the window), and the call's rows x chunk tokens, once a call
WINDOW_COUNTERS = ("win_step_live_keys", "win_step_keys_fetched",
                   "full_step_live_keys", "attn_prefill_grid_steps",
                   "attn_prefill_tokens")


def book_window_counters(stats: Any, counts: Any) -> None:
    """Add one pulled :data:`WINDOW_COUNTERS` vector to a ``StatsMap`` —
    each name a literal, as ``book_moe_counters`` has it and why."""
    stats.inc("win_step_live_keys", int(counts[0]))
    stats.inc("win_step_keys_fetched", int(counts[1]))
    stats.inc("full_step_live_keys", int(counts[2]))
    stats.inc("attn_prefill_grid_steps", int(counts[3]))
    stats.inc("attn_prefill_tokens", int(counts[4]))


def _sow_window_counters(module: nn.Module, counts: Any) -> None:
    """Add ``counts``, in the order of :data:`WINDOW_COUNTERS`, to what
    this ``apply`` sows under ``"win"``."""
    module.sow("counters", "win",
               jnp.stack([jnp.asarray(c, jnp.int32) for c in counts]),
               init_fn=lambda: jnp.zeros((len(WINDOW_COUNTERS),), jnp.int32),
               reduce_fn=lambda u, w: u + w)


class Rows(NamedTuple):
    """What the engine says of a decode-path call's rows, worked out
    once for every layer."""

    slots: jnp.ndarray    # (R,) the slot of each row
    n_real: jnp.ndarray   # (R,) real tokens of the row
    real: jnp.ndarray     # (R, L) bool, per token
    fresh: jnp.ndarray    # (R,) bool: starts at position 0, from nothing
    chained: jnp.ndarray  # (R,) bool: continues the row before it
    last: jnp.ndarray     # (R,) bool: no row after it continues it


def _rows(slot_ids: jnp.ndarray, row_tokens: jnp.ndarray,
          positions: jnp.ndarray) -> Rows:
    n_real = row_tokens.astype(jnp.int32)
    has = n_real > 0
    slots = slot_ids.astype(jnp.int32)
    chained = jnp.concatenate([
        jnp.zeros((1,), bool),
        (slots[1:] == slots[:-1]) & has[1:] & has[:-1]])
    return Rows(slots, n_real,
                jnp.arange(positions.shape[1])[None, :] < n_real[:, None],
                has & (positions[:, 0] == 0), chained,
                jnp.concatenate([~chained[1:], jnp.ones((1,), bool)]))


class _ConvWeights(nn.Module):
    width: int
    channels: int

    @nn.compact
    def __call__(self) -> Tuple[jnp.ndarray, jnp.ndarray]:
        return (self.param("kernel", nn.initializers.lecun_normal(),
                           (self.width, self.channels)),
                self.param("bias", nn.initializers.zeros,
                           (self.channels,)))


class Mamba2Mixer(nn.Module):
    n_heads: int
    head_dim: int
    n_groups: int
    state_dim: int
    conv_width: int = 4
    chunk_size: int = 128
    eps: float = 1e-5

    @nn.compact
    def __call__(self, h: jnp.ndarray, decode: bool,
                 rows: Optional[Rows] = None) -> jnp.ndarray:
        b, s, d = h.shape
        nh, p, g, n = (self.n_heads, self.head_dim, self.n_groups,
                       self.state_dim)
        d_in, gn = nh * p, g * n
        channels, tail_len = d_in + 2 * gn, self.conv_width - 1
        zxd = LoRADense(d_in + channels + nh, 0, name="in_proj")(h)
        z, xbc, dt = (zxd[..., :d_in], zxd[..., d_in:d_in + channels],
                      zxd[..., d_in + channels:])
        conv_w, conv_b = _ConvWeights(self.conv_width, channels,
                                      name="conv1d")()
        a = -jnp.exp(self.param("A_log", nn.initializers.zeros, (nh,)
                                ).astype(jnp.float32))
        dt = jax.nn.softplus(dt.astype(jnp.float32) + self.param(
            "dt_bias", nn.initializers.zeros, (nh,)).astype(jnp.float32))
        skip = self.param("D", nn.initializers.ones, (nh,))

        live = decode and self.has_variable("cache", "ssm")
        if decode:  # a state and a tail a SLOT; the last row is scratch
            state = self.variable("cache", "ssm", jnp.zeros,
                                  (b + 1, nh, p, n), jnp.float32)
            tails = self.variable("cache", "conv", jnp.zeros,
                                  (b + 1, tail_len, channels), h.dtype)
        if live:
            if 1 < s < tail_len:
                raise ValueError(
                    f"a window of {s} tokens is shorter than the "
                    f"convolution's tail of {tail_len}: a row could not "
                    "hand its successor a whole tail")
            # a row with nothing to advance reads and writes the
            # scratch row; of a slot's rows only the last is stored
            scratch = state.value.shape[0] - 1
            has = rows.n_real > 0
            slots = jnp.where(has, rows.slots, scratch)
            writes = jnp.where(has & rows.last, rows.slots, scratch)
            tail = tails.value[slots]
            if s >= tail_len:  # a chained row: the row before it was full
                before = jnp.concatenate(
                    [tail[:1], xbc[:-1, s - tail_len:]], axis=0)
                tail = jnp.where(rows.chained[:, None, None], before, tail)
            tail = jnp.where(rows.fresh[:, None, None], 0, tail)
            n_real = rows.n_real
            dt = jnp.where(rows.real[..., None], dt, 0.0)
        else:  # no cache (or the init trace): whole sequences from nothing
            tail = jnp.zeros((b, tail_len, channels), h.dtype)
            n_real = jnp.full((b,), s, jnp.int32)
        # float32 from the convolution's sum through its activation into
        # the recurrence, which is float32 itself: no rounding between
        xbc, tail = causal_conv(xbc, tail, conv_w, conv_b, n_real)
        xbc = nn.silu(xbc)
        x = xbc[..., :d_in].reshape(b, s, nh, p)
        bm = xbc[..., d_in:d_in + gn].reshape(b, s, g, n)
        cm = xbc[..., d_in + gn:].reshape(b, s, g, n)

        if live and s == 1:
            y, state.value = ssm_state_step(
                state.value, rows.slots, has, rows.fresh,
                x[:, 0], dt[:, 0], a, bm[:, 0], cm[:, 0], skip)
            y = y[:, None]
            tails.value = tails.value.at[writes].set(tail)
            counts = [jnp.sum(has), 0, 0]
        elif live:
            init = jnp.where(rows.fresh[:, None, None, None], 0.0,
                             state.value[slots])
            y, final = ssd_chunk_scan(x, dt, a, bm, cm, skip, init,
                                      rows.chained)
            state.value = state.value.at[writes].set(final)
            tails.value = tails.value.at[writes].set(tail)
            counts = [0, jnp.sum(has), jnp.sum(rows.chained)]
        else:
            y = self._whole(x, dt, a, bm, cm, skip)
            counts = [0, 0, 0]
        self.sow("counters", "ssm",
                 jnp.stack([jnp.asarray(c, jnp.int32) for c in counts]),
                 init_fn=lambda: jnp.zeros((len(SSM_COUNTERS),), jnp.int32),
                 reduce_fn=lambda u, v: u + v)

        # gate, then the norm over each group's channels, one scale of d_in
        y = y.reshape(b, s, d_in) * nn.silu(z.astype(jnp.float32))
        yg = y.reshape(b, s, g, d_in // g)
        yg = yg * jax.lax.rsqrt(
            jnp.mean(yg * yg, axis=-1, keepdims=True) + self.eps)
        scale = self.param("norm_scale", nn.initializers.ones, (d_in,))
        y = (yg.reshape(b, s, d_in) * scale).astype(h.dtype)
        return LoRADense(d, 0, name="out_proj")(y)

    def _whole(self, x, dt, a, bm, cm, skip) -> jnp.ndarray:
        """Whole sequences from a zero state by the chunked scan: each is
        cut into chunks of ``chunk_size`` (padded with tokens that
        advance nothing), every chunk but a sequence's first chained."""
        b, s = x.shape[:2]
        c = min(self.chunk_size, s)
        pad = -s % c

        def chunks(v):
            v = jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2))
            return v.reshape((b * ((s + pad) // c), c) + v.shape[2:])

        x, dt, bm, cm = (chunks(v) for v in (x, dt, bm, cm))
        chained = jnp.arange(x.shape[0]) % ((s + pad) // c) != 0
        y, _ = ssd_chunk_scan(
            x, dt, a, bm, cm, skip,
            jnp.zeros((x.shape[0],) + x.shape[2:] + (bm.shape[-1],),
                      jnp.float32), chained)
        return y.reshape((b, s + pad) + y.shape[2:])[:, :s]


def rotary_table(dim: int, theta: float,
                 yarn: Optional[Tuple[float, int, float, float]]
                 ) -> np.ndarray:
    """The ``dim / 2`` frequencies of a layer kind's rotary embedding:
    ``theta^(-2i/dim)``, or with ``yarn`` = (factor, original max
    positions, beta fast, beta slow) YaRN's blend of those and the same
    over ``factor`` (``latent_moe.yarn_inv_freq``)."""
    if yarn is None:
        return (theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
                ).astype(np.float32)
    return yarn_inv_freq(dim, theta, *yarn)


class PatternAttention(nn.Module):
    """Grouped-query causal attention of the pattern's three kinds.
    ``rope`` None: no position embedding (``*``); else q and k are turned
    by the table of ``rope`` = (theta, YaRN's four numbers or None, a
    factor on cos and sin), half-split pairs (``R``, ``W``). ``window``
    0: every key at or before the query's, K / V in a paged pool (page 0
    scratch: where a token that is not real writes); else the last
    ``window`` keys, K / V in a ring of ``kv_ring`` positions a slot
    (``ops/window_attention.py``; the scratch row takes those tokens)."""

    n_heads: int
    n_kv_heads: int
    head_dim: int
    kv_page_size: int = 0
    kv_pages: int = 0
    paged_kernel: Optional[bool] = None
    rope: Optional[Tuple[float, Optional[Tuple], float]] = None
    window: int = 0
    kv_ring: int = 0
    max_len: int = 0

    @nn.compact
    def __call__(self, h: jnp.ndarray, positions: jnp.ndarray, decode: bool,
                 page_tables: Optional[jnp.ndarray] = None,
                 rows: Optional[Rows] = None) -> jnp.ndarray:
        b, s, d = h.shape
        nh, nkv, dh = self.n_heads, self.n_kv_heads, self.head_dim
        q = LoRADense(nh * dh, 0, name="wq")(h).reshape(b, s, nh, dh)
        k = LoRADense(nkv * dh, 0, name="wk")(h).reshape(b, s, nkv, dh)
        v = LoRADense(nkv * dh, 0, name="wv")(h).reshape(b, s, nkv, dh)
        if self.rope is not None:
            theta, yarn, scale = self.rope
            table = rotary_table(dh, theta, yarn)
            q = rope(q, positions, inv_freq=table, scale=scale)
            k = rope(k, positions, inv_freq=table, scale=scale)
        rep, sm = nh // nkv, dh ** -0.5
        ringed = self.window > 0
        leaves = ("ring_k", "ring_v") if ringed else ("k", "v")
        live = decode and self.has_variable("cache", leaves[0])
        if decode:
            if self.kv_page_size <= 0:
                raise ValueError("per-slot state is served beside a PAGED "
                                 "pool: kv_page_size must be > 0")
            shape = ((b + 1, self.kv_ring) if ringed else
                     (self.kv_pages, self.kv_page_size)) + (nkv, dh)
            ck = self.variable("cache", leaves[0], jnp.zeros, shape, h.dtype)
            cv = self.variable("cache", leaves[1], jnp.zeros, shape, h.dtype)
        t = positions
        counts = [0] * len(WINDOW_COUNTERS)
        if not live:  # no cache, or the init trace (allocates only)
            scores = jnp.einsum(
                "bqhd,bkhd->bhqk", q, jnp.repeat(k, rep, axis=2),
                preferred_element_type=jnp.float32) * sm
            seen = t[:, None, None, :] <= t[:, None, :, None]
            if ringed:
                seen &= t[:, None, None, :] > t[:, None, :, None] \
                    - self.window
            probs = jax.nn.softmax(jnp.where(seen, scores, -1e30), -1)
            o = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(h.dtype),
                           jnp.repeat(v, rep, axis=2))
        elif ringed:
            # a row with nothing to advance reads and writes the scratch
            # row, as a token that is not real writes there
            has = rows.n_real > 0
            slots = jnp.where(has, rows.slots, ck.value.shape[0] - 1)
            ck.value = ring_write(ck.value, slots, t, rows.real, k)
            cv.value = ring_write(cv.value, slots, t, rows.real, v)
            kernel = (resolve_paged_kernel(self.paged_kernel) if s == 1
                      else resolve_paged_window_kernel(self.paged_kernel))
            o, fetched = window_ring_attention(
                q, ck.value, cv.value, slots, t, self.window,
                self.kv_page_size, self.max_len, sm, kernel)
            if s == 1:
                counts[0] = jnp.sum(jnp.where(
                    has, jnp.minimum(t[:, 0] + 1, self.window), 0))
                counts[1] = jnp.sum(jnp.where(has, fetched, 0))
            elif kernel:
                counts[3] = paged_window_grid_steps(
                    q, nkv, self.kv_page_size,
                    -(-self.max_len // self.kv_page_size), self.window)
        else:
            if page_tables is None:
                raise ValueError("kv_page_size > 0 decode requires the "
                                 "page_tables operand (the serving engine "
                                 "supplies it)")
            page = jnp.take_along_axis(page_tables, t // self.kv_page_size,
                                       axis=1)
            # a token that is not real (padding, a lane with nothing to
            # advance) went through layers whose state it may not touch:
            # its keys differ from the real token's at that position, so
            # they go to the scratch page
            page = jnp.where(rows.real, page, 0)
            ck.value = kv_cache_write(ck.value, page, t % self.kv_page_size,
                                      k)
            cv.value = kv_cache_write(cv.value, page, t % self.kv_page_size,
                                      v)
            if resolve_paged_kernel(self.paged_kernel) and s == 1:
                o = paged_decode_attention(
                    q[:, 0], ck.value, cv.value, page_tables, t[:, 0],
                    sm_scale=sm)[:, None]
            elif resolve_paged_window_kernel(self.paged_kernel):
                o = paged_window_attention(
                    q, ck.value, cv.value, page_tables, t, sm_scale=sm)
                counts[3] = paged_window_grid_steps(
                    q, nkv, self.kv_page_size, page_tables.shape[1])
            else:
                def gathered(c):
                    return jnp.repeat(c[page_tables].reshape(
                        (b, page_tables.shape[1] * self.kv_page_size)
                        + c.shape[2:]), rep, axis=2)

                o = _masked_decode_attention(
                    q, gathered(ck.value), gathered(cv.value), t, dh,
                    h.dtype)
            if s == 1 and self.rope is not None:
                counts[2] = jnp.sum(jnp.where(rows.n_real > 0,
                                              t[:, 0] + 1, 0))
        if self.rope is not None:  # the kinds that WINDOW_COUNTERS count
            _sow_window_counters(self, counts)
        return LoRADense(d, 0, name="wo")(o.reshape(b, s, nh * dh))


def _relu2(x: jnp.ndarray) -> jnp.ndarray:
    return jnp.square(nn.relu(x))


class LatentExperts(nn.Module):
    """The ``E`` layer's feed-forward part: the share of the routed
    experts held here, in a latent of ``latent_dim`` (0: at the model's
    width), plus the shared expert at the model's width."""

    #: the fields of the layer's ``ExpertShare``, as (name, value) pairs
    expert_fields: Tuple[Tuple[str, Any], ...]
    latent_dim: int
    shared_dim: int

    @nn.compact
    def __call__(self, h: jnp.ndarray) -> jnp.ndarray:
        d = h.shape[-1]
        share = ExpertShare(**dict(self.expert_fields), name="moe")
        if self.latent_dim:
            u = LoRADense(self.latent_dim, 0, name="latent_down")(h)
            y = LoRADense(d, 0, name="latent_up")(share(u, route_on=h))
        else:
            y = share(h)
        if self.shared_dim:
            y = y + LoRADense(d, 0, name="shared_down")(_relu2(
                LoRADense(self.shared_dim, 0, name="shared_up")(h)))
        return y


class _Layer(nn.Module):
    """``x + mixer(norm(x))``, the mixer by the layer's ``kind``."""

    kind: str
    fields: Tuple[Tuple[str, Any], ...]
    eps: float

    @nn.compact
    def __call__(self, x, positions, decode, page_tables, rows):
        h = RMSNorm(self.eps, name="norm")(x)
        fields = dict(self.fields)
        if self.kind == "M":
            y = Mamba2Mixer(**fields, name="mixer")(h, decode, rows)
        elif self.kind in "*RW":
            y = PatternAttention(**fields, name="mixer")(
                h, positions, decode, page_tables, rows)
        else:
            y = LatentExperts(**fields, name="mixer")(h)
        return x + y


class HybridSSMMoEDecoder(nn.Module):
    """Decoder-only LM whose layer ``i`` is ``layer_pattern[i]``: ``M`` a
    :class:`Mamba2Mixer`; ``*``, ``R``, ``W`` a :class:`PatternAttention`
    (no rotary table; the table ``rope_full``; the table ``rope_window``
    and the ``window``, its keys in a ring of ``kv_ring`` positions a
    slot); ``E`` a :class:`LatentExperts`; untied head. ``experts_held =
    (first id, count)`` is this chip's share of each ``E`` layer's
    ``n_experts`` routed experts (count 0 = all); the router stays
    ``n_experts`` wide."""

    vocab_size: int
    max_len: int
    hidden_dim: int
    layer_pattern: str
    n_heads: int
    n_kv_heads: int
    head_dim: int
    ssm_heads: int
    ssm_head_dim: int
    ssm_groups: int
    ssm_state: int
    n_experts: int
    experts_per_token: int
    expert_dim: int
    latent_dim: int = 0
    shared_dim: int = 0
    experts_held: Tuple[int, int] = (0, 0)
    renormalize_gates: bool = True
    routed_scaling: float = 1.0
    #: the ``E`` layers' rule: SwiGLU experts of three kernels, or two
    #: with ``relu(.)^2`` between; the ``top_k`` largest of ``sigmoid +
    #: bias``, or of a softmax
    experts_gated: bool = False
    sigmoid_scores: bool = True
    #: the ``R`` and the ``W`` layers' rotary tables, each (theta,
    #: YaRN's (factor, original max positions, beta fast, beta slow) or
    #: None, the factor on cos and sin)
    rope_full: Tuple[float, Optional[Tuple], float] = (10000.0, None, 1.0)
    rope_window: Tuple[float, Optional[Tuple], float] = (10000.0, None, 1.0)
    #: keys a ``W`` layer's query sees, and the positions a slot's ring
    #: holds (``ops.window_attention.ring_positions``)
    window: int = 0
    kv_ring: int = 0
    conv_width: int = 4
    chunk_size: int = 128
    eps: float = 1e-5
    #: compute dtype of activations and matmuls; None = f32
    dtype: Any = None
    kv_page_size: int = 0
    kv_pages: int = 0
    paged_kernel: Optional[bool] = None

    #: the cache leaves that are indexed by SLOT (one row a slot and a
    #: scratch row), not by position: a module that names any is handed
    #: ``slot_ids`` and ``row_tokens`` in every decode-path call
    slot_state = ("ssm", "conv", "ring_k", "ring_v")
    #: of those, the ``W`` layers' rings: keys and values, not a state
    #: (the engine's ``window_kv_bytes_per_slot`` gauge)
    window_state = ("ring_k", "ring_v")

    @property
    def _rotary(self) -> bool:
        return any(kind in "RW" for kind in self.layer_pattern)

    @property
    def device_counters(self) -> Tuple[str, ...]:
        """int32 counts the step and prefill programs hand back beside
        their outputs (the ``"counters"`` collection, summed over
        layers); :data:`WINDOW_COUNTERS` where the pattern has a layer
        that counts them."""
        return MOE_COUNTERS + SSM_COUNTERS + (
            WINDOW_COUNTERS if self._rotary else ())

    def fold_device_counters(self, sown: Any) -> jnp.ndarray:
        """One ``apply``'s ``"counters"`` collection as one vector in
        the order of ``device_counters``."""
        return jnp.concatenate([
            sown_counters(sown, "moe", len(MOE_COUNTERS)),
            sown_counters(sown, "ssm", len(SSM_COUNTERS))] + (
            [sown_counters(sown, "win", len(WINDOW_COUNTERS))]
            if self._rotary else []))

    def book_device_counters(self, stats: Any, counts: Any) -> None:
        n_moe, n_ssm = len(MOE_COUNTERS), len(SSM_COUNTERS)
        book_moe_counters(stats, counts[:n_moe])
        book_ssm_counters(stats, counts[n_moe:n_moe + n_ssm])
        if self._rotary:
            book_window_counters(stats, counts[n_moe + n_ssm:])

    def ring_holds_call(self, call_tokens: int) -> None:
        """Raise unless a slot's ring takes the ``call_tokens`` one call
        may write for the slot beside the window the call's first row
        still reads (``DecodeEngine`` asks at construction)."""
        if "W" in self.layer_pattern \
                and self.kv_ring < self.window + call_tokens:
            raise ValueError(
                f"kv_ring {self.kv_ring} is shorter than the window "
                f"{self.window} plus the {call_tokens} tokens one call "
                "may write for a slot: a later row's keys would land on "
                "keys an earlier row still reads")

    def layer_fields(self, kind: str) -> Tuple[Tuple[str, Any], ...]:
        """The fields a layer of ``kind`` builds its mixer from, as
        (name, value) pairs: hashable, as a module's fields have to be."""
        if kind == "M":
            fields = dict(
                n_heads=self.ssm_heads, head_dim=self.ssm_head_dim,
                n_groups=self.ssm_groups, state_dim=self.ssm_state,
                conv_width=self.conv_width, chunk_size=self.chunk_size,
                eps=self.eps)
        elif kind in "*RW":
            fields = dict(
                n_heads=self.n_heads, n_kv_heads=self.n_kv_heads,
                head_dim=self.head_dim, kv_page_size=self.kv_page_size,
                kv_pages=self.kv_pages, paged_kernel=self.paged_kernel)
            if kind == "R":
                fields.update(rope=tuple(self.rope_full))
            elif kind == "W":
                if self.window < 1 or (self.kv_page_size > 0 and (
                        self.kv_ring < self.window
                        or self.kv_ring % self.kv_page_size)):
                    raise ValueError(
                        f"a 'W' layer needs window >= 1 ({self.window}) "
                        f"and a ring ({self.kv_ring}) of whole pages "
                        "that holds it")
                fields.update(rope=tuple(self.rope_window),
                              window=self.window, kv_ring=self.kv_ring,
                              max_len=self.max_len)
        elif kind == "E":
            fields = dict(
                expert_fields=tuple(dict(
                    n_experts=self.n_experts, top_k=self.experts_per_token,
                    mlp_dim=self.expert_dim, held=tuple(self.experts_held),
                    renormalize=self.renormalize_gates,
                    scaling=self.routed_scaling, gated=self.experts_gated,
                    sigmoid_scores=self.sigmoid_scores).items()),
                latent_dim=self.latent_dim, shared_dim=self.shared_dim)
        else:
            raise ValueError(
                f"layer_pattern {self.layer_pattern!r}: a layer is 'M', "
                f"'E', '*', 'R' or 'W', not {kind!r}")
        return tuple(fields.items())

    @nn.compact
    def __call__(self, ids: jnp.ndarray,
                 positions: Optional[jnp.ndarray] = None,
                 decode: bool = False,
                 page_tables: Optional[jnp.ndarray] = None,
                 slot_ids: Optional[jnp.ndarray] = None,
                 row_tokens: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        b, s = ids.shape
        if self.kv_page_size > 0 and self.max_len % self.kv_page_size:
            raise ValueError(f"kv_page_size {self.kv_page_size} must "
                             f"divide max_len {self.max_len}")
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(s), (b, s))
        rows = None
        if decode:  # a caller that says nothing: a row a slot, all real
            rows = _rows(
                jnp.arange(b) if slot_ids is None else slot_ids,
                jnp.full((b,), s) if row_tokens is None else row_tokens,
                positions)
        if decode and s > 1 and self._rotary:  # a prefill call's tokens
            _sow_window_counters(
                self, [0] * (len(WINDOW_COUNTERS) - 1) + [b * s])
        x = nn.Embed(self.vocab_size, self.hidden_dim,
                     name="tok_embed")(ids)
        if self.dtype is not None:
            x = x.astype(self.dtype)
        for i, kind in enumerate(self.layer_pattern):
            x = _Layer(kind, self.layer_fields(kind), self.eps,
                       name=f"block_{i}")(x, positions, decode,
                                          page_tables, rows)
        x = RMSNorm(self.eps, name="final_norm")(x)
        return LoRADense(self.vocab_size, 0, name="lm_head")(x)
