"""A decoder driven by a per-layer PATTERN over three kinds of layer —
``M`` a state-space (Mamba-2) mixer, ``*`` attention, ``E`` routed
experts in a latent narrower than the model — served by ``DecodeEngine``
through the call it makes of every decoder (``ids, positions=,
decode=True, page_tables=``, mutable ``cache``) and the two operands it
gives a module that declares PER-SLOT STATE (``slot_state``):
``slot_ids`` (which slot each row of the call belongs to) and
``row_tokens`` (how many of the row's tokens are real; 0 = leave the
slot's state alone). Every size is a field; nothing here names a model.

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
- ``E``: router in float32 on ``h`` (``ops/moe.py`` ``ExpertShare``: the
  ``top_k`` largest of ``sigmoid + bias``, weighed by their sigmoids over
  their sum, times ``routed_scaling``); ``u = h W_down`` into the
  latent; the routed experts HELD here on ``u`` (two kernels with
  ``relu(.)^2`` between, by grouped products); ``r W_up`` back to the
  model's width; plus the shared expert ``W2 relu(W1 h)^2`` at the
  model's width. ``W_up`` is linear, so the chips' ``r W_up`` add up to
  the whole; the shared expert, the router and both latent projections
  are on every chip alike.

**The cache** holds two kinds of leaves: the attention layers' paged
pools (``k``, ``v``: (kv_pages, kv_page_size, kv heads, head dim),
indexed by page, page 0 scratch) and per ``M`` layer a recurrent state
``ssm`` (slots + 1, heads, head dim, state) float32 and a convolution
tail ``conv`` (slots + 1, conv_width - 1, channels), indexed by SLOT, the
last row scratch. What makes a recurrence safe under an engine that pads
rows, keeps empty lanes stepping and deals one prompt's consecutive
chunks to the rows of one prefill call:

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
from flax import linen as nn

from rafiki_tpu.models.llama_lora import (LoRADense, RMSNorm,
                                          _masked_decode_attention)
from rafiki_tpu.ops.moe import (MOE_COUNTERS, ExpertShare,
                                book_moe_counters, sown_counters)
from rafiki_tpu.ops.paged_attention import (kv_cache_write,
                                            paged_decode_attention,
                                            paged_window_attention,
                                            resolve_paged_kernel,
                                            resolve_paged_window_kernel)
from rafiki_tpu.ops.ssm import causal_conv, ssd_chunk_scan, ssm_state_step

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


class PlainAttention(nn.Module):
    """Grouped-query causal attention with NO position embedding; through
    the cache, K / V live in a paged pool (page 0 scratch: where a token
    that is not real writes)."""

    n_heads: int
    n_kv_heads: int
    head_dim: int
    kv_page_size: int = 0
    kv_pages: int = 0
    paged_kernel: Optional[bool] = None

    @nn.compact
    def __call__(self, h: jnp.ndarray, positions: jnp.ndarray, decode: bool,
                 page_tables: Optional[jnp.ndarray] = None,
                 rows: Optional[Rows] = None) -> jnp.ndarray:
        b, s, d = h.shape
        nh, nkv, dh = self.n_heads, self.n_kv_heads, self.head_dim
        q = LoRADense(nh * dh, 0, name="wq")(h).reshape(b, s, nh, dh)
        k = LoRADense(nkv * dh, 0, name="wk")(h).reshape(b, s, nkv, dh)
        v = LoRADense(nkv * dh, 0, name="wv")(h).reshape(b, s, nkv, dh)
        rep, sm = nh // nkv, dh ** -0.5
        live = decode and self.has_variable("cache", "k")
        if decode:
            if self.kv_page_size <= 0:
                raise ValueError("per-slot state is served beside a PAGED "
                                 "pool: kv_page_size must be > 0")
            shape = (self.kv_pages, self.kv_page_size, nkv, dh)
            ck = self.variable("cache", "k", jnp.zeros, shape, h.dtype)
            cv = self.variable("cache", "v", jnp.zeros, shape, h.dtype)
        if not live:  # no cache, or the init trace (allocates only)
            scores = jnp.einsum(
                "bqhd,bkhd->bhqk", q, jnp.repeat(k, rep, axis=2),
                preferred_element_type=jnp.float32) * sm
            seen = positions[:, None, None, :] <= positions[:, None, :, None]
            probs = jax.nn.softmax(jnp.where(seen, scores, -1e30), -1)
            o = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(h.dtype),
                           jnp.repeat(v, rep, axis=2))
        else:
            if page_tables is None:
                raise ValueError("kv_page_size > 0 decode requires the "
                                 "page_tables operand (the serving engine "
                                 "supplies it)")
            t = positions
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
                o = paged_window_attention(q, ck.value, cv.value,
                                           page_tables, t, sm_scale=sm)
            else:
                def gathered(c):
                    return jnp.repeat(c[page_tables].reshape(
                        (b, page_tables.shape[1] * self.kv_page_size)
                        + c.shape[2:]), rep, axis=2)

                o = _masked_decode_attention(
                    q, gathered(ck.value), gathered(cv.value), t, dh,
                    h.dtype)
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
        elif self.kind == "*":
            y = PlainAttention(**fields, name="mixer")(
                h, positions, decode, page_tables, rows)
        else:
            y = LatentExperts(**fields, name="mixer")(h)
        return x + y


class HybridSSMMoEDecoder(nn.Module):
    """Decoder-only LM whose layer ``i`` is ``layer_pattern[i]``: ``M`` a
    :class:`Mamba2Mixer`, ``*`` a :class:`PlainAttention`, ``E`` a
    :class:`LatentExperts`; untied head. ``experts_held = (first id,
    count)`` is this chip's share of each ``E`` layer's ``n_experts``
    routed experts (count 0 = all); the router stays ``n_experts``
    wide."""

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
    slot_state = ("ssm", "conv")
    #: int32 counts the step and prefill programs hand back beside their
    #: outputs (the ``"counters"`` collection, summed over layers)
    device_counters = MOE_COUNTERS + SSM_COUNTERS

    def fold_device_counters(self, sown: Any) -> jnp.ndarray:
        """One ``apply``'s ``"counters"`` collection as one vector in
        the order of ``device_counters``."""
        return jnp.concatenate([
            sown_counters(sown, "moe", len(MOE_COUNTERS)),
            sown_counters(sown, "ssm", len(SSM_COUNTERS))])

    def book_device_counters(self, stats: Any, counts: Any) -> None:
        book_moe_counters(stats, counts[:len(MOE_COUNTERS)])
        book_ssm_counters(stats, counts[len(MOE_COUNTERS):])

    def layer_fields(self, kind: str) -> Tuple[Tuple[str, Any], ...]:
        """The fields a layer of ``kind`` builds its mixer from, as
        (name, value) pairs: hashable, as a module's fields have to be."""
        if kind == "M":
            fields = dict(
                n_heads=self.ssm_heads, head_dim=self.ssm_head_dim,
                n_groups=self.ssm_groups, state_dim=self.ssm_state,
                conv_width=self.conv_width, chunk_size=self.chunk_size,
                eps=self.eps)
        elif kind == "*":
            fields = dict(
                n_heads=self.n_heads, n_kv_heads=self.n_kv_heads,
                head_dim=self.head_dim, kv_page_size=self.kv_page_size,
                kv_pages=self.kv_pages, paged_kernel=self.paged_kernel)
        elif kind == "E":
            fields = dict(
                expert_fields=tuple(dict(
                    n_experts=self.n_experts, top_k=self.experts_per_token,
                    mlp_dim=self.expert_dim, held=tuple(self.experts_held),
                    renormalize=self.renormalize_gates,
                    scaling=self.routed_scaling, gated=False,
                    sigmoid_scores=True).items()),
                latent_dim=self.latent_dim, shared_dim=self.shared_dim)
        else:
            raise ValueError(
                f"layer_pattern {self.layer_pattern!r}: a layer is 'M', "
                f"'E' or '*', not {kind!r}")
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
