"""A decoder whose attention is multi-head LATENT attention and whose
feed-forward part, in every layer, is a routed expert layer beside one
shared expert — served by ``DecodeEngine`` through the call it makes of
every decoder (``ids, positions=, decode=True, page_tables=``, mutable
``cache``). Every size is a field; nothing here names a model. What
runs where: on the TPU the single-token step attends on the Pallas
kernel ``latent_attn_step`` and windows on the page gather; the routed
experts' three grouped products run on the Pallas kernel
``moe_grouped_matmul`` (``ops/grouped_matmul.py``: a row tile sized to
the call's rows an expert, 64 at a decode step and a prefill call, and
weight tiles of megabytes; gate and up in one call, down in another);
off the TPU both are XLA's (the gather, ``jax.lax.ragged_dot``).

One layer, for ``x`` of ``(T, d)`` (RMSNorm, no biases):

1. ``h = norm(x)``; ``c_q = norm_q(h W_qa)``; ``q = c_q W_qb`` ->
   heads x ``[q_nope | q_rope]``.
2. ``[c_kv | k_r] = h W_kva``; ``c_kv = norm_kv(c_kv)``; ``k_r`` is ONE
   rotary key a token, shared by all heads.
3. Rotary on ``q_rope`` and ``k_r``: pairs ``(2i, 2i+1)``, YaRN
   frequencies (:func:`yarn_inv_freq`).
4. ``[k_nope | v]`` per head ``= c_kv W_kvb``; scores ``(q_nope.k_nope +
   q_rope.k_r) * s``, causal, softmax in f32, times ``v``, ``W_o``.
5. ``h = norm(x)``; the routed experts (``ops/moe.py`` ``ExpertShare``:
   top-k of a softmax over all the router's logits, gates renormalised,
   the sum over the experts HELD here, by grouped products) plus the
   shared expert's SwiGLU.

**Through the cache** a layer keeps ``c_kv`` and ``k_r`` — ``kv_rank +
rope_dim`` values a token — in two leaves: ``kv``, the latents
(``(kv_pages, kv_page_size, kv_rank)``), and ``k_rope``, the rotary keys
packed two positions a row (``(kv_pages, kv_page_size / 2, 2 *
rope_dim)``: both minor dimensions fill whole 128-lane tiles at the
published widths, which is what lets the step kernel copy pages out of
HBM itself); contiguous, ``(b, max_len, kv_rank)`` and ``(b, max_len,
rope_dim)``. It attends in latent space: ``q_lat = q_nope W_kvb[K]``,
scores ``q_lat . c_kv + q_rope . k_r``, output ``probs . c_kv`` through
``W_kvb[V]`` (``ops/latent_attention.py``). The single-token step walks
the block table in a Pallas kernel where :func:`latent_kernel_mode`
says so; windows (chunked prefill) gather the slot's pages and stay in
latent space too. Without a cache (``decode=False``) keys and values
are expanded as in step 4: the two must agree, and a test holds them to
it.
"""

from __future__ import annotations

import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from rafiki_tpu.models.llama_lora import LoRADense, RMSNorm
from rafiki_tpu.ops.latent_attention import (copies_own_pages,
                                             latent_decode_attention,
                                             latent_gather_attention,
                                             packed_key_rows,
                                             packed_key_write)
from rafiki_tpu.ops.moe import (MOE_COUNTERS, ExpertShare, KernelLeaf,
                                book_moe_counters, sown_counters)
from rafiki_tpu.ops.paged_attention import (kv_cache_write,
                                            resolve_paged_kernel)


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(dim: int, theta: float, factor: float,
                  original_max: int, beta_fast: float, beta_slow: float
                  ) -> np.ndarray:
    """YaRN's ``dim / 2`` rotary frequencies: ``theta^(-2i/dim)`` where a
    pair turns more than ``beta_fast`` times over the original context,
    the same divided by ``factor`` where it turns less than ``beta_slow``
    times, and a linear ramp between the two correction dims."""
    extra = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)

    def correction_dim(turns: float) -> float:
        return dim * math.log(original_max / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3),
                   0.0, 1.0)
    return ((extra / factor) * ramp + extra * (1.0 - ramp)).astype(
        np.float32)


def rope_interleaved(x: jnp.ndarray, positions: jnp.ndarray,
                     inv_freq: np.ndarray, scale: float = 1.0
                     ) -> jnp.ndarray:
    """Rotate the pairs ``(2i, 2i+1)`` of ``x``'s last dim by
    ``positions * inv_freq[i]``. ``x``: (b, s, ..., dim); positions
    (b, s). ``scale`` multiplies cos and sin (YaRN's attention factor)."""
    ang = positions.astype(jnp.float32)[..., None] * jnp.asarray(inv_freq)
    ang = ang.reshape(ang.shape[:2] + (1,) * (x.ndim - 3) + ang.shape[2:])
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (-1, 2))
    even, odd = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([even * cos - odd * sin, even * sin + odd * cos], -1)
    return out.reshape(x.shape).astype(x.dtype)


def position_query_scale(positions: jnp.ndarray, beta: float,
                         original_max: int) -> jnp.ndarray:
    """``1 + beta * ln(1 + floor(pos / original_max))``: 1 at every
    position below ``original_max``."""
    return 1.0 + beta * jnp.log1p(jnp.floor(
        positions.astype(jnp.float32) / original_max))


#: what the latent attention counts on the device over single-token
#: calls, after the expert layers' ``MOE_COUNTERS`` in the vector the
#: engine carries: pool pages live under the slots' positions, summed
#: over slots and layers, and pool pages those calls fetched
LATENT_COUNTERS = ("latent_step_live_pages", "latent_step_page_fetches")


def book_latent_counters(stats: Any, counts: Any) -> None:
    """Add one pulled :data:`LATENT_COUNTERS` vector to a ``StatsMap`` —
    each name a literal, as ``book_moe_counters`` has it and why."""
    stats.inc("latent_step_live_pages", int(counts[0]))
    stats.inc("latent_step_page_fetches", int(counts[1]))


def latent_kernel_mode(kv_page_size: int, paged_kernel: Optional[bool]
                       ) -> int:
    """What the latent attention's decode calls take — the engine's
    ``paged_kernel_mode`` gauge reads THIS, and so does the dispatch in
    :class:`LatentAttention`: 1 = the single-token step on the Pallas
    kernel, windows on the page gather; 0 = both on the gather (a
    contiguous cache, or off the TPU unless forced). There is no latent
    window kernel yet, so never 2."""
    return int(kv_page_size > 0 and resolve_paged_kernel(paged_kernel))


class LatentAttention(nn.Module):
    n_heads: int
    q_rank: int
    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    max_len: int
    eps: float = 1e-6
    rope_theta: float = 10000.0
    #: YaRN: (factor, original_max, beta_fast, beta_slow, mscale,
    #: mscale_all_dim); None = plain rotary frequencies
    yarn: Optional[Tuple[float, int, float, float, float, float]] = None
    #: queries times ``1 + beta ln(1 + floor(pos / original_max))``
    query_scale_beta: float = 0.0
    kv_page_size: int = 0
    kv_pages: int = 0
    paged_kernel: Optional[bool] = None

    def _rotary(self) -> Tuple[np.ndarray, float, float]:
        """(frequencies, cos/sin factor, softmax scale)."""
        s = (self.nope_dim + self.rope_dim) ** -0.5
        if self.yarn is None:
            inv = self.rope_theta ** (-np.arange(
                0, self.rope_dim, 2, dtype=np.float64) / self.rope_dim)
            return inv.astype(np.float32), 1.0, s
        factor, orig, fast, slow, mscale, mscale_all = self.yarn
        inv = yarn_inv_freq(self.rope_dim, self.rope_theta, factor,
                            int(orig), fast, slow)
        m = _yarn_mscale(factor, mscale_all)
        return inv, _yarn_mscale(factor, mscale) / m, s * m * m

    @nn.compact
    def __call__(self, x: jnp.ndarray, positions: jnp.ndarray,
                 decode: bool,
                 page_tables: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        b, s, d = x.shape
        nh, r, dn, dr, dv = (self.n_heads, self.kv_rank, self.nope_dim,
                             self.rope_dim, self.v_dim)
        inv_freq, rot_scale, sm_scale = self._rotary()

        def dense(features, name):
            return LoRADense(features, 0, name=name)

        c_q = RMSNorm(self.eps, name="q_norm")(
            dense(self.q_rank, "wq_a")(x))
        q = dense(nh * (dn + dr), "wq_b")(c_q).reshape(b, s, nh, dn + dr)
        kva = dense(r + dr, "wkv_a")(x)
        c_kv = RMSNorm(self.eps, name="kv_norm")(kva[..., :r])
        k_r = rope_interleaved(kva[..., r:], positions, inv_freq,
                               rot_scale)
        q_nope = q[..., :dn]
        q_rope = rope_interleaved(q[..., dn:], positions, inv_freq,
                                  rot_scale)
        if self.query_scale_beta and self.yarn is not None:
            qs = position_query_scale(positions, self.query_scale_beta,
                                      int(self.yarn[1]))
            qs = qs[..., None, None].astype(x.dtype)
            q_nope, q_rope = q_nope * qs, q_rope * qs
        # the up-projection, (r, heads, nope + v): its K part is absorbed
        # into the query and its V part into the output on the cache path
        w_kvb = KernelLeaf((r, nh * (dn + dv)),
                           nn.initializers.lecun_normal(),
                           name="wkv_b")().astype(x.dtype).reshape(
                               r, nh, dn + dv)

        def expanded() -> jnp.ndarray:
            # no cache: keys and values of this very call, expanded
            kv = jnp.einsum("bsr,rhe->bshe", c_kv, w_kvb)
            scores = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, kv[..., :dn],
                                 preferred_element_type=jnp.float32)
                      + jnp.einsum("bqhd,bkd->bhqk", q_rope, k_r,
                                   preferred_element_type=jnp.float32)
                      ) * sm_scale
            seen = positions[:, None, None, :] <= positions[:, None, :, None]
            probs = jax.nn.softmax(jnp.where(seen, scores, -1e30), -1)
            return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(x.dtype),
                              kv[..., dn:])

        live = decode and self.has_variable("cache", "kv")
        if decode:
            paged, page = self.kv_page_size > 0, self.kv_page_size
            if paged and page % 2:
                raise ValueError(f"kv_page_size {page} must be even: the "
                                 "rotary keys lie two positions a row")
            lead = (self.kv_pages, page) if paged else (b, self.max_len)
            lat = self.variable("cache", "kv", jnp.zeros, lead + (r,),
                                x.dtype)
            keys = self.variable(
                "cache", "k_rope", jnp.zeros,
                (self.kv_pages, page // 2, 2 * dr) if paged
                else lead + (dr,), x.dtype)
        if not live:  # no cache, or the init trace (allocates only)
            o = expanded()
        else:
            t = positions
            if paged:
                if page_tables is None:
                    raise ValueError(
                        "kv_page_size > 0 decode requires the "
                        "page_tables operand (the serving engine "
                        "supplies it)")
                widx = (jnp.take_along_axis(page_tables, t // page, axis=1),
                        t % page)
                keys.value = packed_key_write(keys.value, *widx, k_r)
            else:
                widx = (jnp.arange(b)[:, None], t)
                keys.value = kv_cache_write(keys.value, *widx, k_r)
            lat.value = kv_cache_write(lat.value, *widx, c_kv)
            # the softmax scale goes on in f32, BEFORE the one rounding
            # to the compute dtype: rounded itself it would tilt every
            # score the same way
            q_lat = (jnp.einsum("bshd,rhd->bshr", q_nope, w_kvb[..., :dn],
                                preferred_element_type=jnp.float32)
                     * sm_scale).astype(x.dtype)
            q_rope = (q_rope.astype(jnp.float32) * sm_scale).astype(x.dtype)
            step = paged and s == 1
            kernel = step and latent_kernel_mode(page, self.paged_kernel)
            if kernel:
                o_lat = latent_decode_attention(
                    q_lat[:, 0], q_rope[:, 0], lat.value, keys.value,
                    page_tables, t[:, 0])[:, None]
            else:
                rows, key_rows = lat.value, keys.value
                if paged:  # the live-width slice of the table only
                    rows = rows[page_tables].reshape(
                        b, page_tables.shape[1] * page, r)
                    key_rows = packed_key_rows(key_rows, page_tables)
                o_lat = latent_gather_attention(q_lat, q_rope, rows,
                                                key_rows, t)
            if step:
                # pages a single-token call had to read, and pages it
                # fetched: the live ones where the kernel copies its
                # own, every table entry where the pipeline or the
                # gather walks the table
                live_pages = jnp.sum(t // page + 1, dtype=jnp.int32)
                fetched = live_pages if kernel and copies_own_pages(
                    lat.value, keys.value) else jnp.int32(
                        b * page_tables.shape[1])
                self.sow("counters", "latent",
                         jnp.stack([live_pages, fetched]),
                         init_fn=lambda: jnp.zeros(
                             (len(LATENT_COUNTERS),), jnp.int32),
                         reduce_fn=lambda a, b: a + b)
            o = jnp.einsum("bshr,rhd->bshd", o_lat, w_kvb[..., dn:])
        return dense(d, "wo")(o.reshape(b, s, nh * dv))


class LatentMoEBlock(nn.Module):
    #: the fields of this layer's LatentAttention and ExpertShare, as
    #: (name, value) pairs: hashable, as a module's fields have to be
    attn_fields: Tuple[Tuple[str, Any], ...]
    expert_fields: Tuple[Tuple[str, Any], ...]
    shared_dim: int
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x, positions, decode, page_tables=None):
        x = x + LatentAttention(**dict(self.attn_fields), name="attn")(
            RMSNorm(self.eps, name="attn_norm")(x), positions, decode,
            page_tables)
        h = RMSNorm(self.eps, name="ffn_norm")(x)
        y = ExpertShare(**dict(self.expert_fields), name="moe")(h)
        if self.shared_dim:
            def dense(features, name):
                return LoRADense(features, 0, name=name)

            y = y + dense(x.shape[-1], "shared_down")(
                nn.silu(dense(self.shared_dim, "shared_gate")(h))
                * dense(self.shared_dim, "shared_up")(h))
        return x + y


class LatentMoEDecoder(nn.Module):
    """Decoder-only LM of :class:`LatentMoEBlock` layers (every layer
    alike), untied head. ``experts_held = (first id, count)`` is this
    chip's share of each layer's ``n_experts`` routed experts (count 0 =
    all); the router stays ``n_experts`` wide."""

    vocab_size: int
    max_len: int
    hidden_dim: int
    depth: int
    n_heads: int
    q_rank: int
    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    n_experts: int
    experts_per_token: int
    expert_dim: int
    shared_dim: int = 0
    experts_held: Tuple[int, int] = (0, 0)
    renormalize_gates: bool = True
    routed_scaling: float = 1.0
    eps: float = 1e-6
    rope_theta: float = 10000.0
    yarn: Optional[Tuple[float, int, float, float, float, float]] = None
    query_scale_beta: float = 0.0
    #: compute dtype of activations and matmuls; None = f32
    dtype: Any = None
    kv_page_size: int = 0
    kv_pages: int = 0
    paged_kernel: Optional[bool] = None

    #: int32 counts the step and prefill programs hand back beside their
    #: outputs (the ``"counters"`` collection, summed over layers)
    device_counters = MOE_COUNTERS + LATENT_COUNTERS

    def fold_device_counters(self, sown: Any) -> jnp.ndarray:
        """One ``apply``'s ``"counters"`` collection as one vector in
        the order of ``device_counters``."""
        return jnp.concatenate([
            sown_counters(sown, "moe", len(MOE_COUNTERS)),
            sown_counters(sown, "latent", len(LATENT_COUNTERS))])

    def book_device_counters(self, stats: Any, counts: Any) -> None:
        book_moe_counters(stats, counts[:len(MOE_COUNTERS)])
        book_latent_counters(stats, counts[len(MOE_COUNTERS):])

    def paged_kernel_mode(self) -> int:
        return latent_kernel_mode(self.kv_page_size, self.paged_kernel)

    def block_fields(self) -> Tuple[Tuple, Tuple]:
        """The (attention, experts) fields every layer is built from."""
        attn = tuple(dict(
            n_heads=self.n_heads, q_rank=self.q_rank, kv_rank=self.kv_rank,
            nope_dim=self.nope_dim, rope_dim=self.rope_dim,
            v_dim=self.v_dim, max_len=self.max_len, eps=self.eps,
            rope_theta=self.rope_theta, yarn=self.yarn,
            query_scale_beta=self.query_scale_beta,
            kv_page_size=self.kv_page_size, kv_pages=self.kv_pages,
            paged_kernel=self.paged_kernel).items())
        experts = tuple(dict(
            n_experts=self.n_experts, top_k=self.experts_per_token,
            mlp_dim=self.expert_dim, held=tuple(self.experts_held),
            renormalize=self.renormalize_gates,
            scaling=self.routed_scaling).items())
        return attn, experts

    @nn.compact
    def __call__(self, ids: jnp.ndarray,
                 positions: Optional[jnp.ndarray] = None,
                 decode: bool = False,
                 page_tables: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        b, s = ids.shape
        if self.kv_page_size > 0 and self.max_len % self.kv_page_size:
            raise ValueError(f"kv_page_size {self.kv_page_size} must "
                             f"divide max_len {self.max_len}")
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(s), (b, s))
        x = nn.Embed(self.vocab_size, self.hidden_dim,
                     name="tok_embed")(ids)
        if self.dtype is not None:
            x = x.astype(self.dtype)
        attn, experts = self.block_fields()
        for i in range(self.depth):
            x = LatentMoEBlock(attn, experts, self.shared_dim, self.eps,
                               name=f"block_{i}")(x, positions, decode,
                                                  page_tables)
        x = RMSNorm(self.eps, name="final_norm")(x)
        return LoRADense(self.vocab_size, 0, name="lm_head")(x)
