"""Llama-style decoder LM with LoRA fine-tuning — BASELINE.md config #5.

Parity target: benchmark config #5 ("Llama-3 8B LoRA fine-tune +
continuous-batch serving via Predictor"). TPU-first design notes:

- The decoder (RMSNorm → RoPE → GQA causal flash attention → SwiGLU) is a
  flax module whose training attention runs through the Pallas flash
  kernel with per-example ``kv_lens`` (packed ragged batches stay one
  static-shape tensor).
- **2-D (fsdp × tensor) sharding** via ``parallel.sharding``: attention
  and MLP projections are tensor-parallel over the mesh's ``model`` axis
  (wq/wk/wv/gate/up split on the output dim, wo/down on the input dim —
  the Megatron pairing, so XLA inserts exactly one all-reduce per block),
  everything large is additionally fsdp-sharded over ``data``. No
  hand-written collectives anywhere.
- **LoRA**: every projection carries frozen ``kernel`` plus trainable
  ``lora_a``/``lora_b``; freezing is an ``optax.masked`` transform (the
  idiomatic JAX equivalent of requires_grad=False), so the base stays
  untouched and checkpoints can ship adapters only.
- **Generation**: greedy decode over a flax ``cache`` collection carried
  through ``lax.scan`` — one compiled step regardless of output length.
  Prefill is per-token through the same step (correct and simple; chunked
  prefill is a serving-layer optimization).
- No pretrained weights exist in this zero-egress environment, so the
  "base" is random and LoRA+head training carries the learning signal;
  the architecture and sharding are what the 8B config exercises.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import linen as nn

from rafiki_tpu.constants import TaskType
from rafiki_tpu.data import batch_iterator, \
    load_text_classification_dataset
from rafiki_tpu.model import (BaseModel, CategoricalKnob, FixedKnob,
                              FloatKnob, GangSpec, IntegerKnob, KnobConfig,
                              Knobs, PolicyKnob, TrainContext,
                              same_tree_shapes, train_epoch)
from rafiki_tpu.models.bert import _TOKEN_RE, PAD_ID, HashTokenizer
from rafiki_tpu.ops.attention import flash_attention
from rafiki_tpu.ops.paged_attention import (kv_cache_write,
                                            paged_decode_attention,
                                            paged_window_attention,
                                            resolve_paged_kernel,
                                            resolve_paged_window_kernel)
from rafiki_tpu.parallel.sharding import (DATA_AXIS, MODEL_AXIS,
                                          batch_sharding, make_mesh,
                                          overlap_compiler_options,
                                          param_shardings)

BOS_ID = 1  # reuse bert's CLS slot as BOS

#: Megatron-style tensor-parallel rules: column-parallel projections split
#: the output dim, row-parallel ones the input dim → one all-reduce per
#: attention/MLP block. Keys match LoRADense instance names below.
#: "experts" shards stacked MoE expert weights on their EXPERT dim —
#: expert parallelism: each model-axis device owns E/mp experts and XLA
#: schedules the token all-to-all around them (ops/moe.py).
#: NOTE: first matching rule wins and "gate"/"up"/"down" are substrings
#: of the stacked expert names — "experts" must stay first.
TP_RULES = {"experts": 0,
            "wq": -1, "wk": -1, "wv": -1, "gate": -1, "up": -1,
            "wo": 0, "down": 0, "lm_head": -1, "tok_embed": -1}


def rope(x: jnp.ndarray, positions: jnp.ndarray,
         theta: float = 10000.0,
         scaling: Optional[Tuple[float, float, float, float]] = None,
         inv_freq: Optional[np.ndarray] = None, scale: float = 1.0
         ) -> jnp.ndarray:
    """Rotary embedding over (b, s, heads, head_dim) with (b, s)
    positions: the half-split pairs ``(i, i + head_dim / 2)``.

    ``inv_freq`` (head_dim / 2,) puts an explicit frequency table in
    ``theta``'s place (a layer kind's own: YaRN's blend, say), and
    ``scale`` multiplies cos and sin (YaRN's attention factor).

    ``scaling`` applies Llama-3.1-style frequency-dependent NTK
    scaling: ``(factor, low_freq_factor, high_freq_factor,
    original_max_position_embeddings)``. High-frequency components
    (wavelength ≪ the original context) keep their frequency, very
    low-frequency ones divide by ``factor``, and the band between
    interpolates smoothly — the published recipe for stretching a
    pretrained context window without retraining the short-range
    geometry."""
    half = x.shape[-1] // 2
    if inv_freq is not None:
        freqs = jnp.asarray(inv_freq, jnp.float32)
    else:
        freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if scaling is not None:
        factor, low_f, high_f, orig_len = scaling
        # ratio = original_context / wavelength (wavelength = 2π/freq)
        ratio = orig_len * freqs / (2.0 * np.pi)
        smooth = jnp.clip((ratio - low_f) / max(high_f - low_f, 1e-9),
                          0.0, 1.0)
        scaled = freqs / factor
        freqs = jnp.where(
            ratio < low_f, scaled,
            jnp.where(ratio > high_f, freqs,
                      (1.0 - smooth) * scaled + smooth * freqs))
    angles = positions[..., None].astype(jnp.float32) * freqs  # (b, s, half)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos],
        axis=-1).astype(x.dtype)


def _same_tokenizer(a: Any, b: Any) -> bool:
    """Do two tokenizers map ids to the same text? BPE tokenizers
    compare merge tables; otherwise same type + vocab (HashTokenizer
    is fully determined by its vocab size)."""
    if type(a) is not type(b):
        return False
    am, bm = getattr(a, "merges", None), getattr(b, "merges", None)
    if am is not None or bm is not None:
        return am == bm
    return a.vocab_size == b.vocab_size


def _parse_rope_scaling(value: Any
                        ) -> Optional[Tuple[float, float, float, float]]:
    """Knob value (JSON object string, dict, or "") → the static
    scaling tuple :func:`rope` consumes. HF config key names are
    accepted directly, with the published Llama-3.1 defaults for the
    optional band parameters."""
    if not value:
        return None
    if isinstance(value, str):
        import json as _json

        value = _json.loads(value)
    c = dict(value)
    kind = str(c.get("rope_type", c.get("type", "llama3"))).lower()
    if kind == "default":
        return None  # HF semantics: explicit 'default' = unscaled
    if kind != "llama3":
        # linear/dynamic/yarn use DIFFERENT position geometry;
        # applying the llama3 NTK-by-parts formula to them would be
        # silently wrong — refuse loudly instead
        raise ValueError(
            f"unsupported rope_scaling type {kind!r} (only 'llama3' "
            "frequency-dependent scaling is implemented)")
    if "factor" not in c:
        raise ValueError("rope_scaling requires a 'factor' key "
                         f"(got {sorted(c)})")
    return (float(c["factor"]),
            float(c.get("low_freq_factor", 1.0)),
            float(c.get("high_freq_factor", 4.0)),
            float(c.get("original_max_position_embeddings", 8192)))


class RMSNorm(nn.Module):
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        x32 = x.astype(jnp.float32)
        norm = x32 * jax.lax.rsqrt(
            jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.eps)
        return (norm * scale).astype(x.dtype)


class LoRADense(nn.Module):
    """Frozen base kernel + trainable low-rank adapter (classic LoRA).

    ``quantized=True`` swaps the f32 base kernel for an int8 tensor plus
    per-output-channel f32 scales (symmetric absmax — see
    :func:`quantize_llama_params`). Serving-only post-training
    quantization: persistent weight HBM drops 4x and the decode loop —
    HBM-bandwidth-bound at batch 1..slots — reads a quarter of the
    bytes per step. Most kernels are the frozen LoRA bases (their
    trained signal lives in the f32 adapters); the trained ``lm_head``
    kernel is quantized too, with per-channel error ≤ absmax/254 —
    standard W8 PTQ, logits-closeness covered by tests. The int8
    operand feeds the matmul directly (one convert, the most fusable
    form) and the channel scale applies to the OUTPUT, never
    materializing a dequantized kernel; adapters/norms/embeddings stay
    full precision.
    """

    features: int
    rank: int = 0
    alpha: float = 16.0
    quantized: bool = False
    #: >0 — multi-adapter serving (S-LoRA-style): ``lora_a``/``lora_b``
    #: carry a leading adapter axis and every batch row applies ITS OWN
    #: adapter, selected by the per-row ``adapter_ids`` operand. The
    #: base matmul runs once for the whole batch (that's the point:
    #: N fine-tunes share one base's HBM and one MXU pass); only the
    #: rank-r correction is per-row, as two batched einsums over
    #: gathered (B, d, r)/(B, r, f) adapter slices — tiny vs the base.
    n_adapters: int = 0

    @nn.compact
    def __call__(self, x: jnp.ndarray,
                 adapter_ids: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        d_in = x.shape[-1]
        if self.quantized:
            qk = self.param("qkernel", nn.initializers.zeros,
                            (d_in, self.features), jnp.int8)
            qs = self.param("qscale", nn.initializers.ones,
                            (self.features,))
            # scale on the small (…, features) output, not the kernel:
            # (x @ q) * s == x @ (q * s) with b·f elementwise work
            # instead of d_in·f, and the dot consumes a bare int8→dtype
            # convert (fuses; no dequantized kernel ever materializes)
            y = (x @ qk.astype(x.dtype)) * qs.astype(x.dtype)
        else:
            kernel = self.param("kernel", nn.initializers.lecun_normal(),
                                (d_in, self.features))
            # compute in x's dtype (params stay f32): a bf16 activation
            # must not promote the matmul to f32 (~3x cost on the MXU)
            y = x @ kernel.astype(x.dtype)
        if self.rank > 0:
            if self.n_adapters > 0:
                a = self.param("lora_a", nn.initializers.normal(0.02),
                               (self.n_adapters, d_in, self.rank))
                b = self.param("lora_b", nn.initializers.zeros,
                               (self.n_adapters, self.rank, self.features))
                if adapter_ids is None:  # init trace / unselected call
                    adapter_ids = jnp.zeros((x.shape[0],), jnp.int32)
                asel = jnp.take(a, adapter_ids, axis=0).astype(x.dtype)
                bsel = jnp.take(b, adapter_ids, axis=0).astype(x.dtype)
                y = y + jnp.einsum(
                    "bsr,brf->bsf",
                    jnp.einsum("bsd,bdr->bsr", x, asel), bsel) * (
                        self.alpha / self.rank)
            else:
                a = self.param("lora_a", nn.initializers.normal(0.02),
                               (d_in, self.rank))
                b = self.param("lora_b", nn.initializers.zeros,
                               (self.rank, self.features))
                y = y + ((x @ a.astype(x.dtype)) @ b.astype(x.dtype)) * (
                    self.alpha / self.rank)
        return y


def _masked_decode_attention(q, kk, vv, t, dh: int, dtype) -> jnp.ndarray:
    """The decode branch's gather-path attention: (b, s, H, dh) queries
    over (b, length, H, dh) logical-order keys/values, each query token
    masked to keys at-or-before its own position. ``length`` follows
    the gathered view — on paged engines that is the live-width slice
    of the table (pages actually allocated), not ``max_len``, so the
    fallback stops touching dead pages."""
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, kk) / np.sqrt(dh)
    k_pos = jnp.arange(kk.shape[1])[None, None, None, :]
    scores = jnp.where(k_pos <= t[:, None, :, None], scores, -1e30)
    probs = jax.nn.softmax(scores.astype(jnp.float32), -1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(dtype), vv)


class _DecoderAttention(nn.Module):
    n_heads: int
    n_kv_heads: int
    max_len: int
    lora_rank: int
    quantized: bool = False
    n_adapters: int = 0
    #: sequence parallelism (train path): run the causal attention via
    #: ulysses all-to-alls over mesh[seq_axis], with the sequence dim of
    #: every activation sharded on that axis. Loss-exact WITHOUT kv_lens
    #: masking: causal attention means padded keys (beyond an example's
    #: length) are only visible to queries AT padded positions, whose
    #: loss terms are masked — valid positions' logits are untouched.
    seq_mesh: Any = None
    seq_axis: Optional[str] = None
    #: tensor-parallel composition: mesh axis the HEAD dim is sharded
    #: over (Megatron TP). The sp collectives then run within each TP
    #: head group — see ops/ulysses.py / ops/ring_attention.py.
    head_axis: Optional[str] = None
    rope_theta: float = 10000.0
    rope_scaling: Optional[Tuple[float, float, float, float]] = None
    #: serving-only int8 KV cache: K/V rows store as int8 with one f32
    #: absmax scale per (slot, position, kv-head) vector — half the
    #: decode cache's HBM at bf16 (4x at f32), bought with a bounded
    #: per-element quantization error (<= absmax/254 per component).
    #: Reads dequantize on the fly and fuse into the attention einsum.
    kv_int8: bool = False
    #: >0 — paged KV cache (serving decode path): per layer K/V live in
    #: a (kv_pages, kv_page_size, kv_heads, dh) POOL instead of per-slot
    #: (b, max_len, ...) rows; each batch row maps logical pages to pool
    #: pages via the ``page_tables`` call operand ((b, max_len/page)
    #: int32, host-owned). Cache HBM then scales with the pool — live
    #: tokens — not slots x max_len. Writes scatter at
    #: (table[pos // page], pos % page); attention gathers the row's
    #: pages back into logical order, so the masked softmax consumes
    #: exactly the bytes the contiguous layout would (bit-exact; garbage
    #: in unallocated pages sits past the position mask). int8-KV scale
    #: rows page identically. Pool page 0 is the engine's scratch page
    #: (idle lanes write there; never read unmasked).
    kv_page_size: int = 0
    kv_pages: int = 0
    #: paged decode dispatch (kv_page_size > 0 only): ``None`` (auto)
    #: runs the Pallas paged-attention kernels — which walk the block
    #: table directly instead of gathering pages back to logical order
    #: — on TPU and the page gather off-TPU; ``True``/``False`` force
    #: one path (tests force ``True``, riding the interpreter on CPU).
    #: EVERY decode call is kernel-eligible: the single-token step
    #: (s == 1, the generation hot loop) takes
    #: ``paged_decode_attention`` and multi-token windows (chunked
    #: prefill, speculative verify) take ``paged_window_attention``,
    #: which adds the in-window causal mask. Windows honor one extra
    #: operational escape hatch — ``RAFIKI_PAGED_KERNEL_WINDOWS=0``
    #: drops them back onto the gather (step-only mode) without
    #: touching the hot loop. See ``ops/paged_attention.py``.
    paged_kernel: Optional[bool] = None

    @nn.compact
    def __call__(self, x: jnp.ndarray, lens: jnp.ndarray,
                 positions: jnp.ndarray, decode: bool,
                 adapter_ids: Optional[jnp.ndarray] = None,
                 page_tables: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        b, s, d = x.shape
        dh = d // self.n_heads
        dense = functools.partial(LoRADense, rank=self.lora_rank,
                                  quantized=self.quantized,
                                  n_adapters=self.n_adapters)
        q = dense(self.n_heads * dh, name="wq")(x, adapter_ids)
        k = dense(self.n_kv_heads * dh, name="wk")(x, adapter_ids)
        v = dense(self.n_kv_heads * dh, name="wv")(x, adapter_ids)
        q = rope(q.reshape(b, s, self.n_heads, dh), positions,
                 theta=self.rope_theta, scaling=self.rope_scaling)
        k = rope(k.reshape(b, s, self.n_kv_heads, dh), positions,
                 theta=self.rope_theta, scaling=self.rope_scaling)
        v = v.reshape(b, s, self.n_kv_heads, dh)
        rep = self.n_heads // self.n_kv_heads

        if decode:
            # autoregressive path: write this step's k/v into each
            # example's OWN cache row at its OWN position (vectorized
            # scatter), then attend the single query over that example's
            # prefix. Per-slot positions are what continuous batching
            # needs — slots admitted mid-flight run at different depths
            # in the same compiled step. The flax init pass also traces
            # this branch — guard with has_variable so initialization
            # only allocates zeros and never writes.
            is_live = self.has_variable("cache", "k")
            kv_dtype = jnp.int8 if self.kv_int8 else x.dtype
            paged = self.kv_page_size > 0
            if paged:  # pool layout: pages, not per-slot rows
                kv_shape = (self.kv_pages, self.kv_page_size,
                            self.n_kv_heads, dh)
                sc_shape = (self.kv_pages, self.kv_page_size,
                            self.n_kv_heads)
            else:
                kv_shape = (b, self.max_len, self.n_kv_heads, dh)
                sc_shape = (b, self.max_len, self.n_kv_heads)
            ck = self.variable("cache", "k", jnp.zeros, kv_shape,
                               kv_dtype)
            cv = self.variable("cache", "v", jnp.zeros, kv_shape,
                               kv_dtype)
            if self.kv_int8:  # one absmax scale per stored K/V vector
                sk = self.variable("cache", "k_scale", jnp.zeros,
                                   sc_shape, jnp.float32)
                sv = self.variable("cache", "v_scale", jnp.zeros,
                                   sc_shape, jnp.float32)
            if not is_live:
                # init trace: local attention for output shape only
                kk = jnp.repeat(k, rep, axis=2)
                vv = jnp.repeat(v, rep, axis=2)
                scores = jnp.einsum("bqhd,bkhd->bhqk", q, kk) / np.sqrt(dh)
                probs = jax.nn.softmax(scores.astype(jnp.float32), -1)
                o = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(x.dtype), vv)
            else:
                # s >= 1: single-token generation AND chunked prefill ride
                # the same branch — write the chunk's k/v at each slot's
                # own positions (vectorized scatter), then mask each
                # QUERY token to keys at-or-before its own position.
                # Within-chunk causality falls out of the position mask:
                # the whole chunk is written before attention, and query
                # p only sees k_pos <= p. Duplicate positions in a row
                # (idle slots re-fed their current token) rewrite
                # identical values — harmless by construction.
                t = positions  # (b, s) — per-slot, per-token write index
                if paged:
                    if page_tables is None:
                        raise ValueError(
                            "kv_page_size > 0 decode requires the "
                            "page_tables operand (the serving engine "
                            "supplies it; plain generate paths must use "
                            "a contiguous-cache module)")
                    # write at (table[pos // page], pos % page); the
                    # gather below restores logical order, so the mask
                    # math is identical to the contiguous layout
                    widx = (jnp.take_along_axis(
                        page_tables, t // self.kv_page_size, axis=1),
                        t % self.kv_page_size)
                else:
                    widx = (jnp.arange(b)[:, None], t)

                def as_rows(c):
                    # cache → the logical view the attention consumes:
                    # a page gather when paged (covering only the
                    # tables the engine passed — its live-width slice,
                    # not max_len), identity otherwise
                    if paged:
                        return c[page_tables].reshape(
                            (b, page_tables.shape[1]
                             * self.kv_page_size) + c.shape[2:])
                    return c
                # every paged decode call is kernel-eligible: the
                # single-token step takes the step kernel, multi-token
                # windows (chunked prefill, speculative verify) take
                # the window kernel — unless the window escape hatch
                # drops them back onto the gather (step-only mode)
                use_kernel = (
                    paged and resolve_paged_kernel(self.paged_kernel)
                    and (s == 1 or
                         resolve_paged_window_kernel(self.paged_kernel)))
                if self.kv_int8:
                    def q8(u):
                        scale = jnp.maximum(
                            jnp.max(jnp.abs(u.astype(jnp.float32)), -1),
                            1e-8) / 127.0
                        qv = jnp.clip(jnp.round(
                            u.astype(jnp.float32) / scale[..., None]),
                            -127, 127).astype(jnp.int8)
                        return qv, scale

                    qk_, sk_ = q8(k)
                    qv_, sv_ = q8(v)
                    writes = [(ck, qk_), (cv, qv_), (sk, sk_),
                              (sv, sv_)]
                else:
                    writes = [(ck, k), (cv, v)]
                # EVERY cache write — paged or contiguous, kernel or
                # gather — goes through the partitioner shield (a
                # no-op on real TPU and single-device CPU): under a
                # multi-device interpret mesh the inline set-scatter
                # is re-lowered so cache replicas diverge and
                # reconcile additively, storing K exactly DOUBLED
                # (see ops/paged_attention.kv_cache_write)
                for var, val in writes:
                    var.value = kv_cache_write(
                        var.value, widx[0], widx[1], val)
                if use_kernel:
                    # walk the block table directly: partial softmax
                    # per pool page, LSE-merged, int8 dequant fused
                    # into the page load, dead pages skipped — per-call
                    # HBM traffic scales with live tokens
                    scales = ({"k_scale": sk.value, "v_scale": sv.value}
                              if self.kv_int8 else {})
                    sm = 1.0 / float(np.sqrt(dh))
                    if s == 1:  # generation hot loop — unchanged
                        o = paged_decode_attention(
                            q[:, 0], ck.value, cv.value, page_tables,
                            t[:, 0], sm_scale=sm, **scales)[:, None]
                    else:
                        # window positions are nondecreasing per row
                        # by construction of the engine's prefill and
                        # verify windows (idle/overhang rows repeat
                        # the last real entry) — the kernel's contract
                        o = paged_window_attention(
                            q, ck.value, cv.value, page_tables, t,
                            sm_scale=sm, **scales)
                elif self.kv_int8:
                    # multiply in f32 and cast the PRODUCT: casting the
                    # scales to bf16 first would throw away the very
                    # precision their f32 storage pays for (XLA fuses
                    # this into the attention einsum either way)
                    deq_k = (as_rows(ck.value).astype(jnp.float32)
                             * as_rows(sk.value)[..., None]).astype(
                                 x.dtype)
                    deq_v = (as_rows(cv.value).astype(jnp.float32)
                             * as_rows(sv.value)[..., None]).astype(
                                 x.dtype)
                    o = _masked_decode_attention(
                        q, jnp.repeat(deq_k, rep, axis=2),
                        jnp.repeat(deq_v, rep, axis=2), t, dh, x.dtype)
                else:
                    o = _masked_decode_attention(
                        q, jnp.repeat(as_rows(ck.value), rep, axis=2),
                        jnp.repeat(as_rows(cv.value), rep, axis=2),
                        t, dh, x.dtype)
        else:
            if self.seq_axis is not None:
                qt = q.transpose(0, 2, 1, 3)
                # per-TP-shard head count decides the strategy: each
                # model shard owns n_heads/tp whole heads (Megatron),
                # and the sp swap happens within that group
                tp = (self.seq_mesh.shape[self.head_axis]
                      if self.head_axis is not None else 1)
                if (self.n_heads // tp) % \
                        self.seq_mesh.shape[self.seq_axis]:
                    # heads don't split over the axis: rotate K/V blocks
                    # around the ring instead of swapping heads<->seq.
                    # The ring is GQA-aware: pass the UN-repeated
                    # n_kv_heads K/V so each hop moves only the real
                    # bytes (repeat happens per resident block inside)
                    from rafiki_tpu.ops.ring_attention import \
                        ring_attention

                    o = ring_attention(qt, k.transpose(0, 2, 1, 3),
                                       v.transpose(0, 2, 1, 3),
                                       self.seq_mesh, self.seq_axis,
                                       causal=True,
                                       batch_axis=DATA_AXIS,
                                       head_axis=self.head_axis)
                else:
                    from rafiki_tpu.ops.ulysses import ulysses_attention

                    # GQA-aware: un-repeated K/V — ulysses all-to-alls
                    # the small tensors when kv heads also divide the
                    # axis, and repeats before the swap otherwise
                    o = ulysses_attention(
                        qt, k.transpose(0, 2, 1, 3),
                        v.transpose(0, 2, 1, 3),
                        self.seq_mesh, self.seq_axis, causal=True,
                        batch_axis=DATA_AXIS,
                        head_axis=self.head_axis)
            else:
                o = flash_attention(
                    q.transpose(0, 2, 1, 3),
                    jnp.repeat(k, rep, axis=2).transpose(0, 2, 1, 3),
                    jnp.repeat(v, rep, axis=2).transpose(0, 2, 1, 3),
                    causal=True, kv_lens=lens)
            o = o.transpose(0, 2, 1, 3)
        o = o.reshape(b, s, self.n_heads * dh)
        return dense(d, name="wo")(o, adapter_ids)


class _DecoderBlock(nn.Module):
    n_heads: int
    n_kv_heads: int
    mlp_dim: int
    max_len: int
    lora_rank: int
    n_experts: int = 0  # >0 → MoE FFN (expert-parallel, ops/moe.py)
    moe_top_k: int = 1  # experts per token (1 Switch, 2 Mixtral-style)
    quantized: bool = False  # int8 base kernels (MoE experts stay f32)
    n_adapters: int = 0  # >0 → per-row stacked adapters (serving)
    seq_mesh: Any = None  # sequence parallelism (see _DecoderAttention)
    seq_axis: Optional[str] = None
    head_axis: Optional[str] = None  # sp×tp (see _DecoderAttention)
    rope_theta: float = 10000.0
    rope_scaling: Optional[Tuple[float, float, float, float]] = None
    kv_int8: bool = False  # serving-only int8 KV cache
    kv_page_size: int = 0  # >0 → paged KV pool (see _DecoderAttention)
    kv_pages: int = 0
    paged_kernel: Optional[bool] = None  # paged decode dispatch (ditto)

    @nn.compact
    def __call__(self, x, lens, positions, decode, adapter_ids=None,
                 page_tables=None):
        x = x + _DecoderAttention(
            self.n_heads, self.n_kv_heads, self.max_len, self.lora_rank,
            quantized=self.quantized, n_adapters=self.n_adapters,
            seq_mesh=self.seq_mesh, seq_axis=self.seq_axis,
            head_axis=self.head_axis,
            rope_theta=self.rope_theta, rope_scaling=self.rope_scaling,
            kv_int8=self.kv_int8, kv_page_size=self.kv_page_size,
            kv_pages=self.kv_pages, paged_kernel=self.paged_kernel,
            name="attn")(RMSNorm()(x), lens, positions, decode,
                         adapter_ids, page_tables)
        y = RMSNorm()(x)
        if self.n_experts > 0:
            from rafiki_tpu.ops.moe import MoEFeedForward

            return x + MoEFeedForward(self.n_experts, self.mlp_dim,
                                      router_top_k=self.moe_top_k,
                                      name="moe")(y)
        dense = functools.partial(LoRADense, rank=self.lora_rank,
                                  quantized=self.quantized,
                                  n_adapters=self.n_adapters)
        gate = dense(self.mlp_dim, name="gate")(y, adapter_ids)
        up = dense(self.mlp_dim, name="up")(y, adapter_ids)
        y = nn.silu(gate) * up  # SwiGLU
        return x + dense(x.shape[-1], name="down")(y, adapter_ids)


class Llama(nn.Module):
    """Decoder-only LM. Llama-3-8B = hidden 4096, depth 32, heads 32,
    kv_heads 8, mlp_dim 14336, vocab 128256."""

    vocab_size: int
    max_len: int
    hidden_dim: int = 4096
    depth: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    mlp_dim: int = 14336
    lora_rank: int = 0
    # compute dtype for activations/matmuls (params stay f32). None =
    # f32 compute; templates pass bf16 on TPU (f32 matmuls lower to
    # ~3x-cost multi-pass bf16 on the MXU).
    dtype: Any = None
    # gradient checkpointing per decoder block (train path only — the
    # decode path carries a mutable cache and recomputation would
    # double-write it): ~1/3 more FLOPs for O(depth) less activation
    # HBM. Identical math.
    remat: bool = False
    # three-way checkpointing schedule, superseding the legacy `remat`
    # bool when set: "none" (save everything), "full" (save only block
    # boundaries — max recompute, min HBM), "policy" (dots_saveable:
    # matmul outputs stay resident, elementwise ops recompute — the
    # middle ground). "" defers to `remat`. Identical math in all
    # three; only the HBM/recompute trade moves, which is why the knob
    # is searchable and feeds admission control.
    remat_policy: str = ""
    # >0 replaces every block's dense FFN with a top-k-routed MoE of
    # this many experts (ops/moe.py); expert weights shard over the
    # mesh's `model` axis (expert parallelism). The train step picks up
    # the load-balancing aux via mutable=["losses"].
    n_experts: int = 0
    # experts per token when n_experts > 0 (1 Switch, 2 Mixtral-style)
    moe_top_k: int = 1
    # serving-only int8 weight quantization of the LoRADense base
    # kernels (see LoRADense.quantized / quantize_llama_params)
    quantized: bool = False
    # >0 — multi-adapter serving: every LoRA site carries N stacked
    # adapters and each batch row applies the one named by the
    # ``adapter_ids`` call operand (see LoRADense.n_adapters). Build
    # the stacked params with :func:`stack_lora_adapters`.
    n_adapters: int = 0
    # sequence parallelism (train path): with seq_axis set, the causal
    # attention runs via ulysses all-to-alls over mesh[seq_axis] and
    # callers shard every (B, L) operand's L on that axis — long
    # sequences whose activations exceed one device's HBM train with
    # each device holding L/P of every activation. Static module
    # config, like dtype/remat (Mesh is hashable).
    seq_mesh: Any = None
    seq_axis: Optional[str] = None
    # sp×tp composition: mesh axis the head dim is tensor-parallel
    # sharded over — the sp collectives then run within each TP head
    # group (needs n_heads/tp % sp == 0 for ulysses; ring otherwise)
    head_axis: Optional[str] = None
    # RoPE base frequency: 10000 is the Llama-1/2 default; Llama-3
    # checkpoints use 500000 — a mismatched theta loads cleanly but
    # generates garbage, so the template threads the knob through
    rope_theta: float = 10000.0
    # Llama-3.1-style frequency-dependent context scaling as a STATIC
    # tuple (factor, low_freq_factor, high_freq_factor,
    # original_max_position_embeddings); None = unscaled (hashable —
    # dicts can't be flax module fields)
    rope_scaling: Optional[Tuple[float, float, float, float]] = None
    # serving-only int8 KV cache (decode path; see _DecoderAttention.
    # kv_int8): half the decode cache's HBM at bf16, bounded
    # quantization error. Training/eval never touch the decode branch.
    kv_int8: bool = False
    # >0 — paged KV cache (serving decode path; see _DecoderAttention.
    # kv_page_size): per layer K/V live in a (kv_pages, kv_page_size,
    # …) pool and each batch row maps logical→pool pages via the
    # ``page_tables`` call operand, so decode-cache HBM scales with the
    # pool (live tokens), not max_slots × max_len. kv_pages sizes the
    # pool (page 0 is the engine's scratch page). Training/eval and the
    # plain generate paths use contiguous-cache modules.
    kv_page_size: int = 0
    kv_pages: int = 0
    # paged decode dispatch (see _DecoderAttention.paged_kernel): None
    # (auto) = Pallas block-table kernel on TPU, page gather off-TPU;
    # True/False force one path. Serving-surface flag like kv_pages.
    paged_kernel: Optional[bool] = None

    @nn.compact
    def __call__(self, ids: jnp.ndarray, lens: Optional[jnp.ndarray] = None,
                 positions: Optional[jnp.ndarray] = None,
                 decode: bool = False,
                 return_hidden: bool = False,
                 adapter_ids: Optional[jnp.ndarray] = None,
                 page_tables: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        b, s = ids.shape
        if self.kv_page_size > 0:
            if self.max_len % self.kv_page_size:
                raise ValueError(
                    f"kv_page_size {self.kv_page_size} must divide "
                    f"max_len {self.max_len}")
            if self.kv_pages < 2:
                raise ValueError(
                    "kv_page_size > 0 needs kv_pages >= 2 (page 0 is "
                    "the scratch page; at least one usable page)")
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(s), (b, s))
        if lens is None:
            lens = jnp.full((b,), s, jnp.int32)
        x = nn.Embed(self.vocab_size, self.hidden_dim,
                     name="tok_embed")(ids)
        if self.dtype is not None:
            x = x.astype(self.dtype)
        block_cls = _DecoderBlock
        ckpt = self.remat_policy or ("full" if self.remat else "none")
        if ckpt not in ("none", "full", "policy"):
            raise ValueError(f"unknown remat_policy {ckpt!r} "
                             "(none/full/policy)")
        if ckpt != "none" and not decode:
            # decode stays static under remat (python-level branch in
            # the attention), so mark it non-traced — flax passes the
            # module itself as arg 0, putting decode at index 4
            block_cls = nn.remat(
                _DecoderBlock, static_argnums=(4,),
                policy=(jax.checkpoint_policies.dots_saveable
                        if ckpt == "policy" else None))
        for i in range(self.depth):
            x = block_cls(self.n_heads, self.n_kv_heads, self.mlp_dim,
                          self.max_len, self.lora_rank,
                          n_experts=self.n_experts,
                          moe_top_k=self.moe_top_k,
                          quantized=self.quantized,
                          n_adapters=self.n_adapters,
                          seq_mesh=self.seq_mesh, seq_axis=self.seq_axis,
                          head_axis=self.head_axis,
                          rope_theta=self.rope_theta,
                          rope_scaling=self.rope_scaling,
                          kv_int8=self.kv_int8,
                          kv_page_size=self.kv_page_size,
                          kv_pages=self.kv_pages,
                          paged_kernel=self.paged_kernel,
                          name=f"block_{i}")(x, lens, positions, decode,
                                             adapter_ids, page_tables)
        x = RMSNorm(name="final_norm")(x)
        if return_hidden:
            # chunked-loss path (chunked_lm_loss_terms): hand back the
            # final-norm activations so the caller can stream the
            # lm_head projection chunk-by-chunk instead of ever holding
            # (B, L, vocab) logits. lm_head params still initialize via
            # the default trace.
            return x
        return LoRADense(self.vocab_size, 0, quantized=self.quantized,
                         name="lm_head")(x)


def lm_valid_mask(seq_len: int, lens: jnp.ndarray,
                  example_mask: Optional[jnp.ndarray] = None
                  ) -> jnp.ndarray:
    """(B, L) bool: positions whose next-token loss counts — before
    each example's last real token, in unmasked examples. THE masking
    rule: the loss terms, the chunked loss, and gradient accumulation's
    global denominator must all agree on it."""
    pos = jnp.arange(seq_len)[None, :]
    valid = pos < (lens[:, None] - 1)
    if example_mask is not None:
        valid = valid & (example_mask[:, None] > 0)
    return valid


def lm_loss_terms(logits: jnp.ndarray, ids: jnp.ndarray,
                  lens: jnp.ndarray,
                  example_mask: Optional[jnp.ndarray] = None
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Masked next-token cross-entropy: (sum of losses, valid count).

    Targets are ``ids`` shifted left; positions at/after each example's
    last real token (and examples with ``example_mask == 0``) are
    excluded. One implementation shared by train/evaluate/dry-run.
    """
    targets = jnp.pad(ids[:, 1:], ((0, 0), (0, 1)))
    valid = lm_valid_mask(ids.shape[1], lens, example_mask)
    losses = optax.softmax_cross_entropy_with_integer_labels(
        logits.astype(jnp.float32), targets)
    return jnp.sum(losses * valid), jnp.sum(valid)


def chunked_lm_loss_terms(hidden: jnp.ndarray, head_kernel: jnp.ndarray,
                          ids: jnp.ndarray, lens: jnp.ndarray,
                          example_mask: Optional[jnp.ndarray] = None,
                          chunk: int = 256
                          ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``lm_loss_terms`` without ever materializing (B, L, vocab) logits.

    The full-logits tensor is the largest activation in LM training by
    far — Llama-3's 128k vocab at (8, 2048) is ~16 GB in f32, several
    times the model's entire activation footprint. This streams the
    lm_head projection over sequence chunks with ``lax.scan``: each step
    projects one (B, chunk, D) slice of the final-norm activations,
    reduces straight to summed cross-entropy, and discards the chunk's
    logits. ``jax.checkpoint`` on the chunk body keeps the BACKWARD pass
    at one chunk of logits too (recomputed per step), so peak logits
    memory drops from O(L·V) to O(chunk·V) in both passes.

    Same math as ``lm_loss_terms`` up to f32 summation order (the scan
    folds per-chunk partial sums sequentially, so low bits differ from
    the dense path's single reduction): the projection runs in
    ``hidden.dtype`` (matching ``LoRADense``) and the softmax in f32.
    Sequence pads introduced to reach a chunk multiple are masked out of
    both the sum and the count.
    """
    targets = jnp.pad(ids[:, 1:], ((0, 0), (0, 1)))
    valid = lm_valid_mask(hidden.shape[1], lens, example_mask)
    return (_chunked_ce_sum(hidden, targets, valid, head_kernel, chunk),
            jnp.sum(valid))


def _chunked_ce_sum(hidden: jnp.ndarray, targets: jnp.ndarray,
                    valid: jnp.ndarray, head_kernel: jnp.ndarray,
                    chunk: int, unroll: bool = False) -> jnp.ndarray:
    """The chunked projection+CE scan over precomputed targets/valid —
    shared by the dense-path wrapper above and the sequence-parallel
    variant below (which shards the SEQUENCE and must therefore shift
    targets globally before partitioning).

    ``unroll`` replaces the ``lax.scan`` with a Python loop over the
    (static) chunk count, which is how the sp variant runs it INSIDE
    its ``shard_map``. Same math, unrolled HLO."""
    b, length, d = hidden.shape
    chunk = max(1, min(int(chunk), length))
    pad = (-length) % chunk
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)))
        valid = jnp.pad(valid, ((0, 0), (0, pad)))
    n_chunks = (length + pad) // chunk
    # scan carries the running sum; xs walk the chunk axis
    hs = hidden.reshape(b, n_chunks, chunk, d).transpose(1, 0, 2, 3)
    ts = targets.reshape(b, n_chunks, chunk).transpose(1, 0, 2)
    vs = valid.reshape(b, n_chunks, chunk).transpose(1, 0, 2)

    @jax.checkpoint
    def _chunk_sum(h, t, v):
        logits = h @ head_kernel.astype(h.dtype)  # (B, chunk, V) — local
        losses = optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), t)
        return jnp.sum(losses * v)

    if unroll:
        total = jnp.zeros((), jnp.float32)
        for i in range(n_chunks):
            total = total + _chunk_sum(hs[i], ts[i], vs[i])
        return total

    def body(total, xs):
        h, t, v = xs
        return total + _chunk_sum(h, t, v), None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32),
                            (hs, ts, vs))
    return total


def chunked_lm_loss_terms_sp(hidden: jnp.ndarray,
                             head_kernel: jnp.ndarray,
                             ids: jnp.ndarray, lens: jnp.ndarray,
                             example_mask: Optional[jnp.ndarray],
                             chunk: int, mesh, data_axis: str,
                             sp_axis: str
                             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """:func:`chunked_lm_loss_terms` with the SEQUENCE dim sharded over
    ``mesh[sp_axis]`` (the long-context train path) — previously the
    two knobs were mutually exclusive because chunk slicing through
    GSPMD would re-gather the sp-sharded activations every chunk.

    The composition that avoids all gathers: the next-token SHIFT runs
    globally first (targets/valid are (B, L) int/bool — trivial bytes —
    and the shift is what crosses shard boundaries), then a
    ``shard_map`` over (data, sp) hands each device its LOCAL
    (B/dp, L/sp) slice of hidden/targets/valid; every device streams
    its own chunks through the shared scan and the (sum, count) reduce
    with one scalar ``psum``. The head kernel stays replicated (this
    variant is for the dp×sp regime; sp×tp keeps the dense loss —
    a vocab-sharded head inside the shard would need cross-axis
    softmax reductions). Same math as the dense path up to f32
    summation order."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from rafiki_tpu.ops.common import shard_map_checked

    targets = jnp.pad(ids[:, 1:], ((0, 0), (0, 1)))
    valid = lm_valid_mask(hidden.shape[1], lens, example_mask)
    sp = mesh.shape[sp_axis]
    if hidden.shape[1] % sp:
        raise ValueError(f"sequence {hidden.shape[1]} must divide the "
                         f"sp axis ({sp}) for the sharded chunked loss")
    chunk = max(1, min(int(chunk), hidden.shape[1] // sp))

    h_spec = P(data_axis, sp_axis, None)
    t_spec = P(data_axis, sp_axis)

    @functools.partial(
        shard_map_checked, mesh=mesh,
        in_specs=(h_spec, P(None, None), t_spec, t_spec),
        out_specs=(P(), P()))
    def _local(h_l, kernel, t_l, v_l):
        total = _chunked_ce_sum(h_l, t_l, v_l, kernel, chunk,
                                unroll=True)
        count = jnp.sum(v_l)
        return (jax.lax.psum(total, (data_axis, sp_axis)),
                jax.lax.psum(count, (data_axis, sp_axis)))

    hidden = jax.device_put(hidden, NamedSharding(mesh, h_spec))
    return _local(hidden, head_kernel, targets,
                  valid.astype(jnp.float32))


def quantize_llama_params(params: Any) -> Any:
    """f32 param tree → the ``quantized=True`` module's tree: every
    LoRADense base ``kernel`` becomes int8 ``qkernel`` + per-output-
    channel f32 ``qscale`` (symmetric absmax: scale = max|col| / 127);
    adapters, norms, embeddings, and MoE experts pass through unchanged.

    Weight-only post-training quantization for SERVING: persistent
    weight HBM drops 4x and the bandwidth-bound decode loop reads a
    quarter of the bytes. Most kernels are LoRA-frozen bases whose
    trained signal lives in the untouched f32 adapters; the trained
    ``lm_head`` kernel is quantized too (standard W8 PTQ — its
    per-element error is bounded like the rest). Reconstruction error
    is bounded by scale/2 per element (≤ ~0.4% of each channel's
    absmax); training and evaluate() keep the f32 originals.
    """
    def walk(tree: Any) -> Any:
        if not isinstance(tree, dict):
            return tree
        out = {}
        for name, sub in tree.items():
            if (isinstance(sub, dict) and "kernel" in sub
                    and getattr(sub["kernel"], "ndim", 0) == 2):
                k = jnp.asarray(sub["kernel"], jnp.float32)
                scale = jnp.maximum(jnp.max(jnp.abs(k), axis=0), 1e-8) / 127.0
                q = jnp.clip(jnp.round(k / scale[None, :]),
                             -127, 127).astype(jnp.int8)
                out[name] = {"qkernel": q, "qscale": scale,
                             **{kk: vv for kk, vv in sub.items()
                                if kk != "kernel"}}
            else:
                out[name] = walk(sub)
        return out

    return walk(params)


def serving_llama_params(params: Any, dtype: Any) -> Any:
    """Param tree → its SERVING form for compute dtype ``dtype``: every
    leaf a LoRADense site would cast to ``x.dtype`` before using it —
    the base ``kernel``, ``lora_a`` / ``lora_b`` (stacked multi-adapter
    ones too) and a quantized site's ``qscale`` — is held in ``dtype``
    already; everything else passes through as the very leaf it was
    (RMSNorm ``scale`` multiplies in f32, ``tok_embed`` is a gather,
    ``qkernel`` stays int8, the training ``MoEFeedForward`` casts its
    own). A ``kernel`` may be 2-D or a STACK of expert kernels
    (``ops/moe.py`` ``ExpertShare``: 3-D, cast where it is used like
    any other); a leaf already in ``dtype`` is handed on as it is, so a
    tree stored in the compute dtype costs no second copy.
    ``dtype=None`` (f32 compute) returns ``params`` itself.

    Same numbers, cast ONCE: ``LoRADense``'s ``astype(x.dtype)`` rounds
    the same f32 values to the same ``dtype`` values whether it runs
    here or at use (take-then-cast equals cast-then-take for the
    stacked adapters), and on this tree it is a no-op — so a decode
    dispatch streams the weights it computes with instead of re-deriving
    them from an f32 tree twice their size. Serving only: training,
    ``evaluate`` and ``predict`` keep the f32 originals. A cast leaf
    keeps its input's sharding (an elementwise op).
    """
    if dtype is None:
        return params
    cast = ("kernel", "lora_a", "lora_b", "qscale")

    def held(leaf: Any) -> Any:
        # a leaf already in ``dtype`` is the very leaf it was: no copy
        return leaf if getattr(leaf, "dtype", None) == dtype \
            else jnp.asarray(leaf, dtype)

    def walk(tree: Any) -> Any:
        if not isinstance(tree, dict):
            return tree
        out = {}
        for name, sub in tree.items():
            if isinstance(sub, dict) and (
                    "qkernel" in sub
                    or getattr(sub.get("kernel"), "ndim", 0) in (2, 3)):
                out[name] = {kk: (held(vv) if kk in cast else vv)
                             for kk, vv in sub.items()}
            else:
                out[name] = walk(sub)
        return out

    return walk(params)


def stack_block_params(params: Any, depth: int, n_stages: int) -> Any:
    """Canonical ``block_i`` params → (S, k, …) pipeline stacks (stage
    s owns layers [s·k, (s+1)·k), k = depth/S)."""
    from rafiki_tpu.parallel.pipeline import stack_stage_params

    k = depth // n_stages
    blocks = [params[f"block_{i}"] for i in range(depth)]
    # one stacking convention everywhere: layers within a stage AND
    # stages themselves stack via the same helper
    stages = [stack_stage_params(blocks[s * k:(s + 1) * k])
              for s in range(n_stages)]
    return stack_stage_params(stages)


def pipelined_lm_forward(module: Llama, params: Any, ids: jnp.ndarray,
                         lens: jnp.ndarray, mesh, n_micro: int,
                         remat: bool = False,
                         batch_axis: Optional[str] = None) -> jnp.ndarray:
    """``module.apply({"params": params}, ids, lens=lens)`` with the
    decoder blocks PIPELINED over the mesh's ``pipe`` axis.

    Identical math to the canonical forward (tested logits- and
    grads-equal): embedding and head run outside the pipe; the blocks
    restack to (S, k, …) and each stage scans its k layers; microbatches
    stream through ``parallel.pipeline.pipeline_apply`` carrying
    (hidden, lens, positions) as the activation pytree. Train-path only
    (no KV cache). MoE blocks are rejected — their aux loss cannot sow
    through the pipeline scan yet, and silently training without load
    balancing would be wrong.
    """
    from rafiki_tpu.parallel.pipeline import pipeline_apply

    if module.n_experts > 0:
        raise ValueError("pipelined training does not support MoE "
                         "blocks yet (aux loss cannot sow through the "
                         "pipeline scan)")
    n_stages = mesh.shape["pipe"]
    if module.depth % n_stages:
        raise ValueError(f"depth {module.depth} must be divisible by "
                         f"pipeline stages {n_stages}")
    b, s = ids.shape
    if b % n_micro:
        raise ValueError(f"batch {b} must be divisible by "
                         f"n_micro {n_micro}")
    x = nn.Embed(module.vocab_size, module.hidden_dim).apply(
        {"params": params["tok_embed"]}, ids)
    if module.dtype is not None:
        x = x.astype(module.dtype)
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    stacked = stack_block_params(params, module.depth, n_stages)
    mb = b // n_micro
    act = {"h": x.reshape(n_micro, mb, s, module.hidden_dim),
           "lens": lens.reshape(n_micro, mb),
           "pos": pos.reshape(n_micro, mb, s)}
    block = _DecoderBlock(module.n_heads, module.n_kv_heads,
                          module.mlp_dim, module.max_len,
                          module.lora_rank, n_experts=0)

    def stage_fn(p_stage, a):
        def layer(h, p_layer):
            return block.apply({"params": p_layer}, h, a["lens"],
                               a["pos"], False), None

        h, _ = jax.lax.scan(layer, a["h"], p_stage)
        return {"h": h, "lens": a["lens"], "pos": a["pos"]}

    out = pipeline_apply(stage_fn, stacked, act, mesh, axis="pipe",
                         batch_axis=batch_axis, remat=remat)
    h = out["h"].reshape(b, s, module.hidden_dim)
    h = RMSNorm(name="final_norm").apply({"params": params["final_norm"]},
                                         h)
    return LoRADense(module.vocab_size, 0, name="lm_head").apply(
        {"params": params["lm_head"]}, h)


def _kp_path(kp) -> str:
    """Render a tree_map_with_path key path as a lowercase '/'-joined
    string. lower(): flax auto-names unnamed instances "RMSNorm_0"
    etc."""
    return "/".join(str(getattr(k, "key", k)) for k in kp).lower()


def lora_trainable_mask(params: Any) -> Any:
    """True for LoRA adapters, norms, the LM head, and MoE layers;
    False (frozen) for base kernels and the embedding — the LoRA
    fine-tuning recipe. MoE routers/experts have no pretrained base (no
    HF Llama checkpoint carries them — convert.py leaves them at init),
    so freezing them would inject a random frozen transform into every
    residual stream; they always train."""

    def trainable(kp, _) -> bool:
        path = _kp_path(kp)
        return ("lora_" in path or "norm" in path or "/moe/" in path
                or path.startswith("lm_head"))

    return jax.tree_util.tree_map_with_path(trainable, params)


def adapter_only_mask(params: Any) -> Any:
    """True ONLY for ``lora_a``/``lora_b`` leaves — the strict LoRA
    recipe (norms, lm_head, embeddings all frozen). Trials trained
    under this mask differ exclusively in their adapters, which is the
    contract :func:`stack_lora_adapters` / multi-adapter serving
    enforces."""

    def trainable(kp, _) -> bool:
        path = _kp_path(kp)
        return "lora_a" in path or "lora_b" in path

    return jax.tree_util.tree_map_with_path(trainable, params)


def estimate_train_device_bytes(module: "Llama", *,
                                batch_size: int,
                                data_parallel: int = 1,
                                model_parallel: int = 1,
                                sequence_parallel: int = 1,
                                grad_accum: int = 1,
                                loss_chunk: int = 0,
                                remat: bool = True,
                                remat_policy: str = "",
                                adapters_only: bool = False,
                                pipeline_stages: int = 1,
                                pipeline_microbatches: int = 0,
                                fsdp_min_size: int = 2 ** 12,
                                overlap_collectives: bool = False
                                ) -> Dict[str, int]:
    """Per-device HBM budget for one train step, from real shape math.

    The admission-control formula (SURVEY §2.2's v5e-16 stretch config
    needs proof the 8B LoRA job FITS a 16GB chip before a worker
    claims it — an OOM mid-trial wastes the whole slot):

    - ``params`` / ``grads`` / ``opt`` are EXACT: the abstract param
      tree (``jax.eval_shape`` of the real init — no allocation), the
      template's ACTUAL sharding rules (``param_shardings`` with
      ``TP_RULES`` + fsdp over an :class:`~jax.sharding.AbstractMesh`,
      so a 16-chip budget computes on any host), and per-leaf
      ``shard_shape`` byte counts. Grads are f32 and param-sharded
      (``value_and_grad`` materializes the full tree; the frozen-leaf
      mask applies at ``tx.update``, after the tree exists — and with
      ``grad_accum>1`` the scan carries a second, accumulator copy).
      Opt state is adamw mu+nu over TRAINABLE leaves only
      (``optax.multi_transform`` + ``set_to_zero`` allocates nothing
      for frozen leaves).
    - ``activations`` is a documented UPPER BOUND (XLA frees/fuses
      more than this): with remat, block-boundary residuals
      (depth x tokens_dev x hidden) live through the backward, plus
      one block's recompute working set — per token roughly
      q,k,v,attn-out (~4 x hidden) + SwiGLU gate/up/down
      (~3 x mlp_dim) doubled for their cotangents — plus the logits
      chunk (f32 logits + cotangent, vocab tp-sharded; ``loss_chunk=0``
      means full-sequence logits, the large-vocab danger case).
      Without remat the working set multiplies by depth instead.
    - ``transient``: the largest single weight's compute-dtype cast
      (bf16 matmul operands are materialized per layer then freed).

    tokens_dev = batch/(dp·grad_accum) x max_len/sp on each device;
    dims follow the 3-axis (data, sp, model) train mesh exactly as
    :meth:`LlamaLoRA.train` builds it. Returns a dict of byte counts
    plus ``total``.
    """
    from jax.sharding import AbstractMesh, NamedSharding

    from rafiki_tpu.parallel.sharding import (DATA_AXIS, MODEL_AXIS,
                                              param_shardings)

    def abstract_mesh(sizes, names):
        # jax moved AbstractMesh from shape_tuple=((name, size), ...)
        # to (axis_sizes, axis_names) positional args; construct
        # whichever this jax speaks (the old form raises TypeError
        # inside __init__ when handed the new argument layout)
        try:
            return AbstractMesh(tuple(sizes), tuple(names))
        except TypeError:
            return AbstractMesh(tuple(zip(names, sizes)))

    dp, tp, sp = data_parallel, model_parallel, sequence_parallel
    if pipeline_stages > 1:
        return _estimate_pipeline_device_bytes(
            module, batch_size=batch_size, data_parallel=dp,
            pipeline_stages=pipeline_stages,
            pipeline_microbatches=pipeline_microbatches,
            adapters_only=adapters_only)
    if sp > 1 and tp > 1:
        mesh = abstract_mesh((dp, sp, tp), (DATA_AXIS, "sp", MODEL_AXIS))
    elif sp > 1:
        mesh = abstract_mesh((dp, sp), (DATA_AXIS, "sp"))
    else:
        mesh = abstract_mesh((dp, tp), (DATA_AXIS, MODEL_AXIS))
    tp_rules = None if (sp > 1 and tp == 1) else TP_RULES

    abstract = jax.eval_shape(
        lambda: module.init(jax.random.PRNGKey(0),
                            jnp.zeros((1, module.max_len),
                                      jnp.int32)))["params"]
    shardings = param_shardings(abstract, mesh, tp_rules=tp_rules,
                                fsdp=True, min_size=fsdp_min_size)

    def leaf_dev_bytes(leaf, sh: NamedSharding) -> int:
        return int(np.prod(sh.shard_shape(leaf.shape))) * \
            np.dtype(leaf.dtype).itemsize

    flat_p = jax.tree_util.tree_leaves(abstract)
    flat_s = jax.tree_util.tree_leaves(
        shardings, is_leaf=lambda x: isinstance(x, NamedSharding))
    params_dev = sum(leaf_dev_bytes(l, s) for l, s in zip(flat_p, flat_s))
    # grads: full f32 tree, param shardings; accumulation carries a
    # second copy through the scan
    grads_dev = sum(
        int(np.prod(s.shard_shape(l.shape))) * 4
        for l, s in zip(flat_p, flat_s)) * (2 if grad_accum > 1 else 1)
    # opt: adamw mu+nu for trainable leaves (f32, param-sharded)
    mask = (adapter_only_mask if adapters_only
            else lora_trainable_mask)(abstract)
    flat_m = jax.tree_util.tree_leaves(mask)
    opt_dev = 2 * sum(int(np.prod(s.shard_shape(l.shape))) * 4
                      for l, s, m in zip(flat_p, flat_s, flat_m) if m)

    act_bytes = 2 if module.dtype == jnp.bfloat16 else 4
    tokens_dev = max(1, batch_size // (dp * max(1, grad_accum))) * \
        max(1, module.max_len // sp)
    h, mlp = module.hidden_dim, module.mlp_dim
    per_block = tokens_dev * (4 * h + 3 * mlp) * act_bytes * 2  # +cotan
    acts_dev = _remat_activation_bytes(
        remat_policy or ("full" if remat else "none"),
        module.depth, tokens_dev, h, mlp, act_bytes, per_block)
    chunk = loss_chunk or module.max_len // sp
    logits_rows = max(1, batch_size // (dp * max(1, grad_accum)))
    logits_dev = logits_rows * chunk * \
        -(-module.vocab_size // (tp if tp_rules else 1)) * 4 * 2
    transient = max(
        (int(np.prod(s.shard_shape(l.shape))) for l, s in
         zip(flat_p, flat_s)), default=0) * act_bytes
    if overlap_collectives:
        # async fsdp all-gathers double-buffer: layer k+1's gathered
        # weights materialize while layer k computes, so one more
        # gathered-weight copy is live at the peak
        transient *= 2

    out = {"params": params_dev, "grads": grads_dev, "opt": opt_dev,
           "activations": acts_dev + logits_dev, "transient": transient}
    out["total"] = sum(out.values())
    return out


def _remat_activation_bytes(policy: str, depth: int, tokens: int,
                            h: int, mlp: int, act_bytes: int,
                            per_block: int) -> int:
    """Activation bytes resident through the backward under each
    checkpointing schedule — the admission lever the ``remat_policy``
    knob moves (ordered none > policy > full at any shape):

    - ``none``: every block's working set survives to the backward.
    - ``policy`` (dots_saveable): each block's matmul OUTPUTS (~4·h
      attention + ~3·mlp SwiGLU per token) stay resident; elementwise
      ops recompute, and so do the cotangent temporaries (hence no ×2).
    - ``full``: only block-boundary residuals (h per token per block)
      survive, plus one block's recompute working set.
    """
    if policy == "none":
        return depth * per_block
    if policy == "policy":
        return depth * tokens * (4 * h + 3 * mlp) * act_bytes + per_block
    return depth * tokens * h * act_bytes + per_block


def estimate_gang_device_bytes(module: "Llama", *, batch_size: int,
                               gang_size: int, remat_policy: str = "",
                               adapters_only: bool = False,
                               overlap_collectives: bool = False
                               ) -> Dict[str, int]:
    """HBM budget for a K-lane gang train step (gang-compiled tuning).

    The gang executor runs ONE unsharded program: the frozen base tree
    is closed over (broadcast — one copy regardless of K, including its
    never-updated trainable-leaf slots), while the K lanes stack only
    TRAINABLE leaves plus their Adam state, and every per-token
    activation term multiplies by K. ``params``/``grads``/``opt`` are
    exact (the estimator-vs-measured test holds them to the real pool
    bytes); activations follow :func:`_remat_activation_bytes`, which is
    what lets admission admit at ``remat_policy=full`` a gang it refuses
    at ``none``.
    """
    abstract = jax.eval_shape(
        lambda: module.init(jax.random.PRNGKey(0),
                            jnp.zeros((1, module.max_len),
                                      jnp.int32)))["params"]
    flat_p = jax.tree_util.tree_leaves(abstract)
    base_bytes = sum(int(np.prod(l.shape)) * np.dtype(l.dtype).itemsize
                     for l in flat_p)
    mask = (adapter_only_mask if adapters_only
            else lora_trainable_mask)(abstract)
    train_bytes = sum(
        int(np.prod(l.shape)) * np.dtype(l.dtype).itemsize
        for l, m in zip(flat_p, jax.tree_util.tree_leaves(mask)) if m)
    k = max(1, int(gang_size))
    params_dev = base_bytes + k * train_bytes
    grads_dev = k * train_bytes  # grads exist for trainable leaves only
    opt_dev = 2 * k * train_bytes  # adam mu+nu per lane

    act_bytes = 2 if module.dtype == jnp.bfloat16 else 4
    tokens = batch_size * module.max_len
    h, mlp = module.hidden_dim, module.mlp_dim
    per_block = tokens * (4 * h + 3 * mlp) * act_bytes * 2
    acts = _remat_activation_bytes(remat_policy or "none", module.depth,
                                   tokens, h, mlp, act_bytes, per_block)
    logits = batch_size * module.max_len * module.vocab_size * 4 * 2
    transient = max((int(np.prod(l.shape)) for l in flat_p),
                    default=0) * act_bytes
    if overlap_collectives:
        transient *= 2
    out = {"params": params_dev, "grads": grads_dev, "opt": opt_dev,
           "activations": (acts + logits) * k, "transient": transient}
    out["total"] = sum(out.values())
    # informational (already inside params): the K-independent
    # broadcast-base share, so callers can separate one-copy cost from
    # per-lane cost
    out["base"] = base_bytes
    return out


def _estimate_pipeline_device_bytes(module: "Llama", *, batch_size: int,
                                    data_parallel: int,
                                    pipeline_stages: int,
                                    pipeline_microbatches: int,
                                    adapters_only: bool) -> Dict[str, int]:
    """Pipeline-mode budget: train() REPLICATES the param tree on every
    device of the pipe x data mesh (the rep_pp device_put — weight-
    sharded pipeline storage is future work), so params/grads/opt count
    UNSHARDED here; admission control must see the replicated reality,
    not the tp+fsdp layout pp mode doesn't use. Activations: GPipe
    holds every in-flight microbatch's block-boundary activations for
    this device's depth/pp stage through the backward, plus one
    microbatch's within-block working set and the last stage's logits."""
    abstract = jax.eval_shape(
        lambda: module.init(jax.random.PRNGKey(0),
                            jnp.zeros((1, module.max_len),
                                      jnp.int32)))["params"]
    flat_p = jax.tree_util.tree_leaves(abstract)
    params_dev = sum(int(np.prod(l.shape)) * np.dtype(l.dtype).itemsize
                     for l in flat_p)
    grads_dev = sum(int(np.prod(l.shape)) * 4 for l in flat_p)
    mask = (adapter_only_mask if adapters_only
            else lora_trainable_mask)(abstract)
    opt_dev = 2 * sum(
        int(np.prod(l.shape)) * 4 for l, m in
        zip(flat_p, jax.tree_util.tree_leaves(mask)) if m)

    act_bytes = 2 if module.dtype == jnp.bfloat16 else 4
    pp = pipeline_stages
    n_micro = pipeline_microbatches or pp
    dp = max(1, data_parallel)
    rows_dev = max(1, batch_size // dp)  # all microbatches' rows
    micro_rows = max(1, batch_size // (dp * n_micro))
    h, mlp = module.hidden_dim, module.mlp_dim
    stage_depth = max(1, module.depth // pp)
    acts_dev = (stage_depth * rows_dev * module.max_len * h * act_bytes
                + micro_rows * module.max_len * (4 * h + 3 * mlp)
                * act_bytes * 2)
    logits_dev = micro_rows * module.max_len * module.vocab_size * 4 * 2
    transient = max((int(np.prod(l.shape)) for l in flat_p),
                    default=0) * act_bytes
    out = {"params": params_dev, "grads": grads_dev, "opt": opt_dev,
           "activations": acts_dev + logits_dev, "transient": transient}
    out["total"] = sum(out.values())
    return out


def _default_kv_pages(max_slots: int, max_len: int,
                      page_size: int) -> int:
    """Pool size when the operator sets ``kv_page_size`` but not
    ``kv_pages``: one scratch page plus full coverage (every slot can
    reach max_len), i.e. paged mechanics with zero admission stalls and
    no footprint saving. Memory wins come from sizing ``kv_pages`` DOWN
    to the expected live-token load (docs/operations.md)."""
    return 1 + max_slots * (max_len // page_size)


def stack_lora_adapters(trees: List[Any], validate: bool = True) -> Any:
    """Merge N adapter-only fine-tunes of one base into a single
    multi-adapter param tree for ``Llama(n_adapters=N)``.

    ``lora_a``/``lora_b`` leaves are stacked along a new leading
    adapter axis; every other leaf is taken from ``trees[0]`` and (when
    ``validate``) checked byte-identical across inputs — a mismatch
    means the trials were NOT trained with ``adapters_only`` and
    cannot share one serving engine (their norms/lm_head diverged).
    ``validate=False`` skips the scan for huge trees whose provenance
    is already known."""
    if not trees:
        raise ValueError("need at least one adapter tree")

    def merge(kp, *leaves):
        path = _kp_path(kp)
        if "lora_a" in path or "lora_b" in path:
            return jnp.stack([jnp.asarray(lf) for lf in leaves], axis=0)
        if validate:
            first = np.asarray(leaves[0])
            for i, lf in enumerate(leaves[1:], start=1):
                if not np.array_equal(first, np.asarray(lf)):
                    raise ValueError(
                        f"non-adapter leaf {path!r} differs between "
                        f"adapter 0 and {i}: multi-adapter serving "
                        "requires trials trained with adapters_only=True "
                        "(shared base/norms/lm_head)")
        return leaves[0]

    return jax.tree_util.tree_map_with_path(merge, trees[0], *trees[1:])


@functools.partial(jax.jit, static_argnums=(0, 4))
def _greedy_generate_impl(module: Llama, params: Any, prompt: jnp.ndarray,
                          plens: jnp.ndarray, max_new: int) -> jnp.ndarray:
    b, p_len = prompt.shape
    total = p_len + max_new
    cache = module.init(jax.random.PRNGKey(0),
                        jnp.zeros((b, 1), jnp.int32), decode=True)["cache"]

    def step(carry, t):
        cache, tok = carry
        logits, muts = module.apply(
            {"params": params, "cache": cache}, tok[:, None], decode=True,
            positions=jnp.full((b, 1), t, jnp.int32), mutable=["cache"])
        nxt = jnp.argmax(logits[:, -1].astype(jnp.float32), -1)
        nxt = nxt.astype(jnp.int32)
        # next input: the prompt token while it lasts, else our own output
        in_prompt = (t + 1) < plens
        tok_next = jnp.where(in_prompt,
                             prompt[:, jnp.minimum(t + 1, p_len - 1)], nxt)
        return (muts["cache"], tok_next), nxt

    (_, _), outs = jax.lax.scan(step, (cache, prompt[:, 0]),
                                jnp.arange(total - 1))
    # outs[t] is the model's prediction after consuming token t; example i's
    # generation starts at t = plens[i]-1
    outs = outs.transpose(1, 0)  # (b, total-1)
    gather = (plens[:, None] - 1) + jnp.arange(max_new)[None, :]
    gather = jnp.clip(gather, 0, total - 2)
    return jnp.take_along_axis(outs, gather, axis=1)


def greedy_generate(module: Llama, params: Any, prompt_ids: np.ndarray,
                    prompt_lens: np.ndarray, max_new: int) -> jnp.ndarray:
    """Greedy decode: scan one compiled cache step over prompt+generation.

    ``prompt_ids`` (b, P) left-aligned with PAD tails; each example starts
    generating right after its own last prompt token, so pads never enter
    the cache. Returns (b, max_new) generated ids.

    Compiled ONCE per (module config, batch, prompt width, max_new):
    ``module`` and ``max_new`` ride as static jit args, so repeated
    serving calls at bucketed shapes hit the executable cache instead of
    re-tracing the scan (the round-1/round-2 compile-per-request bug).
    """
    return _greedy_generate_impl(module, params,
                                 jnp.asarray(prompt_ids, jnp.int32),
                                 jnp.asarray(prompt_lens, jnp.int32),
                                 int(max_new))


class LlamaLoRA(BaseModel):
    """Causal-LM template: LoRA fine-tune over a 2-D (fsdp × tensor) mesh,
    greedy generation for serving. Accepts the ``.jsonl`` text corpus
    format (labels, if present, are ignored)."""

    TASKS = (TaskType.LANGUAGE_MODELING,)

    @staticmethod
    def get_knob_config() -> KnobConfig:
        return {
            "max_epochs": FixedKnob(6),
            "vocab_size": FixedKnob(1 << 14),
            "hidden_dim": CategoricalKnob([64, 128, 256, 512],
                                          shape_relevant=True),
            "depth": IntegerKnob(2, 8, shape_relevant=True),
            "n_heads": CategoricalKnob([4, 8], shape_relevant=True),
            "kv_ratio": CategoricalKnob([1, 2, 4], shape_relevant=True),
            "lora_rank": CategoricalKnob([4, 8, 16], shape_relevant=True),
            "max_len": CategoricalKnob([32, 64, 128], shape_relevant=True),
            "model_parallel": CategoricalKnob([1, 2, 4],
                                              shape_relevant=True),
            # traceable: rides the gang step as a traced per-lane
            # scalar — K learning rates share one compiled program
            "learning_rate": FloatKnob(1e-4, 3e-2, is_exp=True,
                                       traceable=True),
            # LoRA rank-scale (the α/r of the LoRA paper): the forward
            # applies scale·(x·A·B). Traceable like learning_rate —
            # per-lane in a gang — and FOLDED into lora_b at export, so
            # serving trees need no scale plumbing (scale=1 is the
            # legacy forward bit-for-bit)
            "lora_scale": FloatKnob(0.25, 4.0, is_exp=True,
                                    traceable=True),
            "batch_size": CategoricalKnob([8, 16, 32], shape_relevant=True),
            "bf16": CategoricalKnob([True, False]),
            # gradient checkpointing (train path): bigger batches for
            # ~1/3 extra FLOPs when activations are HBM-bound
            "remat": FixedKnob(False),
            # searchable checkpointing SCHEDULE, superseding the legacy
            # `remat` bool when not "none": none / full / policy
            # (dots_saveable — matmul outputs resident, elementwise
            # recomputed). Static → each value is its own gang compile
            # bucket; feeds estimate_device_budget so admission can
            # trade HBM for recompute instead of refusing the job.
            "remat_policy": CategoricalKnob(["none", "full", "policy"]),
            # overlap the fsdp all-gather/reduce-scatter path with
            # compute (async collectives + latency-hiding scheduler,
            # parallel.sharding.overlap_compiler_options). TPU-only
            # compiler options — a no-op bucket split on CPU. Costs one
            # extra gathered-weight buffer at peak (estimator's
            # transient term).
            "overlap_collectives": CategoricalKnob([False, True]),
            # train ONLY the lora_a/lora_b leaves (norms/lm_head frozen
            # too): the contract multi-adapter serving needs — N trials
            # that differ ONLY in adapters can then share one engine
            # (make_multi_adapter_engine / stack_lora_adapters). A
            # policy, not a search dimension: defaults off, the
            # operator enables it per job via knob_overrides
            "adapters_only": PolicyKnob("ADAPTERS_ONLY"),
            # >1 shards the SEQUENCE dim of every train activation over
            # this many devices — the long-context train path:
            # ulysses all-to-alls when per-TP-shard heads divide it,
            # ring K/V rotation otherwise (both exact). Composes with
            # data parallelism AND tensor parallelism: model_parallel>1
            # builds a (data, sp, model) 3-axis mesh with the sp
            # collectives running within each TP head group (needs
            # n_heads and kv heads divisible by model_parallel).
            # Composes with loss_chunk at model_parallel=1 (each shard
            # streams its own loss chunks — chunked_lm_loss_terms_sp).
            # max_len must divide by it; mutually exclusive with
            # pipeline_stages>1, MoE, and loss_chunk+model_parallel>1.
            "sequence_parallel": FixedKnob(1),
            # >1 pipelines the decoder blocks over this many devices
            # (GPipe microbatching, parallel/pipeline.py); depth must
            # divide by it; mutually exclusive with model_parallel>1.
            # Train path only — serving is unchanged. NOTE: pp mode
            # currently keeps params REPLICATED per device (right when
            # ACTIVATIONS, not weights, are the memory bound; weight-
            # sharded pipeline storage is future work).
            "pipeline_stages": FixedKnob(1),
            # microbatches per batch in pipeline mode (0 → one per
            # stage). GPipe's bubble fraction is (S-1)/(M+S-1): raise M
            # well above pipeline_stages to amortize it.
            "pipeline_microbatches": FixedKnob(0),
            # >0 → stream the lm_head projection + cross-entropy over
            # sequence chunks of this size in the train step instead of
            # materializing (B, L, vocab) logits — the dominant
            # activation at large vocab (chunked_lm_loss_terms). 0 keeps
            # the dense loss. Identical math either way.
            "loss_chunk": FixedKnob(0),
            # >0 → MoE FFN with this many experts per block (expert
            # parallelism over the mesh's model axis; ops/moe.py)
            "moe_experts": FixedKnob(0),
            # experts per token (1 Switch, 2 Mixtral-style)
            "moe_top_k": FixedKnob(1),
            "quick_train": PolicyKnob("QUICK_TRAIN"),
            "share_params": PolicyKnob("SHARE_PARAMS"),
            # serve with int8 weight-only-quantized base kernels
            # (quantize_llama_params): 4x less weight HBM for the
            # bandwidth-bound decode loop. predict()/make_decode_engine
            # only — training and evaluate() (the tuning objective)
            # stay full precision.
            "quantize_int8": FixedKnob(False),
            # >1 accumulates gradients over this many micro-batches
            # before each optimizer step (lax.scan) — big-batch math
            # exactly, one micro-batch's activations in HBM at a time.
            # Mutually exclusive with pipeline_stages>1 (GPipe already
            # microbatches); batch_size rounds to a multiple.
            "grad_accum": FixedKnob(1),
            # serving-only int8 KV cache: halves decode-cache HBM at
            # bf16 (more slots / longer contexts per chip) for a
            # bounded per-vector quantization error; generations are
            # no longer bit-identical to the f32-cache engine
            "kv_cache_int8": FixedKnob(False),
            # RoPE base frequency; match the pretrained checkpoint
            # (Llama-1/2: 10000, Llama-3: 500000). A wrong theta loads
            # cleanly but generates garbage.
            "rope_theta": FixedKnob(10000.0),
            # Llama-3.1-style frequency-dependent context scaling: a
            # JSON object string (or dict at construction) with
            # factor / low_freq_factor / high_freq_factor /
            # original_max_position_embeddings; "" = unscaled. Match
            # the checkpoint's config.json rope_scaling.
            "rope_scaling": FixedKnob(""),
            # serving-quality runs: a trained byte-BPE artifact
            # (data/bpe.py) replaces the hash tokenizer, and an
            # HF-convention safetensors checkpoint (models/convert.py)
            # replaces random base weights. Empty = round-3 behavior.
            "tokenizer_path": FixedKnob(""),
            "pretrained_path": FixedKnob(""),
        }

    def __init__(self, **knobs: Any) -> None:
        super().__init__(**knobs)
        self._params: Optional[Any] = None
        self._qparams: Optional[Any] = None  # lazy int8 serving tree
        self._id2tok: Dict[int, str] = {}
        self._fwd: Optional[Any] = None
        tok_path = str(self.knobs.get("tokenizer_path") or "")
        if tok_path:
            from rafiki_tpu.data.bpe import ByteBPETokenizer

            # vocab_size follows the artifact — the embedding must match
            # the merge table, not the knob default
            self.tokenizer: Any = ByteBPETokenizer.load(tok_path)
        else:
            self.tokenizer = HashTokenizer(int(self.knobs.get("vocab_size",
                                                              1 << 14)))

    # ---- internals ----
    def _module(self, quantized: bool = False, n_adapters: int = 0,
                seq_mesh: Any = None,
                seq_axis: Optional[str] = None,
                head_axis: Optional[str] = None,
                kv_page_size: int = 0, kv_pages: int = 0,
                paged_kernel: Optional[bool] = None) -> Llama:
        k = self.knobs
        hd = int(k["hidden_dim"])
        heads = int(k["n_heads"])
        kv_heads = max(1, heads // int(k["kv_ratio"]))
        return Llama(vocab_size=self.tokenizer.vocab_size,
                     max_len=int(k["max_len"]), hidden_dim=hd,
                     depth=int(k["depth"]), n_heads=heads,
                     n_kv_heads=kv_heads, mlp_dim=4 * hd,
                     lora_rank=int(k["lora_rank"]),
                     dtype=self._dtype(),
                     remat=bool(k.get("remat", False)),
                     remat_policy=str(k.get("remat_policy", "") or ""),
                     n_experts=int(k.get("moe_experts", 0)),
                     moe_top_k=int(k.get("moe_top_k", 1) or 1),
                     quantized=quantized, n_adapters=n_adapters,
                     seq_mesh=seq_mesh, seq_axis=seq_axis,
                     head_axis=head_axis,
                     rope_theta=float(k.get("rope_theta", 10000.0)
                                      or 10000.0),
                     rope_scaling=_parse_rope_scaling(
                         k.get("rope_scaling", "")),
                     kv_int8=bool(k.get("kv_cache_int8", False)),
                     kv_page_size=int(kv_page_size),
                     kv_pages=int(kv_pages),
                     paged_kernel=paged_kernel)

    def estimate_device_budget(self, n_devices: int,
                               gang_size: int = 0) -> Dict[str, int]:
        """Per-device train-step HBM budget for THIS parameterization on
        an ``n_devices`` mesh — the knob-level front of
        :func:`estimate_train_device_bytes` (admission control: a
        worker can refuse a trial whose ``total`` exceeds its chips'
        HBM instead of OOMing mid-step). Mesh factors derive exactly
        as :meth:`train` builds them: sp and model_parallel consume
        their factors, the rest is data parallelism.

        ``gang_size >= 1`` budgets a K-lane gang step instead
        (:func:`estimate_gang_device_bytes`): one broadcast base, K
        stacked adapter/optimizer lanes, unsharded — how the gang
        executor actually runs. 0 (the default) keeps the sequential
        mesh math."""
        if gang_size >= 1:
            return estimate_gang_device_bytes(
                self._module(),
                batch_size=int(self.knobs["batch_size"]),
                gang_size=int(gang_size),
                remat_policy=str(self.knobs.get("remat_policy", "")
                                 or ""),
                adapters_only=bool(self.knobs.get("adapters_only",
                                                  False)),
                overlap_collectives=bool(
                    self.knobs.get("overlap_collectives", False)))
        sp = int(self.knobs.get("sequence_parallel", 1) or 1)
        mp = int(self.knobs.get("model_parallel", 1) or 1)
        pp = int(self.knobs.get("pipeline_stages", 1) or 1)
        if pp > 1:
            # pipe x data mesh: batch shards over n/pp devices and
            # params REPLICATE (modeled by the pipeline estimator)
            sp, mp = 1, 1
            dp = max(1, n_devices // pp)
        else:
            if sp == 1:
                while n_devices % mp:
                    mp //= 2
                mp = max(1, mp)
            dp = max(1, n_devices // (sp * mp))
        return estimate_train_device_bytes(
            self._module(),
            batch_size=int(self.knobs["batch_size"]),
            data_parallel=dp, model_parallel=mp, sequence_parallel=sp,
            grad_accum=int(self.knobs.get("grad_accum", 1) or 1),
            loss_chunk=int(self.knobs.get("loss_chunk", 0) or 0),
            remat=bool(self.knobs.get("remat", False)),
            remat_policy=str(self.knobs.get("remat_policy", "") or ""),
            adapters_only=bool(self.knobs.get("adapters_only", False)),
            pipeline_stages=pp,
            pipeline_microbatches=int(
                self.knobs.get("pipeline_microbatches", 0) or 0),
            overlap_collectives=bool(
                self.knobs.get("overlap_collectives", False)))

    def estimate_serving_device_bytes(self, max_slots: int = 8,
                                      n_extra_adapters: int = 0,
                                      draft: Optional["LlamaLoRA"] = None,
                                      kv_page_size: int = 0,
                                      kv_pages: int = 0,
                                      host_kv_pages: int = 0
                                      ) -> Dict[str, int]:
        """Per-device HBM budget for the continuous-batching decode
        engine — the serving twin of :func:`estimate_train_device_bytes`
        (admission control: an inference worker can refuse a deployment
        whose engine would OOM at boot instead of dying mid-warmup).

        - ``params``: EXACT when the model is loaded — byte count of
          the actual serving tree (the int8 tree when ``quantize_int8``
          is set), else the abstract f32 init.
        - ``engine_params``: the decode engine's compute-dtype copy
          of the kernels and adapters (:func:`serving_llama_params`;
          0 at f32 compute, and with ``max_slots=0``: no engine).
        - ``kv_cache``: max_slots x max_len x kv_heads x head_dim x
          2 (K and V) x depth, at int8+f32-scales when
          ``kv_cache_int8`` else the compute dtype. Multi-adapter
          serving shares ONE cache (the stacked engine batches
          tenants into the same slots). With ``kv_page_size > 0``
          (paged serving) the term is the POOL instead —
          kv_pages x kv_page_size positions per layer — which is the
          whole point: admission can budget live tokens, not
          max_slots x max_len.
        - ``adapters``: stacked LoRA tensors for extra tenants
          (adapter dims scale linearly in tenant count).
        - ``draft``: the draft model's params + its own KV cache when
          draft-model speculation is configured.
        - ``working``: prefill-chunk activations + one (slots, vocab)
          f32 logits buffer — the decode scan's live set.
        - ``host_kv_cache`` (``host_kv_pages > 0``): the pinned-host
          page tier's bytes — HOST RAM, reported for sizing but
          excluded from ``total`` (which stays the per-device HBM
          figure admission compares against chip memory).
        """
        k = self.knobs
        if int(host_kv_pages) and int(kv_page_size) <= 0:
            # mirror the engine-build rule so admission never blesses
            # a tier the engine constructor refuses
            raise ValueError("host_kv_pages requires kv_page_size > 0 "
                             "(pages are the host tier's transfer "
                             "unit)")
        hd, heads = int(k["hidden_dim"]), int(k["n_heads"])
        kv_heads = max(1, heads // int(k["kv_ratio"]))
        dh = hd // heads
        L, depth = int(k["max_len"]), int(k["depth"])
        act_bytes = 2 if bool(k.get("bf16", False)) else 4

        def nbytes(leaf: Any) -> int:
            return int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize

        if self._params is not None:
            module, params = self._serving_module_params()
        else:
            module = self._module()
            params = jax.eval_shape(
                lambda: module.init(jax.random.PRNGKey(0),
                                    jnp.zeros((1, L), jnp.int32)))["params"]
        leaves = jax.tree_util.tree_leaves(params)
        params_dev = sum(nbytes(l) for l in leaves)
        vocab = module.vocab_size
        engine_dev = 0
        if max_slots > 0:
            # the engine serves from its own compute-dtype copy of the
            # leaves LoRADense casts (DecodeEngine.params), held BESIDE
            # the tree above, which predict()/evaluate() keep reading
            served = jax.tree_util.tree_leaves(jax.eval_shape(
                lambda p: serving_llama_params(p, module.dtype), params))
            engine_dev = sum(nbytes(s) for s, l in zip(served, leaves)
                             if s.dtype != l.dtype)

        per_pos = kv_heads * dh
        if int(kv_page_size) > 0:
            # paged pool: kv_pages x page_size positions per layer
            # (exactly what DecodeEngine allocates), independent of
            # max_slots — the footprint the block-table design buys.
            # kv_pages=0 mirrors the engine's full-coverage default.
            # The engine's validity rules apply here too: admission
            # must never pass a budget for a pool the engine build
            # will refuse.
            if L % int(kv_page_size):
                raise ValueError(f"kv_page_size {kv_page_size} must "
                                 f"divide max_len {L}")
            if kv_pages and int(kv_pages) < 2:
                raise ValueError("paged KV needs kv_pages >= 2 "
                                 "(scratch page + at least one usable "
                                 "page)")
            n_pages = int(kv_pages) or _default_kv_pages(
                max_slots, L, int(kv_page_size))
            n_pos = n_pages * int(kv_page_size)
        else:
            n_pos = max_slots * L
        if bool(k.get("kv_cache_int8", False)):
            # int8 rows + one f32 absmax scale per (slot, pos, head)
            kv_dev = n_pos * depth * 2 * (per_pos + 4 * kv_heads)
        else:
            kv_dev = n_pos * depth * 2 * per_pos * act_bytes
        adapters_dev = 0
        if n_extra_adapters:
            rank = int(k.get("lora_rank", 0) or 0)
            # per LoRA site: a (in, r) + b (r, out); 7 sites per block
            # (wq/wk/wv/wo/gate/up/down) + lm_head — linear in tenants
            # 7 LoRA sites per block (wq/wk/wv/wo/gate/up/down); the
            # lm_head is built rank-0 (no adapters stack there)
            site_in_out = [
                (hd, heads * dh), (hd, kv_heads * dh), (hd, kv_heads * dh),
                (heads * dh, hd), (hd, 4 * hd), (hd, 4 * hd), (4 * hd, hd)]
            per_adapter = depth * sum(
                (i * rank + rank * o) * 4 for i, o in site_in_out)
            adapters_dev = n_extra_adapters * per_adapter
        draft_dev = 0
        if draft is not None:
            d = draft.estimate_serving_device_bytes(max_slots=max_slots)
            draft_dev = d["params"] + d["engine_params"] + d["kv_cache"]
        working = (max_slots * 32 * hd * act_bytes  # prefill chunk
                   + max_slots * vocab * 4)         # logits buffer
        out = {"params": params_dev, "engine_params": engine_dev,
               "kv_cache": kv_dev, "adapters": adapters_dev,
               "draft": draft_dev, "working": working}
        out["total"] = sum(out.values())
        if int(host_kv_pages):
            # same per-position bytes as the device pool, host side —
            # after the total so the HBM figure is unchanged
            n_pos_host = int(host_kv_pages) * int(kv_page_size)
            if bool(k.get("kv_cache_int8", False)):
                out["host_kv_cache"] = n_pos_host * depth * 2 * (
                    per_pos + 4 * kv_heads)
            else:
                out["host_kv_cache"] = (n_pos_host * depth * 2
                                        * per_pos * act_bytes)
        return out

    def _serving_module_params(self, kv_page_size: int = 0,
                               kv_pages: int = 0,
                               paged_kernel: Optional[bool] = None
                               ) -> Tuple[Llama, Any]:
        """(module, params) for predict()/make_decode_engine — the int8
        pair when the quantize_int8 knob is set (quantized once per
        trained tree, then cached). Paging fields shape only the decode
        CACHE, never the params, so any (kv_page_size, kv_pages,
        paged_kernel) triple serves the same trained tree."""
        if not self.knobs.get("quantize_int8"):
            return self._module(kv_page_size=kv_page_size,
                                kv_pages=kv_pages,
                                paged_kernel=paged_kernel), self._params
        if self._qparams is None:
            self._qparams = quantize_llama_params(self._params)
        return self._module(quantized=True, kv_page_size=kv_page_size,
                            kv_pages=kv_pages,
                            paged_kernel=paged_kernel), self._qparams

    def _dtype(self):
        # single source of truth for the bf16 knob → compute dtype
        # (params stay f32; the matmul-heavy layers run in this dtype)
        return jnp.bfloat16 if self.knobs.get("bf16", True) else None

    @property
    def _bpe(self) -> bool:
        """True when a real (invertible) tokenizer is active."""
        return hasattr(self.tokenizer, "decode")

    def _encode_lm(self, texts: Sequence[str]) -> Tuple[np.ndarray,
                                                        np.ndarray]:
        """BOS-prefixed token rows. With the hash tokenizer this also
        grows the id→token table used to detokenize generations (hashing
        is one-way); BPE decodes exactly and needs no table."""
        max_len = int(self.knobs["max_len"])
        ids = np.zeros((len(texts), max_len), np.int32)
        lens = np.zeros((len(texts),), np.int32)
        for i, t in enumerate(texts):
            row, n = self.tokenizer.encode(t, max_len)  # CLS slot = BOS
            ids[i], lens[i] = row, n
            if not self._bpe:
                # mirror the tokenizer's own splitting so ids align
                # with words
                for tok_str, tok_id in zip(_TOKEN_RE.findall(t.lower()),
                                           row[1:n]):
                    self._id2tok[int(tok_id)] = tok_str
        return ids, lens

    def _mesh(self, devices):
        n = len(devices)
        mp = int(self.knobs.get("model_parallel", 1))
        while n % mp:
            mp //= 2
        return make_mesh(devices, model=max(1, mp))

    # ---- gang-compiled tuning (vmapped LoRA lanes) ----
    @classmethod
    def gang_blockers(cls, knobs: Knobs) -> List[str]:
        """Why THIS assignment cannot train as a gang lane (empty list
        = gangable). A lane is one unsharded program over a broadcast
        base, so every in-trial parallelism / accumulation regime —
        and a pretrained base, since lanes share the PRNGKey(0) init —
        stays on the sequential mesh path. Each entry names the
        blocking knob; ``tune_model``'s fallback warning surfaces them
        so an operator knows what to pin."""
        def _i(name: str, default: int = 0) -> int:
            return int(knobs.get(name, default) or default)

        out: List[str] = []
        if _i("model_parallel", 1) > 1:
            out.append("model_parallel>1 (tensor parallelism needs the "
                       "sharded mesh path)")
        if _i("sequence_parallel", 1) > 1:
            out.append("sequence_parallel>1 (sp shards activations over "
                       "a mesh the lane step does not build)")
        if _i("pipeline_stages", 1) > 1:
            out.append("pipeline_stages>1 (GPipe owns the device set)")
        if _i("grad_accum", 1) > 1:
            out.append("grad_accum>1 (the accumulation scan is not "
                       "factored into the lane step)")
        if _i("moe_experts") > 0:
            out.append("moe_experts>0 (expert parallelism + aux-loss "
                       "sow need the mesh path)")
        if _i("loss_chunk") > 0:
            out.append("loss_chunk>0 (the streamed loss is not factored "
                       "into the lane step)")
        if str(knobs.get("pretrained_path") or ""):
            out.append("pretrained_path set (lanes broadcast the shared "
                       "PRNGKey(0) base; checkpoint import is a mesh-"
                       "path feature)")
        return out

    @classmethod
    def gang_epochs(cls, knobs: Knobs, budget_scale: float) -> int:
        """Epoch count ``train()`` spends for this assignment — the gang
        scheduler's per-lane budget (must mirror the sequential loop
        exactly, quick_train cap included)."""
        epochs = max(1, round(int(knobs["max_epochs"])
                              * float(budget_scale)))
        if knobs.get("quick_train"):
            epochs = min(epochs, 2)
        return epochs

    @staticmethod
    def _lane_functions(module: "Llama", base_params: Any,
                        adapters_only: bool):
        """``(init_lane, train_step, eval_lane, merge, split)`` — the
        functional training core shared by the sequential
        ``_train_functional`` loop and the gang engine's vmapped lanes
        (1 lane == 1 sequential trial, bit-for-bit).

        The frozen base rides as a CLOSURE: under ``jax.vmap`` a
        closed-over tree is broadcast (``in_axes=None`` semantics), so
        K lanes share ONE HBM copy of the base while only the trainable
        leaves — a flat ``{path: leaf}`` dict — and their Adam state
        stack on the lane axis. ``hp`` carries the traceable knobs as
        traced scalars: ``optax.adamw(lr)`` is exactly
        ``scale_by_adam → add_decayed_weights → scale(-lr)``, so
        applying ``-lr`` to the decayed adam updates keeps the math
        identical while lr differs per lane inside one compiled
        program; ``lora_scale`` multiplies every ``lora_b`` leaf inside
        ``merge`` (the LoRA α/r rank-scale), and the export path folds
        the SAME elementwise product into the stored tree, so serving
        needs no scale plumbing and scale=1 is the legacy forward
        bit-for-bit."""
        mask_fn = adapter_only_mask if adapters_only \
            else lora_trainable_mask
        flat = jax.tree_util.tree_flatten_with_path(base_params)[0]
        flat_m = jax.tree_util.tree_leaves(mask_fn(base_params))
        paths = {_kp_path(kp) for (kp, _), m in zip(flat, flat_m) if m}
        tx = optax.chain(optax.scale_by_adam(),
                         optax.add_decayed_weights(1e-4))

        def split(tree: Any) -> Dict[str, Any]:
            return {_kp_path(kp): leaf for kp, leaf in
                    jax.tree_util.tree_flatten_with_path(tree)[0]
                    if _kp_path(kp) in paths}

        def merge(trainable: Dict[str, Any],
                  hp: Dict[str, Any]) -> Any:
            scale = hp["lora_scale"]

            def fill(kp, leaf):
                p = _kp_path(kp)
                if p not in paths:
                    return leaf  # frozen base — broadcast under vmap
                t = trainable[p]
                return scale * t if "lora_b" in p else t

            return jax.tree_util.tree_map_with_path(fill, base_params)

        def init_lane(rng: Any, hp: Dict[str, Any]) -> Dict[str, Any]:
            t = split(base_params)
            return {"params": t, "opt": tx.init(t)}

        def train_step(state: Dict[str, Any], hp: Dict[str, Any],
                       batch: Dict[str, Any]):
            def loss_fn(t):
                p = merge(t, hp)
                logits = module.apply({"params": p}, batch["ids"],
                                      lens=batch["lens"])
                total, count = lm_loss_terms(logits, batch["ids"],
                                             batch["lens"],
                                             batch["mask"])
                return total / jnp.maximum(count, 1.0)

            loss, grads = jax.value_and_grad(loss_fn)(state["params"])
            updates, opt = tx.update(grads, state["opt"],
                                     state["params"])
            updates = jax.tree_util.tree_map(
                lambda u: -hp["learning_rate"] * u, updates)
            return {"params": optax.apply_updates(state["params"],
                                                  updates),
                    "opt": opt}, loss

        def eval_lane(state: Dict[str, Any], hp: Dict[str, Any],
                      batch: Dict[str, Any]):
            p = merge(state["params"], hp)
            logits = module.apply({"params": p}, batch["ids"],
                                  lens=batch["lens"])
            return lm_loss_terms(logits, batch["ids"], batch["lens"])

        return init_lane, train_step, eval_lane, merge, split

    @classmethod
    def make_gang_spec(cls, knobs: Knobs, train_dataset_path: str,
                       val_dataset_path: str) -> GangSpec:
        """Functional training recipe for the gang engine: K LoRA
        adapter sets (+ Adam state) as lanes of one vmapped step over
        ONE broadcast frozen base. Everything but ``learning_rate`` /
        ``lora_scale`` (the traceable knobs) is burned in from
        ``knobs``; ``remat_policy`` and ``overlap_collectives`` are
        static, so each schedule is its own compile bucket."""
        blockers = cls.gang_blockers(knobs)
        if blockers:
            raise ValueError("knobs block gang lanes: "
                             + "; ".join(blockers))
        model = cls(**knobs)  # tokenizer wiring (vocab / BPE artifact)
        ds = load_text_classification_dataset(train_dataset_path)
        ids, lens = model._encode_lm(ds.texts)
        vds = load_text_classification_dataset(val_dataset_path)
        vids, vlens = model._encode_lm(vds.texts)
        module = model._module()
        batch_size = int(knobs["batch_size"])
        base = module.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, ids.shape[1]),
                                     jnp.int32))["params"]
        init_lane, train_step, eval_lane, merge, _split = \
            cls._lane_functions(module, base,
                                bool(knobs.get("adapters_only", False)))
        meta: Dict[str, Any] = {
            "id2tok": {str(k): v for k, v in model._id2tok.items()}}
        if model._bpe:
            meta["bpe_merges"] = [list(m)
                                  for m in model.tokenizer.merges]

        def epoch_batches(epoch: int):
            return batch_iterator({"ids": ids, "lens": lens},
                                  batch_size, seed=epoch)

        def eval_batches():
            # the SAME bucket-32 zero-padded stream evaluate() walks
            # (padded rows have lens=0, so no loss position counts)
            bucket = 32
            for i in range(0, len(vids), bucket):
                ib, lb = vids[i:i + bucket], vlens[i:i + bucket]
                pad = bucket - len(ib)
                if pad:
                    ib = np.concatenate(
                        [ib, np.zeros((pad, vids.shape[1]), ib.dtype)])
                    lb = np.concatenate(
                        [lb, np.zeros((pad,), lb.dtype)])
                yield {"ids": ib, "lens": lb}

        @jax.jit
        def _nll(params, ib, lb):
            logits = module.apply({"params": params}, ib, lens=lb)
            return lm_loss_terms(logits, ib, lb)

        def eval_seq(lane_state, hp, batch):
            # score on the graph evaluate() compiles: fold the lane's
            # rank-scale EAGERLY (exact elementwise product), then run
            # the same full-params nll jit — merging inside a vmapped
            # eval re-fuses the forward and drifts in the low bits
            p = merge(lane_state["params"], hp)
            return _nll(p, batch["ids"], batch["lens"])

        def export_blob(lane_state, hp):
            # fold the lane's rank-scale into lora_b — the same
            # elementwise product the train forward applied, so the
            # stored tree serves scale-free and token-identically
            # (dump_parameters format: make_multi_adapter_engine /
            # load_parameters load it as-is)
            hp_dev = {"learning_rate": jnp.float32(
                          float(hp["learning_rate"])),
                      "lora_scale": jnp.float32(
                          float(hp["lora_scale"]))}
            folded = merge({k: jnp.asarray(v) for k, v in
                            lane_state["params"].items()}, hp_dev)
            return {"params": jax.tree_util.tree_map(np.asarray,
                                                     folded),
                    "meta": dict(meta)}

        def warm_lane(fresh, blob):
            shared = (blob or {}).get("params")
            if shared is None or not same_tree_shapes(base, shared):
                return fresh  # incompatible architecture → cold start
            # adopt the parent's trainable leaves; the frozen base is
            # already this spec's broadcast copy (pretrained bases are
            # gang blockers, so both inits are PRNGKey(0))
            return {"params": _split(jax.tree_util.tree_map(
                        jnp.asarray, shared)),
                    "opt": fresh["opt"]}

        n_params = sum(int(np.prod(l.shape))
                       for l in jax.tree_util.tree_leaves(base))
        return GangSpec(
            hp_names=("learning_rate", "lora_scale"),
            init_lane=init_lane, train_step=train_step,
            epoch_batches=epoch_batches, eval_lane=eval_lane,
            eval_batches=eval_batches, export_blob=export_blob,
            warm_lane=warm_lane, share_params_knob="share_params",
            score_kind="lm", tokens_per_sample=int(knobs["max_len"]),
            lane_param_count=n_params,
            compiler_options=overlap_compiler_options(
                bool(knobs.get("overlap_collectives", False))) or None,
            eval_seq=eval_seq)

    def _train_functional(self, ids: np.ndarray, lens: np.ndarray,
                          ctx: TrainContext) -> None:
        """The gang-compatible sequential loop: drives the SAME
        ``_lane_functions`` the gang engine vmaps, unvmapped — a 1-lane
        gang trial is this loop bit-for-bit (``jit(f)`` vs
        ``jit(vmap(f))`` at K=1; tier-1 asserts score equality).
        ``train()`` routes here whenever ``gang_blockers`` is empty;
        parallel / accumulation / pretrained regimes keep the legacy
        sharded mesh loop."""
        module = self._module()
        batch_size = int(self.knobs["batch_size"])
        base = module.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, ids.shape[1]),
                                     jnp.int32))["params"]
        if self._params is not None and \
                same_tree_shapes(base, self._params):
            # re-train / load_parameters: current weights are the init
            base = jax.tree_util.tree_map(jnp.asarray, self._params)
        if ctx.shared_params is not None and \
                self.knobs.get("share_params"):
            if hasattr(ctx.shared_params, "restore"):
                import logging

                logging.getLogger(__name__).warning(
                    "sharded warm-start handles target the mesh train "
                    "path; the functional (gang-compatible) path "
                    "cold-starts")
            else:
                shared = ctx.shared_params.get("params")
                if shared is not None and same_tree_shapes(base,
                                                           shared):
                    base = jax.tree_util.tree_map(jnp.asarray, shared)
        init_lane, _train_step, _eval_lane, merge, _split = \
            self._lane_functions(
                module, base,
                bool(self.knobs.get("adapters_only", False)))
        hp = {"learning_rate": jnp.float32(
                  float(self.knobs["learning_rate"])),
              "lora_scale": jnp.float32(
                  float(self.knobs.get("lora_scale", 1.0)))}
        state = init_lane(jax.random.PRNGKey(0), hp)
        if ctx.devices:
            # the worker pins trials to disjoint device slots:
            # committing the state pulls the whole step onto the
            # slot's first device
            state = jax.device_put(state, ctx.devices[0])
        step = jax.jit(
            _train_step, donate_argnums=(0,),
            compiler_options=overlap_compiler_options(
                bool(self.knobs.get("overlap_collectives",
                                    False))) or None)
        epochs = self.gang_epochs(self.knobs, ctx.budget_scale)
        ctx.logger.define_plot("LM loss", ["loss"], x_axis="epoch")
        # donation invalidates buffers aliasing self._params (warm
        # start / re-train): drop the stale references first
        self._params = None
        self._qparams = None
        for epoch in range(epochs):
            losses = []
            for batch in batch_iterator({"ids": ids, "lens": lens},
                                        batch_size, seed=epoch):
                state, loss = step(state, hp, batch)
                losses.append(loss)
            mean_loss = (float(np.mean([float(l) for l in losses]))
                         if losses else float("nan"))
            ctx.logger.log(epoch=epoch, loss=mean_loss,
                           tokens=int(ids.shape[0] * ids.shape[1]))
            if ctx.checkpoint is not None:
                # preemption safety: worker throttles + persists. The
                # stored tree is the FOLDED merge (scale into lora_b),
                # the same shape dump_parameters always produced
                self._params = merge(state["params"], hp)
                ctx.checkpoint(self.dump_parameters,
                               frac_done=(epoch + 1) / epochs,
                               tree={"params": self._params})
            if ctx.should_continue is not None and \
                    not ctx.should_continue(epoch, -mean_loss):
                break
        self._params = merge(state["params"], hp)
        self._qparams = None
        self._fwd = None

    # ---- contract ----
    def train(self, dataset_path: str,
              ctx: Optional[TrainContext] = None) -> None:
        ctx = ctx or TrainContext()
        ds = load_text_classification_dataset(dataset_path)
        ids, lens = self._encode_lm(ds.texts)

        if not self.gang_blockers(self.knobs):
            # unsharded single-program regime: run the functional loop
            # the gang engine vmaps, so a sequential trial and a gang
            # lane are the same computation (bit-exactness contract)
            return self._train_functional(ids, lens, ctx)

        module = self._module()
        devices = ctx.devices or jax.local_devices()
        mesh = self._mesh(devices)
        sp = int(self.knobs.get("sequence_parallel", 1) or 1)
        sp_tp = 1  # model-parallel degree composed WITH sp (3-axis mesh)
        if sp > 1:
            # sequence parallelism: (data, sp[, model]) mesh, every
            # (B, L) operand's L sharded over `sp`, attention via
            # ulysses all-to-alls — or ring K/V rotation when per-shard
            # heads don't divide sp (module seq_mesh/seq_axis; dispatch
            # in _DecoderAttention). Long-context regime — each device
            # holds L/sp of every activation. With model_parallel>1 the
            # mesh gains a third `model` axis: Megatron TP per TP_RULES
            # shards the head dim, and the sp collectives run WITHIN
            # each TP head group (SURVEY §2.2's v5e-16 stretch config —
            # a long-context 8B job needs sp composed with tp).
            from jax.sharding import Mesh

            sp_tp = int(self.knobs.get("model_parallel", 1) or 1)
            if int(self.knobs.get("pipeline_stages", 1) or 1) > 1:
                raise ValueError(
                    "sequence_parallel>1 is mutually exclusive with "
                    "pipeline_stages>1 (pick sp[×tp]×dp or pp×dp)")
            if int(self.knobs.get("moe_experts", 0)) and sp_tp == 1:
                raise ValueError(
                    "moe_experts with sequence_parallel requires "
                    "model_parallel>1: experts shard over the `model` "
                    "axis, which the dp x sp mesh lacks (the 3-axis "
                    "dp x sp x model mesh carries both)")
            if int(self.knobs.get("loss_chunk", 0) or 0) and sp_tp > 1:
                raise ValueError(
                    "loss_chunk with sequence_parallel requires "
                    "model_parallel=1 (the sharded chunked loss keeps "
                    "the head replicated; a vocab-sharded head inside "
                    "the shard would need cross-axis softmax)")
            if len(devices) % (sp * sp_tp):
                raise ValueError(
                    f"sequence_parallel={sp} x model_parallel={sp_tp} "
                    f"must divide the trial's {len(devices)} devices")
            # per-shard n_heads % sp == 0 -> ulysses (2 all-to-alls);
            # otherwise the attention auto-falls-back to ring rotation
            # (P ppermutes) — see _DecoderAttention. Both are exact.
            if int(self.knobs["max_len"]) % sp:
                raise ValueError(f"max_len {self.knobs['max_len']} must "
                                 f"divide by sequence_parallel={sp}")
            heads = int(self.knobs["n_heads"])
            kv_heads = max(1, heads // int(self.knobs["kv_ratio"]))
            if sp_tp > 1 and (heads % sp_tp or kv_heads % sp_tp):
                raise ValueError(
                    f"sequence_parallel with model_parallel={sp_tp} "
                    f"needs n_heads ({heads}) and kv heads ({kv_heads}) "
                    "divisible by it (TP shards whole heads)")
            if sp_tp > 1:
                mesh = Mesh(
                    np.array(devices, dtype=object).reshape(-1, sp, sp_tp),
                    (DATA_AXIS, "sp", MODEL_AXIS))
                module = self._module(seq_mesh=mesh, seq_axis="sp",
                                      head_axis=MODEL_AXIS)
            else:
                mesh = Mesh(
                    np.array(devices, dtype=object).reshape(-1, sp),
                    (DATA_AXIS, "sp"))
                module = self._module(seq_mesh=mesh, seq_axis="sp")
        pp_stages = int(self.knobs.get("pipeline_stages", 1) or 1)
        n_micro = int(self.knobs.get("pipeline_microbatches", 0)
                      or 0) or pp_stages
        mesh_pp = None
        if pp_stages > 1:
            from jax.sharding import Mesh

            if int(self.knobs.get("model_parallel", 1)) > 1:
                # fail fast: the pipe×data mesh consumes every device,
                # so a requested TP regime would be silently dropped
                raise ValueError(
                    "pipeline_stages>1 is mutually exclusive with "
                    "model_parallel>1 (pick pp×dp or tp×fsdp)")
            if len(devices) % pp_stages:
                raise ValueError(
                    f"pipeline_stages={pp_stages} must divide the "
                    f"trial's {len(devices)} devices")
            if int(self.knobs["depth"]) % pp_stages:
                raise ValueError(
                    f"depth {self.knobs['depth']} must divide by "
                    f"pipeline_stages={pp_stages}")
            if n_micro % pp_stages:
                raise ValueError(
                    f"pipeline_microbatches={n_micro} must be a "
                    f"multiple of pipeline_stages={pp_stages}")
            # pipe × data over ALL trial devices (one device set for the
            # whole train step — params/batches live on this mesh too):
            # stages down one axis, each microbatch's batch dim sharded
            # over the other
            mesh_pp = Mesh(
                np.array(devices, dtype=object).reshape(
                    pp_stages, len(devices) // pp_stages),
                ("pipe", "data"))
        grad_accum = int(self.knobs.get("grad_accum", 1) or 1)
        if grad_accum > 1 and pp_stages > 1:
            raise ValueError(
                "grad_accum>1 is redundant with pipeline_stages>1 "
                "(GPipe already microbatches the step)")
        n_experts = int(self.knobs.get("moe_experts", 0))
        if n_experts and pp_stages > 1:
            raise ValueError("pipeline_stages>1 does not support MoE "
                             "blocks yet (aux loss cannot sow through "
                             "the pipeline scan)")
        if n_experts and n_experts % mesh.shape[MODEL_AXIS]:
            # fail fast: an indivisible expert count would silently fall
            # through the "experts" TP rule to the dense gate/up/down
            # rules — a mixed tensor-parallel regime instead of expert
            # parallelism, with a different collective/memory profile
            raise ValueError(
                f"moe_experts={n_experts} must be divisible by the "
                f"mesh's model axis ({mesh.shape[MODEL_AXIS]})")
        b_shard = batch_sharding(mesh)
        if sp > 1:
            # per-leaf shardings: (B, L) operands shard L over `sp`
            # (ids and the loss mask); per-example lens shard batch only
            from jax.sharding import NamedSharding, PartitionSpec

            batch1d = NamedSharding(mesh, PartitionSpec(DATA_AXIS))
            b_shard = {"ids": NamedSharding(
                mesh, PartitionSpec(DATA_AXIS, "sp")),
                "lens": batch1d, "m": batch1d}  # lens/mask: per-example

        n_data = mesh.shape[DATA_AXIS]
        batch_size = int(self.knobs["batch_size"])
        batch_size = max(n_data, batch_size - batch_size % n_data)
        if mesh_pp is not None:
            # n_micro microbatches, each batch-sharded over `data`
            # (size devices/pp) → batch must divide by both
            q = int(np.lcm(n_micro, len(devices)))
            batch_size = max(q, batch_size - batch_size % q)
        if grad_accum > 1:
            # each micro-batch still batch-shards over `data`
            q = grad_accum * n_data
            batch_size = max(q, batch_size - batch_size % q)

        pretrained = str(self.knobs.get("pretrained_path") or "")
        fresh = self._params is None
        if fresh:
            # init through the PLAIN module even in sp mode: ulysses
            # adds no params, and its shard_map would reject the
            # single-row init trace (batch 1 can't shard over `data`)
            init_module = self._module() if sp > 1 else module
            params = init_module.init(jax.random.PRNGKey(0),
                                      jnp.zeros((1, ids.shape[1]),
                                                jnp.int32))["params"]
        else:
            params = self._params
        warm = False
        shared_ref = None
        if ctx.shared_params is not None and self.knobs.get("share_params"):
            if hasattr(ctx.shared_params, "restore"):
                # sharded-checkpoint handle (store/sharded_ckpt.py):
                # gate on the manifest-only shape probe (the sharded
                # twin of same_tree_shapes — a mismatched donor must
                # leave warm=False so a pretrained base still loads),
                # then restore AFTER placement, straight into the 2-D
                # shardings: the warm tree never assembles on a host
                if ctx.shared_params.matches({"params": params}):
                    shared_ref = ctx.shared_params
                    warm = True
            else:
                shared = ctx.shared_params.get("params")
                if shared is not None and same_tree_shapes(params, shared):
                    params = jax.tree_util.tree_map(jnp.asarray, shared)
                    warm = True

        if pretrained and fresh and not warm:
            # base weights from an HF-convention checkpoint, loaded
            # DIRECTLY into their 2-D shardings (shard-sized file reads;
            # LoRA adapters keep their init) — config #5's real base.
            # A warm start / re-train already carries trained state and
            # must not be clobbered back to the checkpoint.
            from rafiki_tpu.models.convert import (import_llama_safetensors,
                                                   read_hf_rope_config)

            cfg_theta, cfg_scaling = read_hf_rope_config(pretrained)
            # the theta the model ACTUALLY uses (single source of
            # truth: _module's resolution), not a re-derivation
            knob_theta = module.rope_theta
            if cfg_theta is not None and \
                    abs(cfg_theta - knob_theta) > 1e-6:
                import logging

                logging.getLogger(__name__).warning(
                    "checkpoint config.json says rope_theta=%s but the "
                    "rope_theta knob is %s — a mismatched theta loads "
                    "cleanly and generates GARBAGE; set the knob to "
                    "match the checkpoint", cfg_theta, knob_theta)
            have = module.rope_scaling
            if cfg_scaling or have is not None:
                # symmetric check: scaling declared but not applied,
                # applied but not declared, mismatched, or of a TYPE
                # this model can't honor (yarn/linear/...) — all the
                # same silent-degradation class
                want = None
                unsupported = False
                if cfg_scaling:
                    try:
                        want = _parse_rope_scaling(cfg_scaling)
                    except (ValueError, TypeError):
                        unsupported = True
                if unsupported or (have is None) != (want is None) or (
                        have is not None and want is not None and any(
                            abs(a - b) > 1e-6
                            for a, b in zip(have, want))):
                    import logging

                    logging.getLogger(__name__).warning(
                        "checkpoint config.json rope_scaling=%r but "
                        "the rope_scaling knob resolves to %r — set "
                        "the knob to the checkpoint's values (or clear "
                        "it) or long-context generations silently "
                        "degrade", cfg_scaling, have)
            params = import_llama_safetensors(
                pretrained, params, mesh=mesh,
                tp_rules=None if (sp > 1 and sp_tp == 1) else TP_RULES,
                fsdp=True, min_size=2 ** 12)
        # 2-D sharding: tensor-parallel per TP_RULES over `model`, fsdp
        # over `data` for everything of >=4k elements — smaller tensors
        # (and test-scale params) are replicated, where fsdp's gather
        # traffic outweighs the memory it saves. The fsdp code path at
        # tiny shapes is covered by __graft_entry__.dryrun_multichip
        # (min_size=0 there). Imported leaves already sit in these
        # shardings (device_put is then a no-op); the put places the
        # rest (LoRA adapters, fresh/warm trees).
        if mesh_pp is not None:
            # pipeline mode: params live replicated on the pipe×data
            # mesh (ONE device set for the jitted step); the pipeline
            # re-annotates the block stacks onto their stages in-jit.
            # This is the activations-bound regime; a pretrained base
            # imported sharded above gets gathered here — weight-
            # sharded pipeline storage is future work, so flag it
            from jax.sharding import NamedSharding, PartitionSpec

            if pretrained:
                import logging

                logging.getLogger(__name__).warning(
                    "pipeline mode replicates the pretrained base on "
                    "every device; use tp×fsdp (pipeline_stages=1) "
                    "when WEIGHTS are the memory bound")
            rep_pp = NamedSharding(mesh_pp, PartitionSpec())
            params = jax.tree_util.tree_map(
                lambda x: jax.device_put(x, rep_pp), params)
            b_shard = rep_pp
        else:
            # dp-only sp mesh has no `model` axis: fsdp-over-data only
            # (the sp regime is activations-bound; adapters are tiny
            # anyway). The sp×tp 3-axis mesh applies full TP_RULES.
            p_shard = param_shardings(
                params, mesh, tp_rules=None if (sp > 1 and sp_tp == 1)
                else TP_RULES,
                fsdp=True, min_size=2 ** 12)
            params = jax.tree_util.tree_map(jax.device_put, params,
                                            p_shard)
        if shared_ref is not None:
            try:
                params = shared_ref.restore({"params": params})["params"]
            except (KeyError, ValueError):
                import logging

                # shape/structure mismatch (different knobs) — cold
                # start, mirroring the same_tree_shapes guard above
                logging.getLogger(__name__).warning(
                    "sharded warm-start checkpoint does not match this "
                    "parameterization; training cold", exc_info=True)

        lr = float(self.knobs["learning_rate"])
        # multi_transform (not optax.masked): masked leaves pass raw
        # gradients through as updates, set_to_zero actually freezes
        mask_fn = (adapter_only_mask
                   if bool(self.knobs.get("adapters_only", False))
                   else lora_trainable_mask)
        tx = optax.multi_transform(
            {"train": optax.adamw(lr), "freeze": optax.set_to_zero()},
            lambda p: jax.tree_util.tree_map(
                lambda t: "train" if t else "freeze", mask_fn(p)))
        opt_state = tx.init(params)

        # donate the param/opt trees: in-place update, no per-step copies
        from rafiki_tpu.ops.moe import MOE_AUX_COEF, moe_aux_loss

        use_remat = bool(self.knobs.get("remat", False))
        loss_chunk = int(self.knobs.get("loss_chunk", 0) or 0)
        if loss_chunk and mesh_pp is not None:
            # the pipelined forward assembles logits stage-wise; wiring
            # the streamed loss through it is a separate change — fail
            # fast rather than silently ignore the knob
            raise ValueError("loss_chunk>0 is not supported with "
                             "pipeline_stages>1")

        def micro_terms(p, ib, lb, mask):
            # (loss-sum, valid-count, moe-aux) over one (micro)batch —
            # shared by the plain step and gradient accumulation
            if loss_chunk:
                # streamed loss: forward stops at the final norm; the
                # lm_head projection + CE run chunk-by-chunk so
                # (B, L, vocab) logits never exist in HBM
                hidden, muts = module.apply(
                    {"params": p}, ib, lens=lb, mutable=["losses"],
                    return_hidden=True)
                aux = moe_aux_loss(muts)
                if sp > 1:
                    # long-context composition: hidden's L is sharded
                    # over `sp` — stream each shard's own chunks and
                    # psum (no per-chunk re-gather)
                    total, count = chunked_lm_loss_terms_sp(
                        hidden, p["lm_head"]["kernel"], ib, lb, mask,
                        loss_chunk, mesh, DATA_AXIS, "sp")
                else:
                    total, count = chunked_lm_loss_terms(
                        hidden, p["lm_head"]["kernel"], ib, lb, mask,
                        chunk=loss_chunk)
            else:
                # mutable=["losses"]: MoE blocks sow their load-
                # balance aux there; dense models sow nothing
                logits, muts = module.apply(
                    {"params": p}, ib, lens=lb, mutable=["losses"])
                aux = moe_aux_loss(muts)
                total, count = lm_loss_terms(logits, ib, lb, mask)
            return total, count, aux

        @functools.partial(
            jax.jit, donate_argnums=(0, 1),
            compiler_options=overlap_compiler_options(
                bool(self.knobs.get("overlap_collectives",
                                    False))) or None)
        def train_step(params, opt_state, ib, lb, mask):
            if grad_accum > 1:
                # gradient accumulation: scan grad_accum micro-batches,
                # summing gradients before ONE optimizer step. The CE
                # term is EXACTLY the big-batch math: the global valid-
                # token count is model-independent, so each micro-
                # batch's objective is total_i / global_count — summed
                # grads == grads of the full-batch loss. The MoE aux
                # (when moe_experts > 0) is computed per micro-batch
                # and averaged — standard practice, but router capacity
                # and load statistics then see T/grad_accum tokens, so
                # that term is NOT bit-identical to one big-batch apply.
                b, seq = ib.shape
                denom = jnp.maximum(jnp.sum(
                    lm_valid_mask(seq, lb, mask)).astype(jnp.float32),
                    1.0)
                mbs = (ib.reshape(grad_accum, b // grad_accum, seq),
                       lb.reshape(grad_accum, b // grad_accum),
                       mask.reshape(grad_accum, b // grad_accum))

                def obj(p, i, l, m):
                    total, _, aux = micro_terms(p, i, l, m)
                    return (total / denom
                            + MOE_AUX_COEF * aux / grad_accum)

                def body(carry, xs):
                    gacc, lacc = carry
                    val, g = jax.value_and_grad(obj)(params, *xs)
                    return (jax.tree_util.tree_map(jnp.add, gacc, g),
                            lacc + val), None

                zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
                (grads, loss), _ = jax.lax.scan(
                    body, (zeros, jnp.asarray(0.0, jnp.float32)), mbs)
                updates, opt_state = tx.update(grads, opt_state, params)
                return (optax.apply_updates(params, updates), opt_state,
                        loss)

            def loss_fn(p):
                if mesh_pp is not None:
                    # decoder blocks pipelined over the `pipe` axis —
                    # identical math to the canonical forward (proven by
                    # tests/test_pipeline.py); MoE rejected upstream
                    logits = pipelined_lm_forward(
                        module, p, ib, lb, mesh_pp, n_micro=n_micro,
                        remat=use_remat, batch_axis="data")
                    aux = jnp.asarray(0.0, jnp.float32)
                    total, count = lm_loss_terms(logits, ib, lb, mask)
                else:
                    total, count, aux = micro_terms(p, ib, lb, mask)
                return (total / jnp.maximum(count, 1.0)
                        + MOE_AUX_COEF * aux)

            loss, grads = jax.value_and_grad(loss_fn)(params)
            updates, opt_state = tx.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, loss

        epochs = self.gang_epochs(self.knobs, ctx.budget_scale)
        def step(state, b):
            params, opt_state = state
            params, opt_state, loss = train_step(
                params, opt_state, b["ids"], b["lens"], b["m"])
            return (params, opt_state), loss

        ctx.logger.define_plot("LM loss", ["loss"], x_axis="epoch")
        # donation invalidates buffers that may alias self._params (warm
        # start / re-train): drop the stale references first
        self._params = None
        self._qparams = None
        with mesh:
            for epoch in range(epochs):
                (params, opt_state), mean_loss = train_epoch(
                    step, (params, opt_state),
                    ({"ids": b["ids"], "lens": b["lens"],
                      "m": b["mask"].astype(np.float32)}
                     for b in batch_iterator({"ids": ids, "lens": lens},
                                             batch_size, seed=epoch)),
                    sharding=b_shard)
                # tokens: the epoch's (padded) token volume — the train
                # worker's obs hook turns it into tokens/s + est_mfu so
                # trials compare on throughput, not just loss
                ctx.logger.log(epoch=epoch, loss=mean_loss,
                               tokens=int(ids.shape[0] * ids.shape[1]))
                if ctx.checkpoint is not None:
                    # preemption safety: worker throttles + persists.
                    # The live (sharded device) tree rides along so a
                    # sharded-capable store saves per-shard + async —
                    # the blob factory only runs on fallback backends
                    self._params = params
                    ctx.checkpoint(self.dump_parameters,
                                   frac_done=(epoch + 1) / epochs,
                                   tree={"params": params})
                if ctx.should_continue is not None and \
                        not ctx.should_continue(epoch, -mean_loss):
                    break
        self._params = params
        self._qparams = None
        self._fwd = None

    def evaluate(self, dataset_path: str) -> float:
        """Inverse perplexity exp(-nll) in (0, 1]; higher is better."""
        assert self._params is not None
        ds = load_text_classification_dataset(dataset_path)
        ids, lens = self._encode_lm(ds.texts)
        if self._fwd is None:  # cache: jit memoizes by function identity
            module = self._module()
            loss_chunk = int(self.knobs.get("loss_chunk", 0) or 0)

            @jax.jit
            def nll(params, ib, lb):
                if loss_chunk:
                    # a config that NEEDS the streamed loss to train
                    # (vocab·L logits over HBM) would OOM right here at
                    # eval otherwise — same chunking, same math
                    hidden = module.apply({"params": params}, ib, lens=lb,
                                          return_hidden=True)
                    return chunked_lm_loss_terms(
                        hidden, params["lm_head"]["kernel"], ib, lb,
                        chunk=loss_chunk)
                logits = module.apply({"params": params}, ib, lens=lb)
                return lm_loss_terms(logits, ib, lb)

            self._fwd = nll
        nll = self._fwd
        total, count = 0.0, 0.0
        bucket = 32
        for i in range(0, len(ids), bucket):
            ib, lb = ids[i:i + bucket], lens[i:i + bucket]
            pad = bucket - len(ib)
            if pad:
                ib = np.concatenate([ib, np.zeros((pad, ids.shape[1]),
                                                  ib.dtype)])
                lb = np.concatenate([lb, np.zeros((pad,), lb.dtype)])
            s, c = nll(self._params, ib, lb)
            total += float(s)
            count += float(c)
        return float(np.exp(-total / max(count, 1.0)))

    def predict(self, queries: Sequence[Any],
                max_new_tokens: int = 8) -> List[Any]:
        """Greedy continuations, detokenized via the learned id→token
        table (unknown ids render as ``<id>``).

        The batch dim is padded up to a power-of-two bucket so repeated
        serving calls reuse the compiled generate (static module +
        max_new, bucketed (b, prompt) shapes → executable-cache hits)."""
        assert self._params is not None, "model is not trained/loaded"
        texts = [q if isinstance(q, str) else str(q) for q in queries]
        max_len = int(self.knobs["max_len"])
        # the KV cache holds max_len positions total (prompt + generation)
        max_new = min(max_new_tokens, max_len - 1)
        prompt_cap = max(1, max_len - max_new)
        ids, lens = self.tokenizer.encode_batch(texts, prompt_cap)
        n = len(texts)
        bucket = 1 << max(0, (n - 1).bit_length())  # next power of two
        if bucket > n:  # pad rows are BOS-only prompts, discarded below
            ids = np.concatenate(
                [ids, np.full((bucket - n, ids.shape[1]), 0, ids.dtype)])
            ids[n:, 0] = BOS_ID
            lens = np.concatenate(
                [lens, np.ones((bucket - n,), lens.dtype)])
        module, params = self._serving_module_params()
        out = np.asarray(greedy_generate(module, params, ids, lens,
                                         max_new))[:n]
        return [self._detok(row) for row in out]

    def _detok(self, ids: Sequence[Any]) -> str:
        """Render generated ids: exact BPE decode when a real tokenizer
        is active, else the learned id→token table (hashing is one-way;
        unknown ids render as ``<id>``)."""
        if self._bpe:
            return self.tokenizer.decode(int(t) for t in ids).lstrip()
        return " ".join(self._id2tok.get(int(t), f"<{int(t)}>")
                        for t in ids)

    def warmup(self) -> None:
        """Compile the serving generate (smallest bucket) before
        traffic arrives."""
        if self._params is None:
            return
        self.predict(["warmup"])

    def make_decode_engine(self, max_slots: int = 8,
                           max_new_tokens: int = 8,
                           steps_per_sync: int = 4,
                           prefill_chunk: int = 32,
                           speculate_k: int = 0,
                           system_prefix: str = "",
                           draft_model: Optional["LlamaLoRA"] = None,
                           kv_page_size: int = 0,
                           kv_pages: int = 0,
                           paged_kernel: Optional[bool] = None,
                           host_kv_pages: int = 0):
        """Continuous-batching serving engine over this model's weights
        (BASELINE.md config #5). The inference worker drives it when
        running in decode-loop mode; see ``serving/decode_engine.py``.

        ``draft_model`` (with ``speculate_k >= 2``): a SMALLER trained
        LlamaLoRA sharing this model's vocabulary drafts the
        speculative continuations instead of prompt-lookup n-grams —
        real draft-model speculation, still greedy-lossless (the
        target's verify step is authoritative either way).

        ``kv_page_size > 0`` serves from a PAGED KV pool of
        ``kv_pages`` pages (block tables; see DecodeEngine): decode-
        cache HBM scales with live tokens and admission backpressures
        on the pool instead of refusing at max_slots × max_len.
        ``kv_pages=0`` defaults to full coverage (no saving, no
        stalls); size it down per docs/operations.md. Token-bit-exact
        with the contiguous engine. The draft model's own cache stays
        contiguous (drafts are small).

        ``paged_kernel`` (paged engines only): ``None`` (auto, the
        default) decodes through the Pallas block-table kernels on TPU
        and the page gather off-TPU; ``True``/``False`` force one
        path. Every decode leg is covered: the s==1 step, chunked
        prefill windows, and speculative-verify windows (the last two
        via ``paged_window_attention``; ``RAFIKI_PAGED_KERNEL_WINDOWS=0``
        drops just the windows back onto the gather). See
        ``ops/paged_attention.py``.

        ``host_kv_pages > 0`` (paged engines only) attaches the
        host-RAM page tier: the admission budget becomes HBM + host
        pages, cold pages spill to pinned host memory and prefetch
        back ahead of the step that resumes them — serviceable
        concurrency stops being hard-capped by HBM (see
        ``serving/kv_tier.py`` and docs/operations.md)."""
        assert self._params is not None, "model is not trained/loaded"
        if host_kv_pages and kv_page_size <= 0:
            raise ValueError("host_kv_pages requires kv_page_size > 0 "
                             "(pages are the host tier's transfer "
                             "unit)")
        if kv_page_size > 0 and not kv_pages:
            kv_pages = _default_kv_pages(max_slots,
                                         int(self.knobs["max_len"]),
                                         int(kv_page_size))
        module, params = self._serving_module_params(
            kv_page_size=kv_page_size, kv_pages=kv_pages,
            paged_kernel=paged_kernel if kv_page_size > 0 else None)
        text_engine = self._build_text_engine(
            module, params, max_slots, max_new_tokens, steps_per_sync,
            prefill_chunk, speculate_k, draft_model=draft_model,
            host_kv_pages=host_kv_pages)
        if system_prefix:
            text_engine.register_prefix(system_prefix)
        return text_engine

    def _build_text_engine(self, module, params, max_slots,
                           max_new_tokens, steps_per_sync, prefill_chunk,
                           speculate_k, draft_model=None,
                           host_kv_pages=0):
        """Common engine wiring for the single- and multi-adapter
        flavors: this model's tokenizer around a DecodeEngine."""
        from rafiki_tpu.serving.decode_engine import (DecodeEngine,
                                                      TextDecodeEngine)

        max_len = int(self.knobs["max_len"])

        def encode(text: str) -> np.ndarray:
            row, n = self.tokenizer.encode(str(text), max_len)
            return row[:max(1, int(n))]

        draft = None
        if draft_model is not None:
            if int(speculate_k) < 2:
                # fail loudly, like the worker's config guard: a caller
                # who handed over a draft believes speculation is live
                raise ValueError(
                    "draft_model requires speculate_k >= 2 "
                    f"(got {speculate_k})")
            assert draft_model._params is not None, \
                "draft model is not trained/loaded"
            d_module, d_params = draft_model._serving_module_params()
            if not _same_tokenizer(self.tokenizer,
                                   draft_model.tokenizer):
                # equal vocab_size is NOT 'same tokenizer': different
                # BPE merge tables map the same ids to different text,
                # so drafts would never match and speculation silently
                # gates off — fail loudly instead
                raise ValueError(
                    "draft and target tokenize differently (merge "
                    "tables / vocab mismatch): speculation compares "
                    "token ids, so the models must share a tokenizer")
            if int(draft_model.knobs["max_len"]) < max_len:
                raise ValueError(
                    "draft max_len must cover the target's (the draft "
                    "cache walks the same positions)")
            # the params must actually fit the draft's knobs: a
            # mis-set draft_knobs would otherwise surface as an opaque
            # XLA shape error at the first dispatch
            abstract = jax.eval_shape(lambda: d_module.init(
                jax.random.PRNGKey(0),
                jnp.zeros((1, 8), jnp.int32))["params"])
            if not same_tree_shapes(abstract, d_params):
                raise ValueError(
                    "draft parameters do not match the draft model's "
                    "knobs (pass the draft trial's own knobs, e.g. "
                    "the worker config's draft_knobs)")
            draft = (d_module, d_params)
        core = DecodeEngine(module, params,
                            max_slots=max_slots, max_len=max_len,
                            steps_per_sync=steps_per_sync,
                            prefill_chunk=prefill_chunk,
                            speculate_k=speculate_k, draft=draft,
                            host_kv_pages=int(host_kv_pages))
        return TextDecodeEngine(
            core, encode, self._detok,
            max_new=min(max_new_tokens, max_len - 1))

    def make_multi_adapter_engine(self, adapter_params: Sequence[Any],
                                  max_slots: int = 8,
                                  max_new_tokens: int = 8,
                                  steps_per_sync: int = 4,
                                  prefill_chunk: int = 32,
                                  speculate_k: int = 0,
                                  validate: bool = True,
                                  kv_page_size: int = 0,
                                  kv_pages: int = 0,
                                  paged_kernel: Optional[bool] = None,
                                  host_kv_pages: int = 0):
        """ONE continuous-batching engine serving N adapter-only
        fine-tunes of one base (S-LoRA-style multi-adapter serving).

        The reference deploys its best-N trials as N independent worker
        replicas, each holding a full model (SURVEY.md §3.3). When the
        trials are LoRA fine-tunes trained with ``adapters_only=True``,
        they differ only in their (tiny) adapter matrices — so all N
        can share one base model's HBM and one compiled decode step,
        with each request selecting its fine-tune via
        ``submit(..., adapter_id=i)``. Requests against different
        adapters batch together in the same fused step: the base matmul
        runs once for the whole batch; only the rank-r correction is
        per-row (see ``LoRADense.n_adapters``).

        ``adapter_params``: param trees in adapter-id order (e.g.
        ``[trial_a.params, trial_b.params]``); non-adapter leaves must
        be identical across trees (validated unless ``validate=False``)
        and the engine serves with ``adapter_params[0]``'s base.
        Tokenization comes from THIS model. Composes with the
        ``quantize_int8`` knob: the SHARED base kernels quantize once
        (4x less HBM for the one base all N tenants read every step);
        the stacked f32 adapters pass through untouched."""
        trees = list(adapter_params)
        if not trees:
            raise ValueError("adapter_params must name >= 1 trees")
        if host_kv_pages and kv_page_size <= 0:
            raise ValueError("host_kv_pages requires kv_page_size > 0 "
                             "(pages are the host tier's transfer "
                             "unit)")
        stacked = stack_lora_adapters(trees, validate=validate)
        quantized = bool(self.knobs.get("quantize_int8"))
        if quantized:
            stacked = quantize_llama_params(stacked)
        if kv_page_size > 0 and not kv_pages:
            kv_pages = _default_kv_pages(max_slots,
                                         int(self.knobs["max_len"]),
                                         int(kv_page_size))
        module = self._module(quantized=quantized,
                              n_adapters=len(trees),
                              kv_page_size=kv_page_size,
                              kv_pages=kv_pages,
                              paged_kernel=(paged_kernel
                                            if kv_page_size > 0
                                            else None))
        return self._build_text_engine(
            module, stacked, max_slots, max_new_tokens, steps_per_sync,
            prefill_chunk, speculate_k, host_kv_pages=host_kv_pages)

    def dump_parameters(self) -> Dict[str, Any]:
        assert self._params is not None, "model is not trained"
        meta: Dict[str, Any] = {"id2tok": {str(k): v
                                           for k, v in
                                           self._id2tok.items()}}
        if self._bpe:
            # the merge table travels WITH the weights: a serving host
            # can reconstruct the exact tokenizer without the artifact
            # file (tokenizer_path may not exist there)
            meta["bpe_merges"] = [list(m) for m in self.tokenizer.merges]
        return {
            "params": jax.tree_util.tree_map(np.asarray, self._params),
            "meta": meta,
        }

    def load_parameters(self, params: Dict[str, Any]) -> None:
        self._id2tok = {int(k): v
                        for k, v in params["meta"]["id2tok"].items()}
        merges = params["meta"].get("bpe_merges")
        if merges is not None:
            from rafiki_tpu.data.bpe import ByteBPETokenizer

            self.tokenizer = ByteBPETokenizer(
                [tuple(int(x) for x in m) for m in merges])
        self._params = jax.tree_util.tree_map(jnp.asarray, params["params"])
        self._qparams = None
        self._fwd = None


if __name__ == "__main__":  # reference-style self-test block
    import tempfile

    from rafiki_tpu.utils.platform import apply_platform_env

    apply_platform_env()  # the shared compile cache

    from rafiki_tpu.data import generate_text_classification_dataset
    from rafiki_tpu.model import test_model_class

    with tempfile.TemporaryDirectory() as d:
        train_p = f"{d}/train.jsonl"
        val_p = f"{d}/val.jsonl"
        generate_text_classification_dataset(train_p, 192, seed=0)
        generate_text_classification_dataset(val_p, 48, seed=1)
        preds = test_model_class(
            LlamaLoRA, TaskType.LANGUAGE_MODELING, train_p, val_p,
            queries=["tok1 tok2 tok3"],
            knobs={"max_epochs": 6, "vocab_size": 1 << 14, "hidden_dim": 64,
                   "depth": 2, "n_heads": 4, "kv_ratio": 2, "lora_rank": 4,
                   "max_len": 32, "model_parallel": 1,
                   "learning_rate": 1e-2, "batch_size": 16,
                   "quick_train": False, "share_params": False})
        print("continuation:", preds[0])
