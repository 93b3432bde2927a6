"""Continuous-batching decode engine for causal-LM serving.

Parity target: BASELINE.md config #5's "continuous-batch serving via
Predictor". The reference serves classifications by batching queued
queries per forward (SURVEY.md §3.3); generation needs more — requests
of different lengths must share the accelerator *mid-flight*. TPU-first
design:

- **One compiled step, fixed slots.** The engine owns a KV cache with
  ``max_slots`` rows and steps ALL slots in one jitted program per
  token. Static shapes: admission/completion never recompiles anything —
  a new request just changes the host-side slot table and the (tiny)
  per-slot token/position vectors fed each step.
- **Per-slot positions.** Each slot runs at its own depth (one mid-
  prompt, one mid-generation); the decoder writes each slot's KV at its
  own index (``models/llama_lora.py`` ``_DecoderAttention`` decode
  branch) and masks keys past it, so stale cache rows from a previous
  occupant are unreachable (a fresh slot starts at position 0).
- **Admission at step boundaries.** Between steps the host pulls queued
  requests into free slots: unified prefill/decode — a slot consumes
  its prompt token-by-token through the same step program, then flips
  to feeding back its own argmax. That is lockstep continuous batching:
  no separate prefill program, no pipeline bubble between phases.
- Completed slots detokenize/reply and free immediately; the step loop
  only runs while any slot is live, so an idle engine costs nothing.
- **Paged KV (block tables).** A module built with ``kv_page_size > 0``
  stores each layer's K/V in a ``(kv_pages, page_size, heads, dh)``
  POOL; every slot maps logical pages → pool pages through a small
  host-owned int32 table fed to each compiled call (static shape, so
  admission/allocation never recompiles). Pages are allocated lazily
  as a slot's position crosses page boundaries and freed at
  completion, so cache HBM and admission scale with LIVE tokens, not
  ``max_slots × max_len``. Admission reserves each request's
  worst-case pages (prompt + max_new, NOT max_len) up front — the
  accounting that makes mid-flight allocation infallible and
  backpressure deadlock-free: a request that does not fit the pool
  WAITS in the queue (``admission_stalls``) until completions free
  reservations, instead of being refused while memory sits idle.
  Token-bit-exact with the contiguous layout: attention gathers the
  row's pages back into logical order and the same position mask
  applies (stale bytes in unallocated/scratch pages sit past it).


The engine is token-level and model-agnostic: it needs a flax module
with the ``decode=True`` cache protocol. Text encode/detok is the
caller's job (``LlamaLoRA.make_decode_engine`` wires its tokenizer).
"""

from __future__ import annotations

import functools
import threading
import time
from dataclasses import dataclass, field
from typing import (Any, Callable, Dict, List, Optional, Sequence,
                    Tuple)

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.metrics import StatsMap
from ..obs.trace import SPANS
from ..ops.latent_attention import packed_key_write
from ..ops.paged_attention import (resolve_paged_kernel,
                                   resolve_paged_window_kernel)
from .kv_tier import HostPageTier
from .kv_transfer import (LAYOUT_PAGED, LAYOUT_ROWS, check_kv_blob,
                          leaf_signature, make_kv_blob)
from .slo import (DEFAULT_SLO, ClassQueue, evictable_occupants,
                  normalize_slo, preemption_victim, slo_priority)

# Speculation break-even (tokens per verify call) and how many scan
# calls to wait before re-probing a gated-off speculator. ~1.5 means a
# draft window must beat single-token decoding by 50% to keep the
# verify path; re-probing is cheap (one call) and content can change.
SPEC_MIN_TOKENS_PER_CALL = 1.5
# draft-MODEL speculation pays two extra device dispatches per verify
# (draft scan + verify mirror) plus a mirror per plain scan, so its
# break-even floor sits higher than free host-side n-gram drafting
SPEC_MIN_TOKENS_PER_CALL_DRAFT = 2.2
SPEC_REPROBE_CALLS = 32
#: rows of a paged engine's prefill programs: a chunk call computes this
#: many rows x ``prefill_chunk`` tokens, dealt to the lanes that HAVE
#: prompt left (a long prompt takes several rows: its consecutive
#: chunks) instead of ``max_slots`` rows of which one or two are real.
#: What does not fit takes the next call. 8 x 32 rows still fill the
#: MXU's tiles, and a call's cost is then the weights' streaming, about
#: a decode step
PREFILL_LANES = 8
#: generated-token interval between decode_mark trace spans per slot —
#: coarse enough to stay off the hot path, fine enough that a stalled
#: generation shows WHERE it stalled in /debug/requests
SPAN_DECODE_MARK_EVERY = 32
# EMA decay for tokens-per-verify-call: 0.7 gates hopeless content off
# after ~2 zero-acceptance calls (start is just above the floor) while
# a healthy acceptance stream keeps the path on indefinitely
SPEC_EMA_DECAY = 0.7


@dataclass
class _Slot:
    request_id: Any
    prompt: np.ndarray          # (p,) int32, valid tokens only
    max_new: int
    temperature: float = 0.0    # <= 0 → greedy
    top_k: int = 0              # <= 0 → no top-k cut
    top_p: float = 1.0          # >= 1 → no nucleus cut
    seed: int = 0               # with (position) → the sample's PRNG key
    eos_id: Optional[int] = None  # emitting this token ends the request
    adapter_id: int = 0         # multi-adapter engines: which fine-tune
    slo: str = DEFAULT_SLO      # admission class (interactive first)
    seq: int = 0                # arrival order; preemption evicts the
    #                             YOUNGEST lowest-class victim
    n_consumed: int = 0         # tokens fed to the model so far
    generated: List[int] = field(default_factory=list)
    #: tokens generated BEFORE a preemption (re-ingested as prompt on
    #: resume, but still part of this request's OUTPUT): poll/
    #: poll_partial present prior + generated, so a preempted request
    #: resumes token-exact with nothing duplicated or lost
    prior: List[int] = field(default_factory=list)
    n_streamed: int = 0         # generated tokens already poll_partial'd
    first_tokened: bool = False  # first_token span already emitted
    #: admitted via the aging promotion (served ahead of waiting
    #: higher-priority work): immune to preemption — evicting it on
    #: the next interactive arrival would starve exactly the way
    #: aging exists to prevent
    shielded: bool = False
    #: disaggregated serving (prefill role): stop after chunked
    #: prefill and surface the slot's KV pages via ``poll_kv`` instead
    #: of generating — the shipment a decode-role worker installs
    prefill_only: bool = False
    #: disaggregated serving (decode role): a validated KV blob whose
    #: rows are installed at seat time, fast-forwarding the slot past
    #: the prefill the shipping worker already did
    kv_import: Optional[Dict[str, Any]] = None


class _Parked:
    """A slot suspended to the host KV tier: its lane is free, its
    pages live wherever the allocator put them (per logical page:
    still-resident HBM pool page, or a host-tier page), and every host
    mirror needed to reseat it rides along. Parking loses NO progress —
    unlike SLO preemption there is no re-prefill on resume; the
    restored pages ARE the KV the slot had."""

    __slots__ = ("slot", "pos", "tok", "stop_pos", "n_res", "pages",
                 "park_seq")

    def __init__(self, slot: _Slot, pos: int, tok: int, stop_pos: int,
                 n_res: int, pages: List[Tuple[str, int]],
                 park_seq: int) -> None:
        self.slot = slot
        self.pos = pos
        self.tok = tok
        self.stop_pos = stop_pos
        self.n_res = n_res
        self.pages = pages      # [("hbm", pool_page) | ("host", hp)]
        self.park_seq = park_seq

    def host_ids(self) -> List[int]:
        return [p for loc, p in self.pages if loc == "host"]

    def hbm_ids(self) -> List[int]:
        return [p for loc, p in self.pages if loc == "hbm"]


def _spanned(name: str) -> Callable:
    """Run a method inside one phase span ``name`` (``obs.SPANS``)."""
    def deco(fn: Callable) -> Callable:
        @functools.wraps(fn)
        def wrapper(*args: Any, **kwargs: Any) -> Any:
            with SPANS.span(name):
                return fn(*args, **kwargs)
        return wrapper
    return deco


def _serving_tree(module: Any, params: Any) -> Any:
    """``params`` (or ``None``) in ``module``'s serving form."""
    from ..models.llama_lora import serving_llama_params

    return serving_llama_params(params, getattr(module, "dtype", None))


def _empty_cache(module: Any, batch: int) -> Any:
    """``module``'s decode cache for ``batch`` rows, all zeros: the
    SHAPES come from ``jax.eval_shape`` over ``module.init`` — nothing is
    drawn, so a module whose f32 weights would not fit the device still
    gets its cache — and the leaves are allocated as zeros, which is
    what every cache variable initialises to."""
    shapes = jax.eval_shape(
        lambda: module.init(jax.random.PRNGKey(0),
                            jnp.zeros((batch, 1), jnp.int32),
                            decode=True)["cache"])
    return jax.tree_util.tree_map(
        lambda leaf: jnp.zeros(leaf.shape, leaf.dtype), shapes)


def _kernel_mode(module: Any, paged: bool) -> int:
    """The ``paged_kernel_mode`` gauge: which decode legs walk the
    block table in a Pallas kernel (0 none, 1 the single-token step, 2
    windows too). A module that dispatches its own attention says what
    its step and its windows really take (``paged_kernel_mode()``);
    otherwise the ``paged_kernel`` flag resolved against the backend,
    the ops-level rules ``_DecoderAttention`` follows."""
    own = getattr(module, "paged_kernel_mode", None)
    if callable(own):
        return int(own()) if paged else 0
    flag = getattr(module, "paged_kernel", None)
    if not (paged and resolve_paged_kernel(flag)):
        return 0
    return 2 if resolve_paged_window_kernel(flag) else 1


class DecodeEngine:
    """Slot-based continuous batching over one compiled decode step.

    ``steps_per_sync`` fuses K decode steps into ONE device program
    (``lax.scan``) with on-device input selection (next prompt token
    while prefilling, argmax feedback while generating). The host then
    pays one dispatch + one sync per K tokens instead of per token —
    the difference between per-token round-trips and streaming on a
    remote-execution TPU backend. Admission still happens at fused-step
    boundaries, so K trades a little admission latency for dispatch
    amortization. K=1 reproduces classic lockstep exactly; any K
    produces identical tokens (the selection logic is the same math).
    """

    def __init__(self, module: Any, params: Any, max_slots: int,
                 max_len: int, steps_per_sync: int = 4,
                 prefill_chunk: int = 32, speculate_k: int = 0,
                 draft: Optional[Tuple[Any, Any]] = None,
                 host_kv_pages: int = 0, table_floor: int = 1) -> None:
        self.module = module
        self.B = int(max_slots)
        self.L = int(max_len)
        self.K = max(1, int(steps_per_sync))
        #: cache leaves the module keeps a SLOT (a recurrent state: one
        #: row a slot and a scratch row), not a position. Such a module
        #: is told, in every decode-path call, which slot each row
        #: belongs to and how many of the row's tokens are real
        #: (``slot_ids``, ``row_tokens``); what cannot carry that state
        #: yet is refused by name, here or at the call
        self._slot_state = tuple(getattr(module, "slot_state", ()))
        #: of those, the rings of keys and values that window layers
        #: keep a slot (``ops/window_attention.py``): counted apart
        self._window_state = tuple(getattr(module, "window_state", ()))
        if self._slot_state:
            for what, asked in (
                    ("a host KV tier (host_kv_pages > 0)", host_kv_pages),
                    ("a draft model (draft=)", draft is not None),
                    ("speculation (speculate_k >= 2)",
                     int(speculate_k) >= 2),
                    ("a contiguous cache (kv_page_size = 0)",
                     not int(getattr(module, "kv_page_size", 0) or 0))):
                if asked:
                    raise ValueError(self._no_slot_state(what))
        #: >=2 enables greedy speculative decoding (prompt-lookup
        #: drafting, no draft model): each fused call verifies
        #: ``speculate_k - 1`` host-drafted tokens plus the model's own
        #: next token in ONE multi-token cache step, emitting 1..k
        #: tokens per call. Greedy-lossless: every emitted token is the
        #: model's argmax given its prefix, so outputs are identical to
        #: plain decoding — speculation only changes how many argmaxes
        #: one dispatch retires. Sampling slots fall back to the scan.
        self.spec_k = 0 if int(speculate_k) < 2 else min(int(speculate_k),
                                                         self.L)
        # acceptance gating: a verify call emits 1..k tokens for ONE
        # dispatch, while the fused scan emits K for one dispatch — at
        # low draft acceptance speculation would pay up to K× the
        # dispatch overhead it is meant to save. Track an EMA of tokens
        # emitted per speculative call; below the break-even floor the
        # engine falls back to the scan and re-probes periodically
        # (drafting quality is content-dependent and can recover).
        #: the EMA seeds just above the applicable floor AFTER the
        #: draft setup below (good content proves itself on call 1;
        #: bad content is gated after ~2 calls)
        self._spec_idle = 0  # scan calls since the last spec attempt
        #: prompt tokens ingested per fused prefill call (1 disables the
        #: separate prefill program — prompts then stream token-by-token
        #: through the decode scan like round-3 did). C-token prefill
        #: turns B (1, d)-matvec steps into (C, d) matmuls the MXU can
        #: tile, and pays 1/C as many dispatches for prompt ingestion.
        self.C = max(1, min(int(prefill_chunk), self.L))
        self._slots: List[Optional[_Slot]] = [None] * self.B
        #: class-aware admission queue (interactive > batch >
        #: background, FIFO within class, aging so background never
        #: starves). Caller-locked: every touch happens under _lock.
        self._cq = ClassQueue()
        self._seq = 0  # arrival stamp: preemption evicts youngest
        self._done: List[Tuple[Any, List[int]]] = []
        self._lock = threading.Lock()
        # host mirrors of the per-slot device inputs; prompts ride to the
        # device so mid-scan prefill continues without host involvement
        self._tok = np.zeros((self.B,), np.int32)
        self._pos = np.zeros((self.B,), np.int32)
        self._prompt_buf = np.zeros((self.B, self.L), np.int32)
        self._prompt_len = np.ones((self.B,), np.int32)
        self._stop_pos = np.zeros((self.B,), np.int32)
        # per-slot sampling config (device operands every fused step)
        self._temp = np.zeros((self.B,), np.float32)
        self._topk = np.zeros((self.B,), np.int32)
        self._topp = np.ones((self.B,), np.float32)
        self._seed = np.zeros((self.B,), np.int32)
        #: multi-adapter serving (module.n_adapters > 0): per-slot
        #: adapter selection, a device operand like the sampling knobs
        self.n_adapters = int(getattr(module, "n_adapters", 0) or 0)
        self._aid = np.zeros((self.B,), np.int32)
        #: device-resident prompt copy, refreshed only on admission — the
        #: (B, L) buffer must not ride host→device on every dispatch
        self._prompt_dev: Optional[jnp.ndarray] = None
        #: paged KV (module.kv_page_size > 0): host-owned page tables +
        #: free-list allocator over the module's (kv_pages, page_size,
        #: …) per-layer pools. Pool page 0 is the SCRATCH page — idle/
        #: free lanes write their idempotent re-feeds there and no slot
        #: ever owns it, so a zeroed table row is always safe to step.
        self.page_size = int(getattr(module, "kv_page_size", 0) or 0)
        self.paged = self.page_size > 0
        if self.paged:
            if self.L % self.page_size:
                raise ValueError(f"kv_page_size {self.page_size} must "
                                 f"divide max_len {self.L}")
            self.n_pages = int(getattr(module, "kv_pages", 0) or 0)
            if self.n_pages < 2:
                raise ValueError("paged KV needs kv_pages >= 2 (scratch"
                                 " page + at least one usable page)")
            self._n_table = self.L // self.page_size  # table width
            #: the narrowest table operand a call is handed (see
            #: :meth:`_live_table_width`): every width from here up is a
            #: compilation of each program, so a deep model whose traffic
            #: keeps some slot long anyway starts at the width it would
            #: reach — the whole table gives ONE shape a program
            self._table_floor = max(1, min(int(table_floor), self._n_table))
            #: LIFO free list over pages 1..n_pages-1; reservation
            #: accounting (below) guarantees pops never fail mid-flight
            self._free_pages = list(range(self.n_pages - 1, 0, -1))
            self._n_alloc = np.zeros((self.B,), np.int32)
            #: worst-case pages reserved per slot at admission — the
            #: invariant sum(_n_res) <= budget (HBM usable pages, plus
            #: the host tier when one is attached) is what makes lazy
            #: allocation infallible and queue waits deadlock-free
            self._n_res = np.zeros((self.B,), np.int32)
            self._res_total = 0
        else:
            self._n_table = 1  # dummy operand keeps signatures uniform
        #: host-RAM KV page tier (``host_kv_pages > 0``, paged engines
        #: only): the admission budget becomes HBM + host pages. Cold
        #: pages — whole slots parked to make room for hotter work —
        #: evict to a pinned-host pool asynchronously and prefetch
        #: back ahead of the step that resumes them, so serviceable
        #: concurrency stops being hard-capped by HBM while the
        #: compiled step only ever touches HBM-resident pages.
        self.host_pages = int(host_kv_pages)
        if self.host_pages and not self.paged:
            raise ValueError("host_kv_pages requires a paged engine "
                             "(kv_page_size > 0): pages are the "
                             "tier's transfer unit")
        self.tier: Optional[HostPageTier] = None
        #: parked slots by a monotonic park key, insertion-ordered
        self._parked: Dict[int, _Parked] = {}
        self._park_seq = 0
        #: which decode legs walk the block table in a Pallas kernel
        #: (``_kernel_mode``): ``paged_kernel_active``: the s==1 step;
        #: ``paged_kernel_windowed``: multi-token windows on top (chunked
        #: prefill + speculative verify). Surfaced as the
        #: ``paged_kernel_mode`` gauge (0 = gather / contiguous, 1 =
        #: step-only, 2 = windowed) so kernel-vs-gather fleets — and
        #: step-only fleets — are tellable apart on /metrics.
        self.paged_kernel_mode = _kernel_mode(module, self.paged)
        self.paged_kernel_active = self.paged_kernel_mode >= 1
        self.paged_kernel_windowed = self.paged_kernel_mode >= 2
        self._ptab = np.zeros((self.B, self._n_table), np.int32)
        self._ptab_dev = jnp.asarray(self._ptab)
        self._ptab_dev_width = self._n_table
        self._ptab_dirty = False
        self._cache = _empty_cache(module, self.B)
        #: int32 counts the module's step and prefill programs hand back
        #: beside their outputs (``module.device_counters`` names them,
        #: ``module.book_device_counters`` adds a pulled vector to the
        #: stats), carried from call to call ON the device and pulled
        #: with the decode output — never a sync of their own
        self._count_names = tuple(getattr(module, "device_counters", ()))
        self._counts_zero = (jnp.zeros((len(self._count_names),),
                                       jnp.int32)
                             if self._count_names else None)
        self._counts_dev = self._counts_zero
        # two compiled step programs: greedy-only traffic must not pay
        # the sampler's (B, vocab) sort per token (measured 18x slower
        # generation on CPU when it rode every step). The host picks per
        # fused call based on the live slots' temperatures.
        self._step_fns = {False: _make_step(module, self.B, self.K, False),
                          True: _make_step(module, self.B, self.K, True)}
        #: rows a prefill call computes. Paged engines deal
        #: ``PREFILL_LANES`` rows to the lanes with prompt left (the
        #: pool is not laid out by slot, and a row whose table is all
        #: zeros writes to the scratch page); contiguous caches are rows
        #: BY slot, and a draft model's mirror pass is compiled
        #: slot-wide: both keep one row a slot (0 = not gathered)
        self._prefill_lanes = (
            min(self.B, PREFILL_LANES)
            if self.paged and not (draft is not None and self.spec_k)
            else 0)
        n_rows = self._prefill_lanes or self.B
        #: a module whose window layers keep a ring of keys a slot says
        #: whether the ring takes what ONE call may write for a slot
        #: (a prompt's consecutive chunks, dealt to the call's rows)
        ring_holds = getattr(module, "ring_holds_call", None)
        if ring_holds is not None:
            ring_holds(max(self._prefill_lanes, 1) * self.C)
        self._prefill_fn = (_make_prefill(module, n_rows, self.C)
                            if self.C > 1 else None)
        #: narrow twin of the prefill program for short remainders: a
        #: 1-token admission walk must not pay a C-wide (B, C) matmul
        #: — at C=32 that call costs about one fused decode step, so
        #: every short-prompt admission used to stall all live streams
        #: by a step. Walks ≤ this width run the narrow program.
        self._small_c = 4
        self._prefill_fn_small = (
            _make_prefill(module, n_rows, self._small_c)
            if self._prefill_fn is not None and self.C > self._small_c
            else None)
        self._verify_fn = (_make_verify(module, self.B, self.spec_k)
                           if self.spec_k else None)
        #: draft-MODEL speculation (``draft=(module, params)``, a
        #: smaller model sharing the vocab): replaces prompt-lookup
        #: drafting with real draft-model continuations. The draft
        #: keeps a slot-parallel KV cache synced by construction —
        #: every target cache advance (chunked prefill, fused scan,
        #: verify) is mirrored with one multi-token draft pass over
        #: the ACTUALLY-CONSUMED tokens, and accepted draft rows are
        #: definitionally the accepted tokens' KV (greedy acceptance
        #: means draft prediction == accepted token), so rejected rows
        #: are the standard unreachable-then-rewritten case. Greedy-
        #: lossless like prompt-lookup: the verify step is target-
        #: authoritative either way.
        self.draft_module, draft_params = draft or (None, None)
        self.draft_params = _serving_tree(self.draft_module, draft_params)
        self._draft_cache = None
        if self.draft_module is not None and self.spec_k:
            self._draft_cache = _empty_cache(self.draft_module, self.B)
            # draft phase: k-1 greedy steps with argmax feedback
            self._draft_scan = _make_step(self.draft_module, self.B,
                                          self.spec_k - 1, False)
            # mirror passes: multi-token KV population (prefill-shaped)
            self._draft_sync_k = _make_prefill(self.draft_module,
                                               self.B, self.K)
            self._draft_sync_c = (_make_prefill(self.draft_module,
                                                self.B, self.C)
                                  if self.C > 1 else None)
            # verify mirror (chunk = spec_k): writes the verify call's
            # consumed inputs [tok, drafts] into the draft cache —
            # idempotent for rows the draft scan already wrote, and it
            # adds the final row the scan stops short of (needed when
            # a window is FULLY accepted: that row's KV must exist for
            # the draft's later attention)
            self._draft_sync_v = _make_prefill(self.draft_module,
                                               self.B, self.spec_k)
        #: draft-cost-aware break-even floor for the acceptance gate
        self._spec_floor = (SPEC_MIN_TOKENS_PER_CALL_DRAFT
                            if self._draft_cache is not None
                            else SPEC_MIN_TOKENS_PER_CALL)
        self._spec_ema = self._spec_floor + 0.5
        #: False while the gate is off and scan mirrors are skipped —
        #: a re-probe first rebuilds the draft cache from the slots'
        #: accepted contexts (cheaper than mirroring every gated scan)
        self._draft_synced = True
        #: registered shared prefix (system prompt): token ids, its
        #: precomputed 1-row KV cache, and its length. Requests whose
        #: prompt extends it skip its prefill — admission copies the
        #: snapshot rows into the slot's cache (bandwidth, not compute).
        #: one registered prefix PER ADAPTER (multi-tenant system
        #: prompts — a prefix's KV is a function of the adapter that
        #: computed it); single-adapter engines use key 0
        self._prefixes: Dict[int, Dict[str, Any]] = {}
        #: served-traffic counters + pool gauges, as a race-free
        #: ``obs.StatsMap`` (dict reads everywhere keep working; writes
        #: go through inc/set/max_set — see the obs-unregistered-metric
        #: lint rule). Gauge names are load-bearing: the worker, the
        #: /health aggregation, and the dashboard all key on them.
        self.stats = StatsMap({
            "steps": 0, "tokens_generated": 0, "requests_done": 0,
            "max_concurrent": 0, "prefill_calls": 0,
            "prefill_tokens": 0, "spec_calls": 0, "spec_drafted": 0,
            "spec_accepted": 0, "prefix_hits": 0, "prefix_tokens": 0,
            "spec_draft_model_calls": 0, "draft_resyncs": 0,
            # paged-KV pool observability (all 0 on contiguous
            # engines): current/peak pages physically allocated, the
            # usable pool size, and how many step() calls found the
            # head-of-queue request unable to reserve its worst case
            # (backpressure waits, not refusals)
            "kv_pages_used": 0, "kv_pages_high_water": 0,
            "kv_pages_total": (self.n_pages - 1 if self.paged else 0),
            "admission_stalls": 0,
            # SLO plane: mid-flight evictions of lower-class work so
            # an interactive request could admit (the victim resumes
            # token-exact from its re-queued prefix), aging promotions
            # (background served ahead of waiting interactive so it
            # never starves), and live per-class queue depths
            "preemptions": 0, "slo_aged_promotions": 0,
            "queued_interactive": 0, "queued_batch": 0,
            "queued_background": 0,
            # host-RAM KV tier (all 0 on untiered engines): host pool
            # occupancy, pages evicted to host over the engine's life,
            # prefetch effectiveness (a miss = the unpark had to pull
            # pages inline), raw bytes moved in both directions, and
            # live suspended-slot counts
            "kv_host_pages_used": 0,
            "kv_host_pages_total": self.host_pages,
            "kv_evictions_total": 0, "kv_prefetch_hits": 0,
            "kv_prefetch_misses": 0, "kv_transfer_bytes_total": 0,
            "kv_parked_slots": 0, "kv_unparks_total": 0,
            # disaggregated prefill/decode: KV page shipments produced
            # (prefill role) and installed (decode role) by this engine
            "kv_exports": 0, "kv_imports": 0,
            # which decode legs the Pallas block-table kernels serve:
            # 0 = page gather / contiguous, 1 = step-only (s==1 hot
            # loop; windows on the gather — the
            # RAFIKI_PAGED_KERNEL_WINDOWS=0 escape hatch), 2 = windowed
            # (chunked prefill + speculative verify too). The token
            # counters say how much traffic each kernel actually
            # carried: window tokens count prefill ingestion plus
            # verify-window rows, step tokens count fused-scan rows.
            "paged_kernel_mode": self.paged_kernel_mode,
            "paged_kernel_step_tokens": 0,
            "paged_kernel_window_tokens": 0,
            # bytes of the tree the step programs take (``params``: the
            # compute-dtype serving form, not the tree it was made from)
            "weight_bytes": 0,
            # bytes one cached position costs over all layers: the
            # cache's own leaves over the positions they hold (pool
            # pages x page size, or slots x max_len)
            "kv_pool_bytes_per_token": self._pool_bytes_per_token(),
            # bytes ONE slot's recurrent state costs over all layers:
            # the cache leaves the module indexes by slot (slots + a
            # scratch row); 0 for a module that keeps none
            "ssm_state_bytes_per_slot": self._cache_bytes(tuple(
                name for name in self._slot_state
                if name not in self._window_state)) // (self.B + 1),
            # bytes ONE slot's rings of keys and values cost over all
            # window layers (slots + a scratch row); 0 without such
            "window_kv_bytes_per_slot":
                self._cache_bytes(self._window_state) // (self.B + 1),
            **{name: 0 for name in self._count_names},
            # the host's share of the loop, counted where the phase
            # spans are cut (docs/observability.md "Phase spans"):
            # step() calls, nanoseconds inside them outside the output
            # sync (admission, operand building, launches, harvest),
            # and nanoseconds blocked in that sync waiting on the device
            "turns": 0, "turn_host_ns": 0, "sync_wait_ns": 0})
        self.params = params
        if self.host_pages:
            self.tier = HostPageTier(self.host_pages, self.stats)
        #: finished prefill-only shipments awaiting poll_kv
        self._done_kv: List[Tuple[Any, Dict[str, Any]]] = []
        #: optional request-lifecycle hook ``(event, request_id, attrs)``
        #: — the inference worker wires it into its trace buffer and
        #: latency histograms (TTFT, time-in-queue). Events: admitted,
        #: prefill, first_token, decode_mark (every
        #: ``SPAN_DECODE_MARK_EVERY`` generated tokens), done. Every
        #: event but decode_mark is also an instant ``req.<event>`` in
        #: the phase-span ring (``obs.SPANS``), sink or no sink, after
        #: the ``req.submitted`` that ``submit`` writes there.
        self.span_sink: Optional[Callable[[str, Any, Dict[str, Any]],
                                          None]] = None
        #: the open ``engine.turn`` span's seq (0 between turns): the
        #: parent of the request instants a turn emits
        self._turn_seq = 0
        #: nanoseconds of the open turn spent in ``engine.sync_wait``
        self._turn_sync_ns = 0

    def _no_slot_state(self, what: str) -> str:
        return (f"{type(self.module).__name__} keeps per-slot state "
                f"{self._slot_state}, which {what} cannot carry yet: "
                "pages can be parked, shipped and shared, a slot's "
                "recurrent state or ring of keys cannot")

    def _cache_bytes(self, names: Tuple[str, ...],
                     named: bool = True) -> int:
        """Bytes of the cache's leaves called one of ``names`` (``named``
        False: of all the others)."""
        return sum(
            int(leaf.nbytes) for path, leaf in
            jax.tree_util.tree_flatten_with_path(self._cache)[0]
            if (getattr(path[-1], "key", None) in names) == named)

    def _pool_bytes_per_token(self) -> int:
        """Of the leaves indexed by position: those no slot keeps."""
        positions = (self.n_pages * self.page_size if self.paged
                     else self.B * self.L)
        return self._cache_bytes(self._slot_state, named=False) // positions

    @property
    def params(self) -> Any:
        """The tree the engine's programs read: the SERVING form of what
        was assigned (``serving_llama_params`` for the module's compute
        dtype), cast once at assignment — never inside a dispatch. The
        engine keeps no reference to the tree it was made from, so a
        caller that drops its f32 leaves gets their memory back."""
        return self._params

    @params.setter
    def params(self, tree: Any) -> None:
        # drop the held tree BEFORE casting the new one: f32 + two
        # compute-dtype copies of a model do not fit beside each other
        self._params = None
        self._params = _serving_tree(self.module, tree)
        self.stats.set("weight_bytes", sum(
            int(x.nbytes) for x in jax.tree_util.tree_leaves(self._params)))

    # ---- submission / results (thread-safe: worker loop vs callers) ----
    @_spanned("engine.submit")
    def submit(self, request_id: Any, prompt_ids: np.ndarray,
               max_new: int, temperature: float = 0.0, top_k: int = 0,
               top_p: float = 1.0, seed: int = 0,
               eos_id: Optional[int] = None,
               adapter_id: int = 0, slo: str = "",
               prefill_only: bool = False,
               kv_import: Optional[Dict[str, Any]] = None) -> None:
        """Queue a request. ``prompt_ids``: 1-D valid tokens (≥1); the
        prompt + generation must fit the cache (truncated to fit).

        Sampling is per-request and fully seeded: ``temperature <= 0``
        is greedy; otherwise top-k/top-p-filtered categorical sampling
        whose PRNG key is ``fold_in(PRNGKey(seed), position)`` — the
        draw at each position is a pure function of (seed, position),
        independent of batch composition, slot index, or
        ``steps_per_sync``, so generations are reproducible under any
        serving load.

        ``eos_id``: emitting this token finishes the request early (the
        EOS itself is dropped from the reply; tokens a fused call
        computed past it are discarded host-side and their cache rows
        are unreachable-then-rewritten, the standard slot-reuse
        invariant).

        ``adapter_id`` (multi-adapter engines only): which stacked
        fine-tune this request decodes under. Out-of-range ids raise
        ``ValueError`` — silently serving a DIFFERENT fine-tune would
        be a correct-looking wrong answer (each adapter is a different
        trial/tenant). Ignored on single-adapter engines.

        ``slo`` (``interactive`` / ``batch`` / ``background``, default
        interactive): admission class. Interactive admits first (FIFO
        within a class, aging so nothing starves) and may PREEMPT
        lower-class occupants when the pool/slots are full — the
        victim's pages free and it resumes token-exact later from its
        re-queued prefix. Unknown classes raise ``ValueError``."""
        if self._slot_state and (prefill_only or kv_import is not None):
            raise ValueError(self._no_slot_state(
                "KV shipping (submit(prefill_only=) / submit(kv_import=))"))
        prompt = np.asarray(prompt_ids, np.int32).ravel()
        max_new = max(1, min(int(max_new), self.L - 1))
        prompt = prompt[:max(1, self.L - max_new)]
        aid = self._check_adapter_id(adapter_id)
        cls = normalize_slo(slo)
        if kv_import is not None:
            # validated HERE (caller thread) so a bad shipment is a
            # structured refusal the worker can degrade on — never a
            # shape error escaping from the step thread mid-install
            flat = jax.tree_util.tree_leaves(self._cache)
            cov = int(kv_import.get("covered", 0) or 0) \
                if isinstance(kv_import, dict) else 0
            if self.paged:
                sig = [[list(c.shape[1:]), str(c.dtype)] for c in flat]
                lead = ((cov - 1) // self.page_size + 1) if cov else 0
            else:  # rows layout: leaves are (covered, heads, dh)
                sig = [[list(c.shape[2:]), str(c.dtype)] for c in flat]
                lead = cov
            kv_import = check_kv_blob(
                kv_import,
                layout=LAYOUT_PAGED if self.paged else LAYOUT_ROWS,
                page_size=self.page_size, expect_sig=sig,
                expect_leading=lead,
                prompt_len=len(prompt), adapter_id=aid)
        if self.paged:
            # a request whose worst case exceeds what can ever be
            # HBM-RESIDENT could never take a step — it would stall
            # the queue forever. Refuse loudly here; everything
            # smaller waits its turn (with a host tier the admission
            # BUDGET is larger, but residency is still HBM-bound).
            # Prefill-only work stops at the last prompt token, so its
            # worst case is the prompt walk alone.
            need = self._pages_for(
                max(1, len(prompt) - 1) if prefill_only
                else min(len(prompt) - 1 + max_new, self.L))
            if need > self.n_pages - 1:
                raise ValueError(
                    f"request needs {need} KV pages worst-case but the "
                    f"pool has {self.n_pages - 1} usable pages; raise "
                    "kv_pages or lower max_new/prompt length")
        with self._lock:
            self._seq += 1
            self._cq.push(cls, _Slot(
                request_id, prompt, max_new,
                temperature=float(temperature), top_k=int(top_k),
                top_p=float(top_p), seed=int(seed),
                eos_id=None if eos_id is None else int(eos_id),
                adapter_id=aid, slo=cls, seq=self._seq,
                prefill_only=bool(prefill_only),
                kv_import=kv_import))
        # ring only: the sink is the step thread's, this is a caller's
        SPANS.instant("req.submitted", key=request_id,
                      prompt_tokens=len(prompt), slo=cls)

    def _check_adapter_id(self, adapter_id: int) -> int:
        """Validate a request's adapter selection. Out-of-range ids
        raise — silently serving a DIFFERENT fine-tune would be a
        correct-looking wrong answer (each adapter is a different
        trial/tenant). Single-adapter engines ignore the field."""
        if self.n_adapters <= 0:
            return 0
        aid = int(adapter_id)
        if not 0 <= aid < self.n_adapters:
            raise ValueError(f"adapter_id {aid} out of range for "
                             f"{self.n_adapters}-adapter engine")
        return aid

    # ---- paged-KV allocator (host side, step-thread only: the lock
    # ---- protects queue/slots vs submitters; tables/free list are
    # ---- touched exclusively by the thread driving step()) ----
    def _pages_for(self, stop_pos: int) -> int:
        """Worst-case pages a request can touch: the scan path writes
        positions <= stop_pos - 1, and a speculative verify window can
        overwrite up to ``spec_k - 1`` past it (clamped to the cache).
        Reserved at admission so lazy allocation can never fail and a
        waiting queue can never deadlock."""
        h = min(stop_pos - 1 + (self.spec_k - 1 if self.spec_k else 0),
                self.L - 1)
        return h // self.page_size + 1

    @property
    def _budget_pages(self) -> int:
        """The two-tier admission budget: HBM usable pages plus the
        host tier. Reservations are granted against THIS total — the
        allocator invariant becomes sum(reservations) <= budget, and
        HBM shortfalls are resolved by evicting cold pages to host
        (:meth:`_reclaim_one_hbm_page`), which the invariant proves is
        always possible while any within-reservation growth is
        pending."""
        return self.n_pages - 1 + self.host_pages

    def _ensure_pages_to(self, i: int, last_pos: int,
                         have_lock: bool = False) -> None:
        """Allocate slot ``i``'s logical pages covering positions
        [0, last_pos] — called just before every compiled call with
        that call's write horizon (this is the LAZY part: a slot holds
        pages for where it is, not for max_len). With a host tier an
        empty free list is not failure: cold pages (parked slots
        first, then a freshly-parked victim's) evict to host until the
        growth fits — infallible by the combined-budget reservation
        invariant."""
        need = last_pos // self.page_size + 1
        grew = need > int(self._n_alloc[i])
        while int(self._n_alloc[i]) < need:
            if not self._free_pages:
                # only reachable on tiered engines (the untiered
                # invariant keeps the free list ahead of reservations)
                self._reclaim_one_hbm_page(protect=i,
                                           have_lock=have_lock)
            self._ptab[i, int(self._n_alloc[i])] = self._free_pages.pop()
            self._n_alloc[i] += 1
        if grew:
            self._ptab_dirty = True
            used = self.n_pages - 1 - len(self._free_pages)
            self.stats.set("kv_pages_used", used)
            self.stats.max_set("kv_pages_high_water", used)
            self.stats.set("kv_pages_total", self.n_pages - 1)

    # ---- host-tier mechanics (step thread; the tier's transfer
    # ---- thread only ever touches its own pool/staging state) ----
    def _reclaim_one_hbm_page(self, protect: int,
                              have_lock: bool = False) -> None:
        """Free at least one HBM pool page by evicting a cold page to
        the host tier: parked slots' still-resident pages first
        (coldest — nothing is stepping them), else park a live victim
        (never ``protect``) and evict from it. Raises only on an
        allocator-invariant breach (a bug, not an operating state)."""
        if self.tier is None:
            raise RuntimeError(
                "paged-KV allocator invariant breached: free list "
                "empty inside reservation and no host tier to spill "
                "to")
        if self._evict_parked_pages(limit=1, exclude_key=None):
            return
        j = self._park_victim(protect)
        if j is None:
            raise RuntimeError(
                "paged-KV allocator invariant breached: no free page, "
                "no parked cold page, and no parkable victim")
        self._park_slot(j, have_lock=have_lock)
        if not self._evict_parked_pages(limit=1, exclude_key=None):
            raise RuntimeError(
                "paged-KV allocator invariant breached: host tier "
                "full while within-reservation growth is pending")

    def _evict_parked_pages(self, limit: int,
                            exclude_key: Optional[int]) -> int:
        """Move up to ``limit`` HBM-resident pages of parked slots to
        the host tier (freeing their pool pages), taking from the
        LOWEST-priority / youngest parked slot first — the work least
        likely to resume next. Returns pages moved. The d2h copy runs
        on the tier thread; the freed pool pages are safe to reuse
        immediately (the gather dispatched here orders before any
        later donated step's writes)."""
        moved = 0
        order = sorted(
            (k for k in self._parked if k != exclude_key),
            key=lambda k: (slo_priority(self._parked[k].slot.slo),
                           self._parked[k].slot.seq),
            reverse=True)
        for k in order:
            if moved >= limit:
                break
            rec = self._parked[k]
            hbm = [(t, p) for t, (loc, p) in enumerate(rec.pages)
                   if loc == "hbm"]
            if not hbm:
                continue
            take = hbm[-(limit - moved):]  # tail pages: evict the
            #                                farthest-ahead KV first so
            #                                partial restores refill in
            #                                logical order
            host_ids = self.tier.alloc(len(take))
            if host_ids is None:
                if self.tier.free_pages() == 0:
                    break
                host_ids = self.tier.alloc(self.tier.free_pages())
                take = take[-len(host_ids):]
            pool_ids = [p for _t, p in take]
            idx = jnp.asarray(pool_ids, jnp.int32)
            leaves = [c[idx] for c in
                      jax.tree_util.tree_leaves(self._cache)]
            self.tier.evict_submit(host_ids, leaves)
            self.tier.drop_staged(k)  # staging for the old id set is
            #                           stale now; the prefetcher will
            #                           re-stage the grown set
            for (t, _p), h in zip(take, host_ids):
                rec.pages[t] = ("host", int(h))
            self._free_pages.extend(pool_ids)
            self._ptab_dirty = True
            moved += len(take)
        if moved:
            self.stats.set("kv_pages_used",
                           self.n_pages - 1 - len(self._free_pages))
        return moved

    def _park_victim(self, protect: int) -> Optional[int]:
        """The live slot to suspend when HBM must shrink: lowest
        class, youngest — mirroring the preemption order, but parking
        is allowed across classes and shields because NO progress is
        lost (the slot resumes from its exact KV, no re-prefill)."""
        cands = [j for j in range(self.B)
                 if j != protect and self._slots[j] is not None]
        if not cands:
            return None
        return max(cands, key=lambda j: (
            slo_priority(self._slots[j].slo), self._slots[j].seq))

    def _park_slot(self, j: int, have_lock: bool = False) -> None:
        """Suspend live slot ``j`` to the parked set: lane freed, host
        mirrors captured, pages kept (initially all HBM-resident —
        eviction moves them to host on demand). The reservation stays
        counted (the slot is still admitted work)."""
        slot = self._slots[j]
        n = int(self._n_alloc[j])
        self._park_seq += 1
        rec = _Parked(slot, pos=int(self._pos[j]),
                      tok=int(self._tok[j]),
                      stop_pos=int(self._stop_pos[j]),
                      n_res=int(self._n_res[j]),
                      pages=[("hbm", int(self._ptab[j, t]))
                             for t in range(n)],
                      park_seq=self._park_seq)
        self._slots[j] = None
        self._tok[j] = 0
        self._pos[j] = 0
        self._prompt_len[j] = 1
        self._stop_pos[j] = 0
        self._ptab[j, :] = 0
        self._n_alloc[j] = 0
        self._ptab_dirty = True
        if have_lock:
            self._n_res[j] = 0
        else:
            with self._lock:
                self._n_res[j] = 0
        self._parked[rec.park_seq] = rec
        if self._draft_cache is not None:
            # the draft cache's lane no longer mirrors this slot; the
            # next speculative re-probe rebuilds from accepted contexts
            self._draft_synced = False
        self.stats.set("kv_parked_slots", len(self._parked))
        self._span("parked", slot.request_id, slot=j,
                   pages=len(rec.pages))

    def _unpark_order(self) -> List[int]:
        """Resume order: highest class first, then oldest arrival —
        the inverse of the eviction order, so fill and evict work
        opposite ends of the parked set and the interleaved
        page-by-page exchange always converges."""
        return sorted(self._parked,
                      key=lambda k: (slo_priority(
                          self._parked[k].slot.slo),
                          self._parked[k].slot.seq))

    def _try_unpark(self) -> List[Tuple[int, _Parked, List[int],
                                        List[int], Any]]:
        """Admission-phase resume pass (lock held): restore parked
        slots' host pages into freshly-allocated HBM pages as capacity
        allows, and seat fully-resident parked slots into free lanes.
        Returns ``(install work, slots seated)`` — the installs are
        ``(lane, rec, pool_ids, host_ids, staged)`` tuples the caller
        scatters IMMEDIATELY, still under the lock: a later seat in
        the same admission pass may reclaim these very pages back to
        host, and a deferred install would let that eviction capture
        pre-install garbage (a silently-wrong resume)."""
        installs: List[Tuple[int, _Parked, List[int], List[int], Any]] \
            = []
        seated = 0
        for k in self._unpark_order():
            rec = self._parked[k]
            host = [(t, p) for t, (loc, p) in enumerate(rec.pages)
                    if loc == "host"]
            if host:
                fill = min(len(self._free_pages), len(host))
                if fill < len(host):
                    # not fully restorable yet: pull what fits (head
                    # pages first — logical order) and try again next
                    # step; evicting OTHER parked slots' pages to make
                    # room happens on demand in _reclaim_one_hbm_page
                    if fill == 0:
                        continue
                    host = host[:fill]
                pool_ids = [self._free_pages.pop() for _ in host]
                host_ids = [p for _t, p in host]
                staged = None
                if self.tier is not None and fill == len(
                        rec.host_ids()):
                    staged = self.tier.take_staged(k, host_ids)
                for (t, _p), pid in zip(host, pool_ids):
                    rec.pages[t] = ("hbm", int(pid))
                installs.append((-1, rec, pool_ids, host_ids, staged))
                self.stats.set(
                    "kv_pages_used",
                    self.n_pages - 1 - len(self._free_pages))
            if rec.host_ids():
                continue  # still partially host-resident
            i = next((j for j in range(self.B)
                      if self._slots[j] is None), None)
            if i is None:
                continue  # fully resident, waiting for a lane
            self._seat_parked(i, k, rec)
            seated += 1
        return installs, seated

    def _seat_parked(self, i: int, key: int, rec: _Parked) -> None:
        """Reseat a fully-HBM-resident parked slot into lane ``i``
        (lock held): mirrors restored, page table rebuilt, reservation
        moved back onto the lane. No re-prefill — the pages are the
        KV it had."""
        slot = rec.slot
        self._slots[i] = slot
        self._tok[i] = rec.tok
        self._pos[i] = rec.pos
        self._prompt_buf[i, :] = 0
        self._prompt_buf[i, :len(slot.prompt)] = slot.prompt
        self._prompt_len[i] = len(slot.prompt)
        self._stop_pos[i] = rec.stop_pos
        self._temp[i] = slot.temperature
        self._topk[i] = slot.top_k
        self._topp[i] = slot.top_p
        self._seed[i] = np.int32(slot.seed & 0x7FFFFFFF)
        self._aid[i] = slot.adapter_id
        for t, (loc, p) in enumerate(rec.pages):
            assert loc == "hbm"
            self._ptab[i, t] = p
        self._n_alloc[i] = len(rec.pages)
        self._n_res[i] = rec.n_res
        self._ptab_dirty = True
        del self._parked[key]
        if self.tier is not None:
            self.tier.drop_staged(key)
        self.stats.set("kv_parked_slots", len(self._parked))
        self.stats.inc("kv_unparks_total")

    def _apply_unpark_installs(self, installs) -> None:
        """Scatter restored pages' content into the cache (outside the
        engine lock, before any compiled call). Prefetch hits consume
        device arrays the tier thread staged; misses pull the host
        copies and upload inline (host→device — the direction that
        does not stall the device pipeline)."""
        for _lane, rec, pool_ids, host_ids, staged in installs:
            if staged is None:
                self.stats.inc("kv_prefetch_misses")
                leaves = self.tier.fetch(host_ids)
                staged = [jnp.asarray(a) for a in leaves]
                self.stats.inc("kv_transfer_bytes_total",
                               int(sum(a.nbytes for a in leaves)))
            else:
                self.stats.inc("kv_prefetch_hits")
            idx = jnp.asarray(pool_ids, jnp.int32)
            flat, treedef = jax.tree_util.tree_flatten(self._cache)
            flat = [c.at[idx].set(v.astype(c.dtype))
                    for c, v in zip(flat, staged)]
            self._cache = jax.tree_util.tree_unflatten(treedef, flat)
            self.tier.free(host_ids)
            self._span("unparked", rec.slot.request_id,
                       pages=len(pool_ids))

    def _prefetch_hint(self) -> None:
        """Tell the tier thread which parked slot resumes next so its
        host pages are staged as device arrays before the unpark needs
        them — the async path that keeps the compiled step from ever
        blocking on a transfer."""
        if self.tier is None or not self._parked:
            return
        for k in self._unpark_order():
            ids = self._parked[k].host_ids()
            if ids:
                self.tier.prefetch_submit(k, ids)
                return

    def _release_slot_pages(self, i: int, have_lock: bool = False
                            ) -> None:
        """Return slot ``i``'s pages + reservation to the pool (request
        completed or preempted): the table row points back at the
        scratch page, so the freed lane keeps stepping harmlessly.
        ``have_lock``: the SLO-preemption path calls this from inside
        the admission loop, which already holds ``_lock`` (the lock is
        not reentrant)."""
        n = int(self._n_alloc[i])
        if n:
            self._free_pages.extend(
                int(p) for p in self._ptab[i, :n])
            self._ptab[i, :n] = 0
            self._n_alloc[i] = 0
            self._ptab_dirty = True
        if have_lock:
            self._res_total -= int(self._n_res[i])
            self._n_res[i] = 0
        else:
            with self._lock:
                # reservation counters share the admission loop's lock
                # discipline (admission reads/writes them under _lock)
                self._res_total -= int(self._n_res[i])
                self._n_res[i] = 0
        self.stats.set("kv_pages_used",
                       self.n_pages - 1 - len(self._free_pages))
        self.stats.set("kv_pages_total", self.n_pages - 1)

    def _live_table_width(self) -> int:
        """Table columns the NEXT compiled call actually needs: enough
        to cover every slot's allocated pages (``_ensure_pages_to`` runs
        before every call, so ``_n_alloc`` already reflects that call's
        write horizon), doubled up from ``table_floor`` (1: a power of
        two) so the jit cache sees at most log2(max_len/page_size)
        distinct operand widths.
        Slicing the operand shrinks BOTH decode paths' per-step cost to
        live tokens: the gather fallback stops materializing (and
        soft-maxing over) dead pages, and the kernel's page grid stops
        iterating them."""
        hi = max(1, int(self._n_alloc.max()))
        w = self._table_floor
        while w < hi:
            w *= 2
        return min(w, self._n_table)

    def _ptab_arg(self) -> jnp.ndarray:
        """The page-table operand every compiled call consumes (a tiny
        constant zeros array on contiguous engines), re-uploaded only
        when allocation changed it — and sliced to the live width (see
        :meth:`_live_table_width`) on paged engines."""
        width = self._live_table_width() if self.paged else self._n_table
        if self._ptab_dirty or width != self._ptab_dev_width:
            self._ptab_dev = jnp.asarray(self._ptab[:, :width])
            self._ptab_dev_width = width
            self._ptab_dirty = False
        return self._ptab_dev

    @_spanned("engine.poll")
    def poll(self) -> List[Tuple[Any, List[int]]]:
        """Completed (request_id, generated ids) since the last poll."""
        with self._lock:
            done, self._done = self._done, []
        return done

    @_spanned("engine.poll")
    def poll_partial(self) -> List[Tuple[Any, List[int]]]:
        """(request_id, generated-so-far) for STILL-LIVE slots that
        produced new tokens since the last ``poll_partial``. Cumulative
        snapshots (copies), not deltas — the text layer re-detokenizes
        the whole sequence per event, which is what makes streaming
        byte-level BPE safe (a token boundary can split a multi-byte
        character; only the cumulative decode is well-formed). Call
        from the loop thread that drives ``step`` (same discipline as
        ``step`` itself); finished requests surface via ``poll``."""
        out: List[Tuple[Any, List[int]]] = []
        for slot in self._slots:
            if slot is None:
                continue
            total = len(slot.prior) + len(slot.generated)
            if total > slot.n_streamed:
                # prior + generated: a preempt-resumed request streams
                # its full output, never re-emitting the re-ingested
                # prefix (n_streamed carried across the preemption)
                out.append((slot.request_id,
                            slot.prior + list(slot.generated)))
                slot.n_streamed = total
        return out

    def poll_kv(self) -> List[Tuple[Any, Dict[str, Any]]]:
        """Completed prefill-only shipments since the last call:
        ``(request_id, KV blob)`` pairs ready to ride the hub to a
        decode-role engine's ``submit(..., kv_import=blob)``."""
        with self._lock:
            done, self._done_kv = self._done_kv, []
        return done

    def _harvest_prefill_only(self) -> None:
        """Complete prefill-only slots whose prompt walk reached its
        last token: extract the KV shipment, free the lane and pages.
        Runs after chunked prefill and costs one attribute scan when
        no prefill-role traffic exists."""
        shipped: List[Tuple[Any, Dict[str, Any]]] = []
        for i in range(self.B):
            s = self._slots[i]
            if s is None or not s.prefill_only:
                continue
            if int(self._pos[i]) >= len(s.prompt) - 1:
                shipped.append((s.request_id,
                                self._extract_slot_kv(i)))
                self._slots[i] = None
                self._tok[i] = 0
                self._pos[i] = 0
                self._prompt_len[i] = 1
                self._stop_pos[i] = 0
                if self.paged:
                    self._release_slot_pages(i)
        if shipped:
            with self._lock:
                self._done_kv.extend(shipped)
                self.stats.inc("requests_done", len(shipped))
            for rid, blob in shipped:
                self._span("prefilled", rid, covered=blob["covered"])

    def _extract_slot_kv(self, i: int) -> Dict[str, Any]:
        """Slot ``i``'s prefilled KV as a wire blob: the pages (paged)
        or rows (contiguous) covering positions ``0..pos-1``, every
        cache leaf uniformly (int8 pools and scale rows included)."""
        s = self._slots[i]
        covered = max(0, min(int(self._pos[i]), len(s.prompt) - 1))
        flat = jax.tree_util.tree_leaves(self._cache)
        leaves: List[np.ndarray] = []
        if covered:
            if self.paged:
                n = (covered - 1) // self.page_size + 1
                idx = jnp.asarray(self._ptab[i, :n], jnp.int32)
                dev = [c[idx] for c in flat]
            else:
                dev = [c[i, :covered] for c in flat]
            # the one sanctioned d2h sync outside the tier thread:
            # this is the prefill ROLE's shipment materialization —
            # by construction not the decode hot loop (prefill-only
            # slots never generate). One batched fetch for every
            # leaf, not a per-leaf round-trip.
            leaves = list(jax.device_get(dev))  # rafiki: noqa[blocking-transfer-in-decode-loop] — shipment materialization on the prefill leg, not the decode hot loop
        self.stats.inc("kv_exports")
        return make_kv_blob(
            covered, LAYOUT_PAGED if self.paged else LAYOUT_ROWS,
            self.page_size, leaves, adapter_id=s.adapter_id)

    def stage_kv_blob(self, blob: Dict[str, Any]) -> Dict[str, Any]:
        """Upload a shipment's leaves to device AHEAD of admission
        (call when the blob arrives off the wire, any thread). The
        h2d copies dispatch asynchronously and overlap whatever step
        is in flight, so the seat-time install pays one scatter
        dispatch instead of staging + scatter. Best-effort: on any
        failure the original host blob installs fine, just later."""
        try:
            staged = dict(blob)
            staged["leaves"] = [jnp.asarray(a)
                                for a in blob["leaves"]]
            return staged
        except Exception:  # noqa: BLE001 — staging is an overlap
            # optimization, never a correctness gate: the host blob
            # installs fine at seat time, just without the overlap
            import logging

            logging.getLogger(__name__).debug(
                "kv blob staging failed; installing from host",
                exc_info=True)
            return blob

    def _install_kv(self, i: int, blob: Dict[str, Any]) -> None:
        """Scatter a shipped blob's rows into slot ``i``'s pages/rows
        (validated at submit; pages allocated at seat). Upload
        direction only, through the donated installer — in-place on
        the cache buffers, O(shipped pages) device work; an eager
        ``at[].set`` here would copy the ENTIRE page pool per leaf on
        every install, a whole-HBM tax per arriving shipment."""
        cov = int(blob["covered"])
        staged = [jnp.asarray(a) for a in blob["leaves"]]
        flat, treedef = jax.tree_util.tree_flatten(self._cache)
        if self.paged:
            n = (cov - 1) // self.page_size + 1
            idx = jnp.asarray(self._ptab[i, :n], jnp.int32)
            flat = _install_pages(flat, idx, staged)
        else:
            flat = _install_rows(flat, jnp.int32(i), staged)
        self._cache = jax.tree_util.tree_unflatten(treedef, flat)
        self.stats.inc("kv_imports")
        self.stats.inc("kv_transfer_bytes_total",
                       int(blob.get("nbytes", 0) or 0))

    def register_prefix(self, prefix_ids: np.ndarray,
                        adapter_id: int = 0) -> int:
        """Precompute the KV cache of a shared prompt prefix (system
        prompt). Any later request whose prompt strictly extends these
        tokens skips their prefill: admission copies the snapshot's KV
        rows into the slot's cache — a device copy at HBM bandwidth
        instead of ``len(prefix)`` of model forward compute. Exact by
        construction (the copied KV is the same math prefill would
        produce); one prefix PER ADAPTER (re-register to replace, empty
        ids to clear).
        Returns the registered length (truncated to leave room for at
        least one prompt token + one generated token). Not safe to call
        concurrently with ``step`` (register before serving traffic, or
        between steps).

        ``adapter_id`` (multi-adapter engines): the prefix KV is a
        function of the adapter that computed it, so each adapter keeps
        its OWN registered prefix (multi-tenant system prompts) and
        hits are gated on the requesting slot's adapter."""
        if self._slot_state:
            raise ValueError(self._no_slot_state(
                "a shared prefix (register_prefix)"))
        aid = self._check_adapter_id(adapter_id)
        prefix = np.asarray(prefix_ids, np.int32).ravel()[:self.L - 2]
        if len(prefix) == 0:
            self._prefixes.pop(aid, None)
            return 0
        # snapshots compute through a CONTIGUOUS-cache twin of the
        # module even on paged engines: a 1-row (1, plen, …) snapshot
        # is the natural install source either way (the paged install
        # scatters it into the hit slots' pages)
        snap_module = (self.module.clone(kv_page_size=0, kv_pages=0)
                       if self.paged else self.module)
        cache1 = _empty_cache(snap_module, 1)
        # one multi-token cache pass over the prefix (same program shape
        # as chunked prefill, batch 1, chunk = len(prefix))
        fill = _make_prefill(snap_module, 1, len(prefix))
        snap = fill(self.params, cache1, jnp.asarray(prefix[None, :]),
                    jnp.arange(len(prefix), dtype=jnp.int32)[None, :],
                    jnp.asarray([aid], jnp.int32),
                    jnp.zeros((1, 1), jnp.int32))
        plen = len(prefix)
        install = _make_prefix_install(plen)
        # store only the populated rows: the snapshot allocates at
        # max_len but install() reads [:plen] — trimming cuts the
        # per-adapter resident HBM by max_len/plen
        snap = jax.tree_util.tree_map(lambda p: p[:, :plen], snap)
        snap = jax.block_until_ready(snap)
        if self.tier is not None:
            # host-tier engines keep the snapshot store in HOST memory
            # (numpy leaves): zero resident HBM while idle, uploaded
            # per install (jit device-puts host operands) — the same
            # capacity trade the page tier makes, and the form the
            # export/import shipment rides
            snap = jax.tree_util.tree_map(np.asarray, snap)
        entry = {"ids": prefix, "cache": snap,
                 "len": plen, "install": install, "aid": aid}
        if self._draft_cache is not None:
            # the draft attends the same positions: without its own
            # snapshot a prefix-hit slot would draft over zero KV for
            # 0..plen-1 (still lossless, but acceptance collapses and
            # the draft's cost is pure waste)
            d1 = _empty_cache(self.draft_module, 1)
            d_fill = _make_prefill(self.draft_module, 1, plen)
            d_snap = d_fill(self.draft_params, d1,
                            jnp.asarray(prefix[None, :]),
                            jnp.arange(plen, dtype=jnp.int32)[None, :],
                            jnp.asarray([aid], jnp.int32),
                            jnp.zeros((1, 1), jnp.int32))
            d_snap = jax.tree_util.tree_map(lambda p: p[:, :plen],
                                            d_snap)
            entry["draft_cache"] = jax.block_until_ready(d_snap)
        self._prefixes[aid] = entry
        return plen

    def _install_prefix(self, rows: List[int],
                        pre: Dict[str, Any]) -> None:
        """Copy prefix ``pre``'s KV rows into the given slots (the
        same snapshot admission matched/fast-forwarded against). On a
        paged engine the snapshot scatters into the hit slots' pages
        (allocated at admission); the draft cache, always contiguous,
        keeps the row install."""
        rws = jnp.asarray(rows, jnp.int32)
        if self.paged:
            inst = _make_paged_prefix_install(pre["len"], self.page_size)
            self._cache = inst(
                self._cache, pre["cache"],
                jnp.asarray(self._ptab[np.asarray(rows, np.int64)],
                            jnp.int32))
        else:
            self._cache = pre["install"](self._cache, pre["cache"], rws)
        if self._draft_cache is not None and "draft_cache" in pre:
            self._draft_cache = pre["install"](
                self._draft_cache, pre["draft_cache"], rws)
        self.stats.inc("prefix_hits", len(rows))
        self.stats.inc("prefix_tokens", pre["len"] * len(rows))

    def export_prefix(self, adapter_id: int = 0
                      ) -> Optional[Dict[str, Any]]:
        """The registered prefix snapshot as a wire blob (msgpack-able
        numpy leaves): a shared prefix prefilled ONCE can serve every
        replica of a job — peers install it via
        :meth:`import_prefix` instead of re-running the prefill
        forward. None when no prefix is registered for the adapter."""
        pre = self._prefixes.get(self._check_adapter_id(adapter_id))
        if pre is None:
            return None
        leaves = [np.asarray(a) for a in
                  jax.tree_util.tree_leaves(pre["cache"])]
        return {"v": 1, "ids": np.asarray(pre["ids"], np.int32),
                "len": int(pre["len"]), "adapter_id": int(pre["aid"]),
                "sig": leaf_signature(leaves), "leaves": leaves,
                "nbytes": int(sum(a.nbytes for a in leaves))}

    def import_prefix(self, blob: Dict[str, Any],
                      adapter_id: int = 0) -> int:
        """Install a peer's exported prefix snapshot (see
        :meth:`export_prefix`) without recomputing its prefill.
        Validates geometry before touching state; raises
        ``ValueError`` on any mismatch. Draft-model engines fall back
        to undrafted prefix rows (still lossless — acceptance just
        starts cold until generation warms the draft cache). Returns
        the installed length. Same concurrency contract as
        :meth:`register_prefix` (not concurrent with ``step``)."""
        if self._slot_state:
            raise ValueError(self._no_slot_state(
                "a shared prefix (import_prefix)"))
        aid = self._check_adapter_id(adapter_id)
        if not isinstance(blob, dict) or int(blob.get("v", -1)) != 1:
            raise ValueError("not a prefix snapshot blob")
        ids = np.asarray(blob.get("ids"), np.int32).ravel()
        plen = int(blob.get("len", -1))
        if plen != len(ids) or not 0 < plen <= self.L - 2:
            raise ValueError(
                f"prefix blob length {plen} does not fit this engine "
                f"(1..{self.L - 2} tokens)")
        snap_module = (self.module.clone(kv_page_size=0, kv_pages=0)
                       if self.paged else self.module)
        cache1 = _empty_cache(snap_module, 1)
        flat, treedef = jax.tree_util.tree_flatten(cache1)
        leaves = [np.asarray(a) for a in blob.get("leaves") or []]
        if len(leaves) != len(flat) or any(
                v.shape[:2] != (1, plen) or v.shape[2:] != c.shape[2:]
                or v.dtype != c.dtype
                for v, c in zip(leaves, flat)):
            raise ValueError(
                "prefix blob does not match this engine's cache "
                "geometry (model shape / dtype / int8 mismatch)")
        tree = jax.tree_util.tree_unflatten(treedef, leaves)
        if self.tier is None:
            tree = jax.tree_util.tree_map(jnp.asarray, tree)
        self._prefixes[aid] = {"ids": ids, "cache": tree, "len": plen,
                               "install": _make_prefix_install(plen),
                               "aid": aid}
        self.stats.inc("kv_imports")
        return plen

    @property
    def busy(self) -> bool:
        with self._lock:
            return bool(self._cq) or bool(self._parked) \
                or any(s is not None for s in self._slots)

    def reset_stats(self) -> None:
        """Zero the served-traffic counters without losing capacity
        gauges (``kv_pages_total`` describes the pool, not traffic) —
        what the worker's post-warmup scrub needs."""
        keep = {"paged_kernel_mode": self.paged_kernel_mode,
                "kv_host_pages_total": self.host_pages,
                "weight_bytes": self.stats["weight_bytes"],
                "kv_pool_bytes_per_token":
                    self.stats["kv_pool_bytes_per_token"],
                "ssm_state_bytes_per_slot":
                    self.stats["ssm_state_bytes_per_slot"],
                "window_kv_bytes_per_slot":
                    self.stats["window_kv_bytes_per_slot"]}
        if self.paged:
            keep.update(kv_pages_total=self.n_pages - 1,
                        kv_pages_used=(self.n_pages - 1
                                       - len(self._free_pages)))
        if self.tier is not None:
            keep.update(
                kv_host_pages_used=(self.host_pages
                                    - self.tier.free_pages()),
                kv_parked_slots=len(self._parked))
        self.stats.reset(keep=keep)
        SPANS.instant("engine.stats_reset")

    def stats_snapshot(self) -> Dict[str, int]:
        """A point-in-time copy of the counters, taken under the stats
        lock — the ONLY race-free way to read them while the step
        thread runs (iterating ``stats`` key-by-key from another thread
        used to race concurrent mutation)."""
        return self.stats.snapshot()

    def _span(self, event: str, request_id: Any, **attrs: Any) -> None:
        """Record a request-lifecycle event: an instant ``req.<event>``
        in the phase-span ring under the open turn, then the wired sink
        (if any)."""
        SPANS.instant("req." + event, request_id, self._turn_seq or None,
                      **attrs)
        self._to_sink(event, request_id, attrs)

    def _to_sink(self, event: str, request_id: Any,
                 attrs: Dict[str, Any]) -> None:
        """Hand an event to the wired sink (one attribute read when
        nothing is wired)."""
        sink = self.span_sink
        if sink is None:
            return
        try:
            sink(event, request_id, attrs)
        except Exception:  # noqa: BLE001 — observability must never
            import logging  # kill the step loop; log once per type

            logging.getLogger(__name__).warning(
                "span sink failed on %s", event, exc_info=True)
            self.span_sink = None  # a broken sink stays broken: detach

    def close(self) -> None:
        """Release the host tier's transfer thread and pinned pool.
        Idempotent; everything else dies with its references, but the
        tier's thread polls forever and its host pool is real RAM —
        a process that builds engines repeatedly (benches, tests,
        notebooks) must not accumulate one of each per engine."""
        if self.tier is not None:
            self.tier.close()

    def reset(self) -> None:
        """Drop all occupants and rebuild device state. For error
        recovery: a step that raised may have consumed the donated cache
        buffer, so the old cache must not be touched again."""
        with self._lock:
            self._slots = [None] * self.B
            self._cq.clear()
            self._done.clear()
            # host mirrors under the same lock: a submit() racing this
            # reset must observe either the old world or the cleared
            # one, never a half-cleared mix
            self._tok[:] = 0
            self._pos[:] = 0
            self._prompt_buf[:] = 0
            self._prompt_len[:] = 1
            self._stop_pos[:] = 0  # empty slots must be device-inactive
            self._temp[:] = 0.0
            self._topk[:] = 0
            self._topp[:] = 1.0
            self._seed[:] = 0
            self._aid[:] = 0
            self._prompt_dev = None
            self._spec_ema = self._spec_floor + 0.5
            self._spec_idle = 0
            self._draft_synced = True
            self._parked.clear()
            self._done_kv.clear()
            if self.tier is not None:
                self.tier.reset()
            self.stats.set("kv_parked_slots", 0)
            if self.paged:
                # every occupant is gone: the whole pool returns to the
                # free list and every table row points at scratch
                self._free_pages = list(range(self.n_pages - 1, 0, -1))
                self._ptab[:] = 0
                self._n_alloc[:] = 0
                self._n_res[:] = 0
                self._res_total = 0
                self._ptab_dirty = True
                self.stats.set("kv_pages_used", 0)
        self._cache = _empty_cache(self.module, self.B)
        self._counts_dev = self._counts_zero
        if self.draft_module is not None and self.spec_k:
            self._draft_cache = _empty_cache(self.draft_module, self.B)

    def _chunked_prefill(self) -> int:
        """Ingest admitted prompts C tokens per compiled call before they
        join the decode scan (positions 0..plen−2; the scan then starts
        at the LAST prompt token, whose step emits the first generated
        token). A paged engine's call deals ``PREFILL_LANES`` rows to
        the lanes that have prompt left, its spare rows writing to the
        scratch page; elsewhere every slot is a row, and slots
        not prefilling re-feed their current input — an identical
        rewrite of a cache entry, harmless by construction. Either way
        one fixed-shape program serves any admission mix. Returns
        the number of calls made; each is one ``engine.prefill_prep``
        span (its operands) and one ``engine.prefill_dispatch`` (the
        launch, then the call's counters and position advance)."""
        occupied = np.array([s is not None for s in self._slots],
                            bool)
        calls = 0
        while True:
            with SPANS.span("engine.prefill_prep"):
                prep = self._prefill_operands(occupied)
            if prep is None:
                return calls
            fill_fn, adv, tok_dev, pos_dev, aid_dev, ptab, rows = prep
            calls += 1
            with SPANS.span("engine.prefill_dispatch") as dispatch:
                if rows is not None:  # a gathered call
                    dispatch.set(rows=int(np.count_nonzero(rows[1])),
                                 lanes=int(np.count_nonzero(adv)))
                    rows = (tuple(jnp.asarray(r) for r in rows)
                            if self._slot_state else None)
                if self._counts_dev is None:
                    self._cache = fill_fn(
                        self.params, self._cache, tok_dev, pos_dev,
                        aid_dev, ptab, rows=rows)
                else:
                    self._cache, self._counts_dev = fill_fn(
                        self.params, self._cache, tok_dev, pos_dev,
                        aid_dev, ptab, self._counts_dev, rows=rows)
                if self._draft_cache is not None and self._draft_synced:
                    # keep the draft's KV in lockstep with the prompt
                    # walk (while desynced, resync rebuilds prompts
                    # anyway)
                    self._draft_cache = self._draft_sync_c(
                        self.draft_params, self._draft_cache, tok_dev,
                        pos_dev, aid_dev, self._ptab_arg())
                del prep, tok_dev, pos_dev, aid_dev, ptab, rows  # see _turn()
                self._book_prefill(adv)

    def _prefill_operands(self, occupied: np.ndarray) -> Optional[Tuple]:
        """One chunk call's program, advance and device operands, or
        None when no occupied lane has prompt left to ingest. On a
        paged engine the call's rows are GATHERED (:meth:`_gathered_rows`);
        elsewhere one row a slot."""
        rem = np.where(occupied,
                       np.maximum(0, (self._prompt_len - 1)
                                  - self._pos), 0)
        if rem.max() == 0:
            return None
        lanes = (np.flatnonzero(rem)[:self._prefill_lanes]
                 if self._prefill_lanes else np.arange(self.B))
        fill_fn, c_use = self._prefill_fn, self.C
        if (self._prefill_fn_small is not None
                and self._draft_cache is None
                and rem[lanes].max() <= self._small_c):
            # short remainder: the narrow program ingests it
            # without the C-wide call's cost (the draft mirror is
            # compiled at C only, so draft engines stay wide)
            fill_fn, c_use = self._prefill_fn_small, self._small_c
        if self._prefill_lanes:
            return (fill_fn,) + self._gathered_rows(rem, lanes, c_use)
        adv = np.minimum(rem, c_use)
        tok_chunk = np.empty((self.B, c_use), np.int32)
        pos_chunk = np.empty((self.B, c_use), np.int32)
        for i in range(self.B):
            a = int(adv[i])
            if a > 0:
                p0 = int(self._pos[i])
                tok_chunk[i, :a] = self._prompt_buf[i, p0:p0 + a]
                pos_chunk[i, :a] = np.arange(p0, p0 + a)
                # pad by repeating the chunk's last real entry —
                # rewrites a just-written cache slot identically
                tok_chunk[i, a:] = tok_chunk[i, a - 1]
                pos_chunk[i, a:] = pos_chunk[i, a - 1]
            else:
                tok_chunk[i, :] = self._tok[i]
                pos_chunk[i, :] = self._pos[i]
        self._map_prefill_pages(adv)
        return (fill_fn, adv, jnp.asarray(tok_chunk),
                jnp.asarray(pos_chunk), jnp.asarray(self._aid),
                self._ptab_arg(), None)

    def _gathered_rows(self, rem: np.ndarray, lanes: np.ndarray,
                       c_use: int) -> Tuple:
        """A paged call's ``_prefill_lanes`` rows of ``c_use`` tokens,
        dealt to ``lanes`` (all with prompt left) in order: a lane takes
        a row for every ``c_use`` tokens it has left while rows remain,
        so ONE long prompt fills the call with its consecutive chunks —
        every layer writes the whole call's rows to the pool before any
        row attends, and a query sees the keys at or before its own
        position, whichever row wrote them. Rows left over carry token
        0 at position 0 through an all-zero table row: the scratch page.
        Returns ``(adv, tokens, positions, adapter ids, table rows)``."""
        n_rows = self._prefill_lanes
        adv = np.zeros((self.B,), rem.dtype)
        tok_chunk = np.zeros((n_rows, c_use), np.int32)
        pos_chunk = np.zeros((n_rows, c_use), np.int32)
        row_lane, row_real = [], np.zeros((n_rows,), np.int32)
        for i in lanes:
            left, p0 = int(rem[i]), int(self._pos[i])
            while left > 0 and len(row_lane) < n_rows:
                row, a = len(row_lane), min(left, c_use)
                row_real[row] = a
                tok_chunk[row, :a] = self._prompt_buf[i, p0:p0 + a]
                pos_chunk[row, :a] = np.arange(p0, p0 + a)
                # pad by repeating the row's last real entry —
                # rewrites a just-written cache slot identically
                tok_chunk[row, a:] = tok_chunk[row, a - 1]
                pos_chunk[row, a:] = pos_chunk[row, a - 1]
                row_lane.append(i)
                adv[i] += a
                left, p0 = left - a, p0 + a
        self._map_prefill_pages(adv)
        # the rows' table rows at the engine's live width (the widths
        # the step program meets, so no shape of its own), read AFTER
        # the allocation: a lane parked by it has a zeroed row
        n = len(row_lane)
        aid = np.zeros((n_rows,), np.int32)
        aid[:n] = self._aid[row_lane]
        ptab = np.zeros((n_rows, self._live_table_width()), np.int32)
        ptab[:n] = self._ptab[row_lane, :ptab.shape[1]]
        slots = np.full((n_rows,), self.B, np.int32)
        slots[:n] = row_lane
        return (adv, jnp.asarray(tok_chunk), jnp.asarray(pos_chunk),
                jnp.asarray(aid), jnp.asarray(ptab), (slots, row_real))

    def _map_prefill_pages(self, adv: np.ndarray) -> None:
        """Lazy allocation tracks the prompt walk: each chunk call only
        maps the pages it is about to write. The slot re-check matters
        on tiered engines: an earlier lane's growth may have PARKED this
        one (page reclaim) inside this very loop — its row is zeroed and
        its pos reset, so ensuring pages here would allocate for an
        empty lane and leak them."""
        if not self.paged:
            return
        for i in np.flatnonzero(adv):
            if self._slots[i] is not None:
                self._ensure_pages_to(
                    i, int(self._pos[i]) + int(adv[i]) - 1)

    def _book_prefill(self, adv: np.ndarray) -> None:
        """Counters and the host-side position advance of one chunk
        call that ingested ``adv[i]`` prompt tokens on lane ``i``."""
        self.stats.inc("prefill_calls")
        self.stats.inc("prefill_tokens", int(adv.sum()))
        if self.paged_kernel_windowed:
            # these prompt tokens attended through the window
            # kernel (the chunk call is an s=C window)
            self.stats.inc("paged_kernel_window_tokens",
                           int(adv.sum()))
        for i in range(self.B):
            if adv[i] > 0 and self._slots[i] is not None:
                # a lane parked mid-chunk (page reclaim) skips the
                # advance: its record saved the PRE-chunk position,
                # so the resume re-prefills this chunk — the
                # chunk's writes went to the scratch page (its
                # table row was zeroed at park), losing nothing
                self._pos[i] += int(adv[i])
                self._slots[i].n_consumed += int(adv[i])
                self._tok[i] = self._prompt_buf[i, int(self._pos[i])]

    # ---- SLO preemption (lock held: admission-loop context) ----
    def _occupants(self, live_only: bool = False
                   ) -> List[Tuple[Any, str, int, bool]]:
        """Admitted work as the ``(handle, slo, seq, shielded)``
        tuples the shared eviction policy (`serving/slo.py`)
        consumes. Handles are ``("live", lane)`` for seated slots and
        ``("parked", key)`` for slots suspended to the host tier —
        parked work holds reservations (and host pages) too, so a
        higher-class head may reclaim them the same way."""
        occ: List[Tuple[Any, str, int, bool]] = [
            (("live", j), s.slo, s.seq, s.shielded)
            for j, s in enumerate(self._slots) if s is not None]
        if not live_only:
            occ.extend((("parked", k), r.slot.slo, r.slot.seq,
                        r.slot.shielded)
                       for k, r in self._parked.items())
        return occ

    def _victim_for(self, cls: str, live_only: bool = False
                    ) -> Optional[Any]:
        """The occupant to evict so a ``cls`` head can admit — the
        shared :func:`preemption_victim` policy (youngest
        lowest-class, shielded immune). ``live_only`` restricts to
        seated slots (a LANE can only come from a live victim; page
        reservations can come from parked ones too)."""
        return preemption_victim(cls, self._occupants(live_only))

    def _evictable_for(self, cls: str, live_only: bool = False
                       ) -> List[Any]:
        """Every occupant :meth:`_victim_for` could ever return for a
        ``cls`` head — the feasibility pre-check sums their
        reservations BEFORE committing any eviction (a preemption
        that cannot end in the head admitting would destroy the
        victims' progress for nothing; pre-SLO behavior just stalled
        in place with the lower-class work still running). Same
        predicate as victim selection BY CONSTRUCTION (both call
        :func:`evictable_occupants`), which is what guarantees the
        paged reclaim loop in :meth:`step` terminates in admission."""
        return [h for h, _s, _q in
                evictable_occupants(cls, self._occupants(live_only))]

    def _res_of(self, handle: Any) -> int:
        kind, ref = handle
        if kind == "live":
            return int(self._n_res[ref])
        return int(self._parked[ref].n_res)

    def _preempt_handle(self, handle: Any, by: str
                        ) -> Tuple[Any, int, int, str, str]:
        kind, ref = handle
        if kind == "live":
            return self._preempt_slot(ref, by)
        return self._preempt_parked(ref, by)

    def _resumed_from(self, slot: _Slot) -> _Slot:
        """The front-of-class re-queued request a preemption victim
        becomes: original prompt plus everything generated so far (the
        PR 7 forced-prefix shape) so re-admission re-ingests the
        prefix through chunked prefill at the SAME absolute positions
        — token-exact in every decode mode."""
        gen = list(slot.generated)
        prompt = (np.concatenate([slot.prompt,
                                  np.asarray(gen, np.int32)])
                  if gen else slot.prompt)
        resumed = _Slot(slot.request_id, prompt,
                        slot.max_new - len(gen),
                        temperature=slot.temperature, top_k=slot.top_k,
                        top_p=slot.top_p, seed=slot.seed,
                        eos_id=slot.eos_id,
                        adapter_id=slot.adapter_id, slo=slot.slo,
                        seq=slot.seq, prior=slot.prior + gen,
                        prefill_only=slot.prefill_only)
        resumed.n_streamed = slot.n_streamed
        resumed.first_tokened = slot.first_tokened
        resumed.shielded = slot.shielded
        return resumed

    def _preempt_parked(self, key: int, by: str
                        ) -> Tuple[Any, int, int, str, str]:
        """Evict a PARKED occupant: cheapest of all — nothing is
        seated, so its HBM pages, host pages, and reservation free
        immediately and it re-queues front-of-class exactly like a
        live victim (resumes token-exact later)."""
        rec = self._parked.pop(key)
        slot = rec.slot
        hbm = rec.hbm_ids()
        if hbm:
            self._free_pages.extend(hbm)
            self._ptab_dirty = True
        host = rec.host_ids()
        if host and self.tier is not None:
            self.tier.free(host)
        if self.tier is not None:
            self.tier.drop_staged(key)
        self._res_total -= rec.n_res
        self._cq.push(slot.slo, self._resumed_from(slot), front=True)
        self.stats.inc("preemptions")
        self.stats.set("kv_parked_slots", len(self._parked))
        self.stats.set("kv_pages_used",
                       self.n_pages - 1 - len(self._free_pages))
        return (slot.request_id, -1, len(slot.generated), slot.slo, by)

    def _preempt_slot(self, j: int, by: str
                      ) -> Tuple[Any, int, int, str, str]:
        """Evict slot ``j`` mid-generation so a higher-class admission
        fits. Cheap under paged KV: the victim's pages + reservation
        return to the pool NOW; the victim becomes a front-of-class
        re-queued request whose prompt is its original prompt PLUS
        everything generated so far (the PR 7 forced-prefix shape), so
        on re-admission it re-ingests that prefix through chunked
        prefill and continues at the SAME absolute positions —
        token-exact in every decode mode (greedy argmax depends only
        on history; sampled draws are pure functions of (seed,
        position); speculation is greedy-lossless; int8-KV and
        multi-adapter ride the same cache math). The vacated KV rows
        are the standard unreachable-then-rewritten slot-reuse case.
        Returns the ``preempted`` span record."""
        slot = self._slots[j]
        gen = list(slot.generated)
        resumed = self._resumed_from(slot)
        self._slots[j] = None
        self._tok[j] = 0
        self._pos[j] = 0  # fresh occupant restarts at position 0
        self._prompt_len[j] = 1
        self._stop_pos[j] = 0
        if self.paged:
            self._release_slot_pages(j, have_lock=True)
        self._cq.push(resumed.slo, resumed, front=True)
        self.stats.inc("preemptions")
        return (slot.request_id, j, len(gen), slot.slo, by)

    def _seat_slot(self, i: int, slot: _Slot) -> None:
        """Install a popped request into free slot ``i``: host mirrors,
        shared-prefix fast-forward (or a shipped-KV fast-forward for
        disaggregated decode), first lazy pages. Lock held.

        Content installs (prefix snapshot / shipped KV blob) happen
        HERE, immediately after the lane's pages are mapped — not
        batched after admission. On a tiered engine a LATER seat in
        the same admission pass can park this very lane and evict its
        pages to host; deferred installs would let that eviction
        capture pre-install garbage (a silently-wrong resume). The
        scatters are async dispatches; holding the lock across them
        costs submitters microseconds."""
        self._slots[i] = slot
        self._tok[i] = slot.prompt[0]
        self._pos[i] = 0
        self._prompt_buf[i, :] = 0
        self._prompt_buf[i, :len(slot.prompt)] = slot.prompt
        self._prompt_len[i] = len(slot.prompt)
        pre = self._prefixes.get(slot.adapter_id)
        install: Optional[Tuple[str, Any]] = None
        if slot.kv_import is not None \
                and int(slot.kv_import["covered"]) > 0:
            # disaggregated decode: a prefill-role worker already
            # computed positions 0..covered-1; the shipped rows
            # scatter into this slot's pages/rows (below, once the
            # pages are mapped) and the prompt walk resumes past them
            # — exactly the prefix-hit shape, sourced from the wire
            cov = int(slot.kv_import["covered"])
            self._pos[i] = cov
            slot.n_consumed = cov
            self._tok[i] = slot.prompt[cov]
            install = ("kv", slot.kv_import)
            slot.kv_import = None  # installed once; a preempt-resume
            #                        re-ingests through chunked prefill
        elif (pre is not None and len(slot.prompt) > pre["len"]
                and np.array_equal(slot.prompt[:pre["len"]],
                                   pre["ids"])):
            # shared-prefix hit: skip its prefill — the KV copy makes
            # positions 0..plen-1 as if prefilled, and the prompt walk
            # resumes at plen. `pre` is the snapshot the prompt
            # MATCHED, held through the install below — never a fresh
            # self._prefixes lookup a concurrent register could swap
            install = ("prefix", pre)
            self._pos[i] = pre["len"]
            slot.n_consumed = pre["len"]
            self._tok[i] = slot.prompt[pre["len"]]
        if slot.prefill_only:
            # prefill-role serving: stop at the last prompt token —
            # the position the decode leg starts from; the slot never
            # generates, its KV ships via poll_kv instead
            self._stop_pos[i] = max(0, len(slot.prompt) - 1)
        else:
            # finish once pos reaches plen - 1 + max_new (the step at
            # input position p emits a GENERATED token iff p >= plen-1)
            self._stop_pos[i] = min(
                len(slot.prompt) - 1 + slot.max_new, self.L)
        self._temp[i] = slot.temperature
        self._topk[i] = slot.top_k
        self._topp[i] = slot.top_p
        self._seed[i] = np.int32(slot.seed & 0x7FFFFFFF)
        self._aid[i] = slot.adapter_id
        if self.paged:
            # map the pages the slot starts on: position 0, or the
            # whole prefix/import span for a hit (the install below
            # scatters into them)
            self._ensure_pages_to(i, int(self._pos[i]),
                                  have_lock=True)
        if install is not None:
            kind, payload = install
            if kind == "kv":
                self._install_kv(i, payload)
            else:
                self._install_prefix([i], payload)

    # ---- the loop body ----
    def step(self) -> int:
        """Admit queued requests into free slots, run K fused compiled
        steps for every live slot, harvest completions. Returns live
        count (at admission time).

        One ``engine.turn`` span whose body is tiled by the leaf spans
        ``engine.admit`` / ``prefill_prep`` / ``prefill_dispatch`` /
        ``decode_prep`` / ``decode_dispatch`` / ``sync_wait`` /
        ``harvest`` (docs/observability.md "Phase spans"); the counters
        ``turns``, ``turn_host_ns`` and ``sync_wait_ns`` are cut at the
        same boundaries."""
        self._turn_sync_ns = 0
        with SPANS.span("engine.turn") as turn:
            self._turn_seq = turn.seq
            try:
                n_live = self._turn(turn)
            finally:
                self._turn_seq = 0
        self.stats.inc("turns")
        self.stats.inc("sync_wait_ns", self._turn_sync_ns)
        self.stats.inc("turn_host_ns",
                       turn.t1 - turn.t0 - self._turn_sync_ns)
        return n_live

    def _turn(self, turn: Any) -> int:
        """The body of :meth:`step`, phase by phase."""
        with SPANS.span("engine.admit"):
            live, admitted, admitted_info = self._admit()
        prefilling = bool(live and admitted
                          and self._prefill_fn is not None)
        n_prefill = self._chunked_prefill() if prefilling else 0
        with SPANS.span("engine.decode_prep"):
            if prefilling:
                for rid, row, plen, cls, resumed in admitted_info:
                    self._span("prefill", rid, prompt_tokens=plen)
            call = self._decode_prep(live, admitted)
        turn.set(live=len(live), admitted=len(admitted_info),
                 prefill_calls=n_prefill,
                 path=call[0] if call else "idle")
        if call is None:
            return 0
        path, live, any_sampling, operands = call
        if path == "spec":
            return self._speculative_step(live)
        with SPANS.span("engine.decode_dispatch"):
            self._cache, emitted, *counts = \
                self._step_fns[any_sampling](*operands)
            # the launch's operand handles die with the launch, as the
            # temporaries they are (freeing eleven device buffers takes
            # ~0.1 ms: inside a span, not in the turn's own time)
            del call, operands
        # the loop's OUTPUT sync: generated tokens must reach the host
        # to stream; the fused K-step scan amortizes it. The module's
        # device counters, where it has any, come with them: one small
        # vector that was ready when the tokens were
        emitted, *counts = self._sync_wait(emitted, *counts)
        with SPANS.span("engine.harvest"):
            if counts:  # the module books them under its own names
                self.module.book_device_counters(self.stats, counts[0])
                self._counts_dev = self._counts_zero
            self._harvest_scan(live, emitted, any_sampling)
        return len(live)

    def _sync_wait(self, *outputs: Any) -> List[np.ndarray]:
        """Pull a launch's outputs to the host: the one place the loop
        blocks on the device. One ``engine.sync_wait`` span, counted
        into the turn's ``sync_wait_ns``."""
        with SPANS.span("engine.sync_wait") as wait:
            pulled = [np.asarray(x) for x in outputs]  # rafiki: noqa[blocking-transfer-in-decode-loop] — the decode loop's output sync; each caller says why it must pull
        self._turn_sync_ns += wait.t1 - wait.t0
        return pulled

    def _admit(self) -> Tuple[List[int], bool, List[Tuple]]:
        """Unpark, admit queued requests into free lanes (preempting
        where the SLO policy allows), publish the queue gauges and emit
        the turn's ``preempted`` / ``admitted`` events. Returns the
        live lanes, whether occupancy changed, and the admissions."""
        admitted_info: List[Tuple[Any, int, int, str]] = []
        preempted_info: List[Tuple[Any, int, int, str, str]] = []
        with self._lock:
            # resume parked slots first: they hold reservations and
            # partial progress, and freeing their host pages is what
            # keeps the tier from silting up
            unpark_installs, n_unparked = self._try_unpark()
            if unpark_installs:
                # restored page CONTENT lands IMMEDIATELY (still under
                # the lock, before admission): a later seat's page
                # reclaim may evict these very pages back to host, and
                # it must evict their bytes, not pre-install garbage
                self._apply_unpark_installs(unpark_installs)
            admitted = n_unparked > 0
            while True:
                nxt = self._cq.peek()
                if nxt is None:
                    break
                cls, head = nxt
                i = next((j for j in range(self.B)
                          if self._slots[j] is None), None)
                # feasibility BEFORE any eviction: admission is
                # bounded by slots AND (paged) the page pool — the
                # head admits only if its worst case (prompt +
                # max_new + spec margin — its ACTUAL size, never
                # max_len) fits what is free plus what eviction could
                # reclaim from strictly-lower-class, non-shielded
                # occupants (parked ones included: their reservations
                # and pages free the same way). If even that is
                # insufficient, STALL WITHOUT evicting: destroying a
                # victim's progress while the head still cannot admit
                # would be pure loss (backpressure keeps FIFO
                # fairness — smaller latecomers never starve the
                # head; completions free reservations).
                victims = self._evictable_for(cls)
                live_victims = [h for h in victims if h[0] == "live"]
                if i is None and not live_victims:
                    break  # a lane can only come from a live victim
                n_res = 0
                if self.paged:
                    n_res = self._pages_for(
                        max(1, len(head.prompt) - 1)
                        if head.prefill_only
                        else min(len(head.prompt) - 1 + head.max_new,
                                 self.L))
                    avail = self._budget_pages - self._res_total
                    reclaim = sum(self._res_of(h) for h in victims)
                    if avail + reclaim < n_res:
                        self.stats.inc("admission_stalls")
                        break
                if i is None:
                    # every slot occupied: evict the youngest
                    # lowest-class LIVE occupant (pages return NOW —
                    # cheap under paged KV; the victim resumes
                    # token-exact later from its re-queued prefix)
                    h = self._victim_for(cls, live_only=True)
                    i = h[1]
                    preempted_info.append(self._preempt_slot(i, cls))
                if self.paged:
                    while self._res_total + n_res > self._budget_pages:
                        # guaranteed to terminate in admission by the
                        # feasibility check above; parked victims are
                        # the cheapest (nothing seated to destroy)
                        h = self._victim_for(cls)
                        preempted_info.append(
                            self._preempt_handle(h, cls))
                    self._n_res[i] = n_res
                    self._res_total += n_res
                # pop() == the peeked head: nothing ran between (a
                # preemption only pushes into strictly LOWER classes,
                # whose skip counters are unchanged)
                _, slot = self._cq.pop()
                if self._cq.last_pop_promoted:
                    slot.shielded = True  # aging fired: this slot may
                    #                       not be preempted in turn
                self._seat_slot(i, slot)
                admitted = True
                admitted_info.append((slot.request_id, i,
                                      len(slot.prompt), slot.slo,
                                      bool(slot.prior)))
            depths = self._cq.depths()
            self.stats.set("slo_aged_promotions", self._cq.promotions)
            live = [i for i in range(self.B) if self._slots[i] is not None]
            self.stats.max_set("max_concurrent",
                               len(live) + len(self._parked))
        for c, d in depths.items():
            self.stats.set(f"queued_{c}", d)
        # span emission OUTSIDE the engine lock: the sink may take its
        # own locks (trace buffer, histograms) and must not nest ours
        for rid, row, n_gen, vslo, by in preempted_info:
            self._span("preempted", rid, slot=row, tokens=n_gen,
                       slo=vslo, by=by)
        for rid, row, plen, cls, resumed in admitted_info:
            # `resumed` marks a preempt-resume RE-admission: observers
            # must not treat it as a fresh queue-wait sample (the gap
            # since submit includes the victim's pre-preemption
            # service time, not backlog)
            self._span("admitted", rid, slot=row, prompt_tokens=plen,
                       slo=cls, resumed=resumed)
        return live, admitted, admitted_info

    def _decode_prep(self, live: List[int], admitted: bool
                     ) -> Optional[Tuple[str, List[int], bool, Tuple]]:
        """Everything between prefill and the decode launch: settle the
        lanes, pick the path, map the pages the call will write and
        build its operands. Returns ``(path, live, any_sampling,
        operands)`` with ``path`` ``"scan"`` or ``"spec"`` (operands
        empty: :meth:`_speculative_step` builds its own), or None when
        no lane is left to run."""
        if not live:
            self._prefetch_hint()
            return None
        # prefill-only slots that reached their last prompt token are
        # done NOW: extract their KV shipment and free the lane before
        # the decode scan (they never generate)
        self._harvest_prefill_only()
        # chunked prefill / prefill-only harvest may have parked or
        # freed lanes: the scan must see the CURRENT occupancy
        live = [i for i in range(self.B) if self._slots[i] is not None]
        if admitted or self._prompt_dev is None:
            # refresh the device-resident prompts only when they changed
            self._prompt_dev = jnp.asarray(self._prompt_buf)
        self._prefetch_hint()
        if not live:
            return None

        any_sampling = bool(any(
            self._slots[i] is not None and self._slots[i].temperature > 0
            for i in range(self.B)))
        # speculative path: all live slots greedy, past their prompts,
        # room for a full draft window in the cache, and recent
        # acceptance above break-even (or a periodic re-probe) —
        # otherwise this fused call runs the plain scan (the paths
        # interleave freely call-to-call; both emit exact argmax tokens)
        if (self._verify_fn is not None and not any_sampling
                and (self._spec_ema >= self._spec_floor
                     or self._spec_idle >= SPEC_REPROBE_CALLS)
                and all(self._pos[i] >= len(self._slots[i].prompt) - 1
                        and int(self._pos[i]) + self.spec_k <= self.L
                        for i in live)):
            return "spec", live, any_sampling, ()
        if self._verify_fn is not None:
            self._spec_idle += 1
        if self.paged:
            for i in live:
                # the fused scan writes positions pos..pos+K-1, frozen
                # at stop_pos-1: map exactly that window's pages. The
                # slot re-check guards tiered engines: an earlier
                # lane's growth can PARK this one inside this loop —
                # allocating for the emptied lane would leak its pages
                if self._slots[i] is None:
                    continue
                self._ensure_pages_to(i, min(
                    int(self._pos[i]) + self.K,
                    int(self._stop_pos[i])) - 1)
        return "scan", live, any_sampling, (
            self.params, self._cache, jnp.asarray(self._tok),
            jnp.asarray(self._pos), self._prompt_dev,
            jnp.asarray(self._prompt_len), jnp.asarray(self._stop_pos),
            jnp.asarray(self._temp), jnp.asarray(self._topk),
            jnp.asarray(self._topp), jnp.asarray(self._seed),
            jnp.asarray(self._aid), self._ptab_arg(),
            *(() if self._counts_dev is None else (self._counts_dev,)))

    def _harvest_scan(self, live: List[int], emitted: np.ndarray,
                      any_sampling: bool) -> None:
        """Book one fused scan's output: counters, the draft mirror,
        each lane's new tokens, finished requests and their pages."""
        self.stats.inc("steps", self.K)
        if self.paged_kernel_active:
            # every live lane ran K single-token steps through the
            # step kernel inside this fused call
            self.stats.inc(
                "paged_kernel_step_tokens",
                self.K * sum(1 for s in self._slots if s is not None))
        if self._draft_cache is not None:
            if not any_sampling and (
                    self._spec_ema >= self._spec_floor
                    or self._spec_idle >= SPEC_REPROBE_CALLS - 1):
                if not self._draft_synced:
                    self._resync_draft()
                self._mirror_scan_onto_draft(emitted)
            else:
                # speculation can't pay off right now (gate off, or
                # sampling slots block the all-greedy precondition):
                # skip the per-scan mirror — a draft engine must not be
                # slower than no draft — and let the next re-probe
                # rebuild the cache from accepted contexts
                self._draft_synced = False

        finished: List[Tuple[Any, List[int]]] = []
        for i in live:
            slot = self._slots[i]
            if slot is None:
                continue  # parked mid-call by a page reclaim: its
                #           lane idled through the scan (stop_pos 0)
            plen = len(slot.prompt)
            pos0 = int(self._pos[i])
            # steps this slot actually took inside the fused program
            # (slots that hit their stop mid-scan idle for the rest)
            n_real = max(0, min(self.K, int(self._stop_pos[i]) - pos0,
                                self.L - pos0))
            eos_hit = False
            n0 = len(slot.generated)
            for j in range(n_real):
                if pos0 + j >= plen - 1:  # emission at a generated pos
                    t = int(emitted[j, i])
                    if slot.eos_id is not None and t == slot.eos_id:
                        # EOS ends the request; drop it and whatever the
                        # fused call computed past it
                        eos_hit = True
                        break
                    slot.generated.append(t)
            n1 = len(slot.generated)
            if n1 > n0:
                self.stats.inc("tokens_generated", n1 - n0)
                self._mark_progress(slot, n0, n1)
            slot.n_consumed += n_real
            self._pos[i] = pos0 + n_real
            if (eos_hit or len(slot.generated) >= slot.max_new
                    or int(self._pos[i]) >= self.L):
                # prior + generated: a preempt-resumed request replies
                # with its FULL output (the re-ingested prefix counts)
                finished.append((slot.request_id,
                                 slot.prior + slot.generated))
                self._slots[i] = None
                self._tok[i] = 0
                self._pos[i] = 0  # fresh occupant restarts at position 0
                self._prompt_len[i] = 1
                self._stop_pos[i] = 0
                if self.paged:  # pages (and the reservation) free NOW,
                    self._release_slot_pages(i)  # not at slot reuse
            else:
                # reconstruct the next input host-side (mirrors the
                # on-device selection, so the next fused call continues
                # seamlessly)
                self._tok[i] = (slot.prompt[slot.n_consumed]
                                if slot.n_consumed < plen
                                else slot.generated[-1])
        if finished:
            with self._lock:
                self._done.extend(finished)
                self.stats.inc("requests_done", len(finished))
            for rid, toks in finished:
                self._span("done", rid, tokens=len(toks))

    def _mark_progress(self, slot: "_Slot", n0: int, n1: int) -> None:
        """first_token / periodic decode_mark events for a slot that
        grew from ``n0`` to ``n1`` generated tokens this call. The
        decode_mark is the sink's alone: nothing reads it from the
        ring."""
        if not slot.first_tokened:
            # flag, not n0 == 0: a preempt-resumed slot restarts its
            # generated list at 0 but its stream already first-tokened
            slot.first_tokened = True
            self._span("first_token", slot.request_id)
        if n0 // SPAN_DECODE_MARK_EVERY != n1 // SPAN_DECODE_MARK_EVERY:
            self._to_sink("decode_mark", slot.request_id, {"tokens": n1})

    def _resync_draft(self) -> None:
        """Rebuild the draft cache from every live slot's ACCEPTED
        context (prompt + generated, positions 0..pos-1). Runs when a
        re-probe follows a gated-off stretch during which scan mirrors
        were skipped — a bounded number of K-chunk passes instead of a
        mirror on every gated scan."""
        self._draft_cache = _empty_cache(self.draft_module, self.B)
        ctxs = {}
        maxp = 0
        for i in range(self.B):
            s = self._slots[i]
            if s is None:
                continue
            ctx = np.concatenate(
                [s.prompt, np.asarray(s.generated, np.int32)])
            ctxs[i] = ctx[:int(self._pos[i])]
            maxp = max(maxp, len(ctxs[i]))
        for c0 in range(0, maxp, self.K):
            tok_m = np.zeros((self.B, self.K), np.int32)
            pos_m = np.zeros((self.B, self.K), np.int32)
            for i in range(self.B):
                ctx = ctxs.get(i)
                if ctx is None or len(ctx) <= c0:
                    # nothing (left) for this lane: idempotent rewrite
                    # of its current token at its current position
                    tok_m[i, :] = self._tok[i]
                    pos_m[i, :] = self._pos[i]
                    continue
                n = min(self.K, len(ctx) - c0)
                tok_m[i, :n] = ctx[c0:c0 + n]
                pos_m[i, :n] = np.arange(c0, c0 + n)
                tok_m[i, n:] = tok_m[i, n - 1]
                pos_m[i, n:] = pos_m[i, n - 1]
            self._draft_cache = self._draft_sync_k(
                self.draft_params, self._draft_cache,
                jnp.asarray(tok_m), jnp.asarray(pos_m),
                jnp.asarray(self._aid), self._ptab_arg())
        self._draft_synced = True
        self.stats.inc("draft_resyncs")

    def _mirror_scan_onto_draft(self, emitted: np.ndarray) -> None:
        """Write the fused scan's ACTUALLY-CONSUMED inputs into the
        draft cache (one multi-token KV pass) so the draft stays
        token-for-token synced with the target through prompts,
        generation, and mixed admission — the invariant draft-model
        speculation relies on. Idle lanes re-write their current token
        at their current position (idempotent)."""
        tok_m = np.empty((self.B, self.K), np.int32)
        pos_m = np.empty((self.B, self.K), np.int32)
        for i in range(self.B):
            s = self._slots[i]
            p0 = int(self._pos[i])
            cur = int(self._tok[i])
            if s is None:
                tok_m[i, :] = cur
                pos_m[i, :] = p0
                continue
            plen = len(s.prompt)
            n_real = max(0, min(self.K, int(self._stop_pos[i]) - p0,
                                self.L - p0))
            for j in range(self.K):
                if j < n_real:
                    p = p0 + j
                    if j == 0:
                        t = cur
                    elif p < plen:
                        t = int(s.prompt[p])
                    else:  # generated region: the previous step's token
                        t = int(emitted[j - 1, i])
                    tok_m[i, j], pos_m[i, j] = t, p
                else:  # idle remainder: idempotent rewrite of the last
                    tok_m[i, j] = tok_m[i, j - 1] if j else cur
                    pos_m[i, j] = pos_m[i, j - 1] if j else p0
        self._draft_cache = self._draft_sync_k(
            self.draft_params, self._draft_cache, jnp.asarray(tok_m),
            jnp.asarray(pos_m), jnp.asarray(self._aid),
            self._ptab_arg())

    def _speculative_step(self, live: List[int]) -> int:
        """One verify call: host-drafted continuations for every live
        slot ride through a single multi-token cache step; each slot
        emits its accepted prefix plus the model's own token at the
        first mismatch (1..spec_k tokens). Rejected drafts leave stale
        KV rows ABOVE the slot's new position — unreachable by the
        position mask, and rewritten in place when generation reaches
        them (the admission-reuse invariant already relies on this).
        The same four leaf spans as the scan: ``engine.decode_prep`` /
        ``decode_dispatch`` / ``sync_wait`` / ``harvest`` (a draft
        model's own scan and pull are a dispatch and a wait too)."""
        k = self.spec_k
        if self._draft_cache is not None:
            with SPANS.span("engine.decode_prep"):
                if not self._draft_synced:  # re-probe after a gated-off
                    self._resync_draft()    # stretch with skipped mirrors
                draft_operands = (
                    self.draft_params, self._draft_cache,
                    jnp.asarray(self._tok), jnp.asarray(self._pos),
                    self._prompt_dev, jnp.asarray(self._prompt_len),
                    jnp.asarray(self._stop_pos), jnp.asarray(self._temp),
                    jnp.asarray(self._topk), jnp.asarray(self._topp),
                    jnp.asarray(self._seed), jnp.asarray(self._aid),
                    self._ptab_arg())
            # draft phase: k-1 fused greedy steps on the DRAFT model
            # (argmax feedback), advancing its synced cache; then the
            # verify mirror writes the window's inputs [tok, drafts]
            # so the final row exists for fully-accepted windows
            with SPANS.span("engine.decode_dispatch"):
                self._draft_cache, d_emit = self._draft_scan(
                    *draft_operands)
                del draft_operands  # see _turn()
            # draft tokens feed the host-built verify operands: one
            # pull per K-token window
            d_emit, = self._sync_wait(d_emit)
            drafts = d_emit.T.astype(np.int32)
        with SPANS.span("engine.decode_prep"):
            if self._draft_cache is not None:
                offs = np.arange(k, dtype=np.int32)[None, :]
                self._draft_cache = self._draft_sync_v(
                    self.draft_params, self._draft_cache,
                    jnp.asarray(np.concatenate(
                        [self._tok[:, None], drafts], axis=1)),
                    jnp.asarray(self._pos[:, None] + offs),
                    jnp.asarray(self._aid), self._ptab_arg())
                self.stats.inc("spec_draft_model_calls")
            else:
                drafts = np.zeros((self.B, k - 1), np.int32)
                for i in live:
                    s = self._slots[i]
                    ctx = np.concatenate(
                        [s.prompt, np.asarray(s.generated, np.int32)])
                    drafts[i] = _ngram_draft(ctx, k - 1)
            if self.paged:
                for i in live:
                    # the verify window writes positions pos..pos+k-1
                    # (gated above to fit the cache); its pages must
                    # exist even for drafts that end up rejected — the
                    # standard unreachable-then-rewritten rows, inside
                    # reservation. Slot re-check: a mid-loop park
                    # (tiered page reclaim) empties a later lane — see
                    # _chunked_prefill
                    if self._slots[i] is None:
                        continue
                    self._ensure_pages_to(i, min(
                        int(self._pos[i]) + k - 1, self.L - 1))
            operands = (
                self.params, self._cache, jnp.asarray(self._tok),
                jnp.asarray(self._pos), jnp.asarray(drafts),
                jnp.asarray(self._stop_pos), jnp.asarray(self._aid),
                self._ptab_arg())
        with SPANS.span("engine.decode_dispatch"):
            self._cache, g, n_emit = self._verify_fn(*operands)
            del operands  # see _turn()
        # verify OUTPUT sync: accepted tokens must reach the host to
        # stream, and the acceptance counts gate the host-side emit
        g, n_emit = self._sync_wait(g, n_emit)
        with SPANS.span("engine.harvest"):
            self._harvest_verify(live, k, g, n_emit)
        return len(live)

    def _harvest_verify(self, live: List[int], k: int, g: np.ndarray,
                        n_emit: np.ndarray) -> None:
        """Book one verify call's output: counters, the acceptance
        gate, each lane's accepted tokens, finished requests."""
        self.stats.inc("steps")
        self.stats.inc("spec_calls")
        if self.paged_kernel_windowed:
            # each live lane attended a k-wide verify window through
            # the window kernel (the draft model's own mirror passes
            # stay contiguous and are not counted here)
            self.stats.inc("paged_kernel_window_tokens", k * len(live))
        self._spec_idle = 0
        self._spec_ema = (SPEC_EMA_DECAY * self._spec_ema
                          + (1 - SPEC_EMA_DECAY)
                          * float(np.mean(n_emit[live])))

        finished: List[Tuple[Any, List[int]]] = []
        for i in live:
            slot = self._slots[i]
            if slot is None:
                continue  # parked mid-call by a page reclaim
            pos0 = int(self._pos[i])
            take = max(1, min(int(n_emit[i]),
                              int(self._stop_pos[i]) - pos0,
                              self.L - pos0))
            toks = [int(t) for t in g[i, :take]]
            eos_hit = slot.eos_id is not None and slot.eos_id in toks
            if eos_hit:  # drop the EOS and anything verified past it
                toks = toks[:toks.index(slot.eos_id)]
            n0 = len(slot.generated)
            slot.generated.extend(toks)
            slot.n_consumed += take
            self._pos[i] = pos0 + take
            if toks:
                self.stats.inc("tokens_generated", len(toks))
                self._mark_progress(slot, n0, len(slot.generated))
            self.stats.inc("spec_drafted", k - 1)
            self.stats.inc("spec_accepted", take - 1)
            if (eos_hit or len(slot.generated) >= slot.max_new
                    or int(self._pos[i]) >= self.L):
                finished.append((slot.request_id,
                                 slot.prior + slot.generated))
                self._slots[i] = None
                self._tok[i] = 0
                self._pos[i] = 0
                self._prompt_len[i] = 1
                self._stop_pos[i] = 0
                if self.paged:
                    self._release_slot_pages(i)
            else:
                self._tok[i] = slot.generated[-1]
        if finished:
            with self._lock:
                self._done.extend(finished)
                self.stats.inc("requests_done", len(finished))
            for rid, toks in finished:
                self._span("done", rid, tokens=len(toks))


def _ngram_draft(context: np.ndarray, k: int, max_n: int = 3) -> np.ndarray:
    """Prompt-lookup drafting: find the longest (≤ ``max_n``) suffix
    n-gram of ``context`` with an earlier occurrence and propose the
    ``k`` tokens that followed its most recent match; repeat-last when
    nothing matches. Pure host-side numpy — drafting costs no device
    time, and a bad draft costs nothing but its rejected verify lanes."""
    ctx = np.asarray(context, np.int32).ravel()
    n_ctx = len(ctx)
    for n in range(min(max_n, n_ctx - 1), 0, -1):
        suffix = ctx[n_ctx - n:]
        # windows over ctx[:-1]: every start whose n-gram ends before
        # the suffix's own final token
        windows = np.lib.stride_tricks.sliding_window_view(ctx[:-1], n)
        hits = np.nonzero(np.all(windows == suffix, axis=1))[0]
        if len(hits):
            j = int(hits[-1]) + n  # continuation of the latest match
            cont = ctx[j:j + k]
            if len(cont) < k:
                cont = np.concatenate(
                    [cont, np.full(k - len(cont), ctx[-1], np.int32)])
            return cont.astype(np.int32)
    return np.full(k, ctx[-1], np.int32)


def _select_next(logits, temp, top_k, top_p, seed, pos):
    """Per-slot token selection on device: greedy when ``temp <= 0``,
    else temperature-scaled categorical over the top-k/top-p-filtered
    distribution. Both filters reduce to a per-row LOGIT THRESHOLD on
    the descending sort (k-th largest for top-k; the smallest logit of
    the minimal nucleus for top-p), so one sort serves both and the
    masked sample needs no gather back through sort order. The PRNG key
    is ``fold_in(fold_in(base, seed), position)`` — a pure function of
    (seed, position), so draws are reproducible under any batch
    composition, slot placement, or step fusion."""
    v = logits.shape[-1]
    greedy = jnp.argmax(logits, -1).astype(jnp.int32)
    lg = logits / jnp.maximum(temp, 1e-6)[:, None]
    sorted_lg = jnp.sort(lg, axis=-1)[:, ::-1]  # descending
    kk = jnp.clip(jnp.where(top_k <= 0, v, top_k), 1, v)
    k_thresh = jnp.take_along_axis(
        sorted_lg, (kk - 1)[:, None].astype(jnp.int32), axis=-1)
    probs = jax.nn.softmax(sorted_lg, -1)
    cum = jnp.cumsum(probs, -1)
    # keep the minimal prefix whose mass reaches top_p (the first token
    # is always kept: its "mass before" is 0 < top_p)
    keep = (cum - probs) < jnp.maximum(top_p, 1e-6)[:, None]
    p_thresh = jnp.min(jnp.where(keep, sorted_lg, jnp.inf), -1,
                       keepdims=True)
    masked = jnp.where(lg >= jnp.maximum(k_thresh, p_thresh), lg, -1e30)
    base = jax.random.PRNGKey(0)
    keys = jax.vmap(lambda s, p: jax.random.fold_in(
        jax.random.fold_in(base, s), p))(seed, pos)
    sampled = jax.vmap(jax.random.categorical)(keys,
                                               masked).astype(jnp.int32)
    return jnp.where(temp <= 0.0, greedy, sampled)


def _mutable_collections(module: Any) -> List[str]:
    """What a decode-path ``apply`` lets the module write: its cache,
    and — for a module that names ``device_counters`` — the
    ``"counters"`` collection its layers sow their counts into."""
    return ["cache", "counters"] if getattr(
        module, "device_counters", ()) else ["cache"]


def _add_counts(module: Any, counts: Sequence[jnp.ndarray], muts: Any
                ) -> List[jnp.ndarray]:
    """The running device counters (one int32 vector, or none) plus
    what this ``apply`` sowed, summed over layers — or, for a module
    whose layers sow vectors of several kinds, folded into one by its
    ``fold_device_counters``."""
    fold = getattr(module, "fold_device_counters", None) or (
        lambda sown: sum(jax.tree_util.tree_leaves(sown)))
    return [c + fold(muts.get("counters", {})) for c in counts]


@functools.lru_cache(maxsize=8)
def _make_step(module: Any, n_slots: int, k: int,
               sampling: bool) -> Callable:
    """K fused decode steps over all slots (cache donated in-place).

    On-device input selection between steps: while a slot's next
    position is still inside its prompt, the next input is the next
    prompt token (device-resident prompt buffer); afterwards it is the
    slot's own sampled/greedy token (``_select_next`` when ``sampling``,
    plain argmax otherwise — the greedy program never compiles the
    sampler's per-token vocab sort). Slots whose next position reaches
    ``stop_pos`` freeze (their tok/pos stop advancing) so a finished
    slot idles harmlessly for the remainder of the scan.

    Multi-adapter modules additionally consume the per-slot ``aid``
    operand (which stacked fine-tune each row decodes under); paged-KV
    modules the per-slot ``ptab`` page tables (a tiny ignored constant
    otherwise — one signature for both layouts). A module with
    ``device_counters`` takes their running int32 vector as one more
    operand and returns it, its K steps' counts added, as one more
    output (see :func:`_add_counts`).

    A module with per-slot state (``slot_state``) is told, at every
    step, which slot each row is (row ``i`` is slot ``i``) and whether
    its token is real: a recurrence is advanced by a re-fed token as by
    any other, so a lane that is empty, or froze at its ``stop_pos``
    earlier in this scan, has 0 real tokens and its state is left
    alone."""
    multi = int(getattr(module, "n_adapters", 0) or 0) > 0
    paged = int(getattr(module, "kv_page_size", 0) or 0) > 0
    slot_state = bool(getattr(module, "slot_state", ()))
    mutable = _mutable_collections(module)

    @functools.partial(jax.jit, donate_argnums=(1,))
    def step_fn(params, cache, tok, pos, prompt_buf, prompt_len, stop_pos,
                temp, top_k, top_p, seed, aid, ptab, *counts):
        rows = jnp.arange(n_slots)

        def body(carry, _):
            cache, tok, pos, alive, *counts = carry
            logits, muts = module.apply(
                {"params": params, "cache": cache}, tok[:, None],
                positions=pos[:, None], decode=True, mutable=mutable,
                **({"adapter_ids": aid} if multi else {}),
                **({"page_tables": ptab} if paged else {}),
                **({"slot_ids": rows, "row_tokens": alive.astype(jnp.int32)}
                   if slot_state else {}))
            counts = _add_counts(module, counts, muts)
            lg = logits[:, -1].astype(jnp.float32)
            if sampling:
                nxt = _select_next(lg, temp, top_k, top_p, seed, pos)
            else:
                nxt = jnp.argmax(lg, -1).astype(jnp.int32)
            new_pos = pos + 1
            is_prefill = new_pos < prompt_len
            nxt_prompt = prompt_buf[
                rows, jnp.minimum(new_pos, prompt_buf.shape[1] - 1)]
            nxt_input = jnp.where(is_prefill, nxt_prompt, nxt)
            active = new_pos < stop_pos
            tok2 = jnp.where(active, nxt_input, tok)
            pos2 = jnp.where(active, new_pos, pos)
            return (muts["cache"], tok2, pos2, alive & active,
                    *counts), nxt

        (cache, tok, pos, _, *counts), emitted = jax.lax.scan(
            body, (cache, tok, pos, pos < stop_pos, *counts), None,
            length=k)
        return (cache, emitted, *counts)  # emitted: (K, n_slots)

    return step_fn


@functools.lru_cache(maxsize=8)
def _make_verify(module: Any, n_slots: int, k: int) -> Callable:
    """One speculative verify step: feed each slot's current token plus
    its k-1 drafted continuations at positions pos..pos+k-1 through the
    decode-cache path (the chunked-prefill machinery — KV for the whole
    window is written before attention, and each query only sees keys
    at-or-before its own position). ``g[:, j]`` is the model's argmax
    AFTER input j, so draft j+1 is correct iff it equals ``g[:, j]``;
    ``n_emit`` = 1 + the length of the all-correct draft prefix — every
    emitted token is conditioned only on accepted history, which is what
    makes greedy speculation lossless. Free/finished slots re-feed their
    current token at their current position (an idempotent rewrite)."""

    multi = int(getattr(module, "n_adapters", 0) or 0) > 0
    paged = int(getattr(module, "kv_page_size", 0) or 0) > 0

    @functools.partial(jax.jit, donate_argnums=(1,))
    def verify_fn(params, cache, tok, pos, drafts, stop_pos, aid, ptab):
        active = (pos < stop_pos)[:, None]
        offs = jnp.arange(k)[None, :]
        seq = jnp.concatenate([tok[:, None], drafts], axis=1)
        seq = jnp.where(active, seq, tok[:, None])
        positions = jnp.where(active, pos[:, None] + offs, pos[:, None])
        logits, muts = module.apply(
            {"params": params, "cache": cache}, seq,
            positions=positions, decode=True, mutable=["cache"],
            **({"adapter_ids": aid} if multi else {}),
            **({"page_tables": ptab} if paged else {}))
        g = jnp.argmax(logits.astype(jnp.float32), -1).astype(jnp.int32)
        ok = jnp.cumprod((drafts == g[:, :-1]).astype(jnp.int32), axis=1)
        n_emit = 1 + jnp.sum(ok, axis=1).astype(jnp.int32)
        return muts["cache"], g, n_emit

    return verify_fn


@functools.lru_cache(maxsize=32)
def _make_prefix_install(plen: int) -> Callable:
    """Scatter a trimmed prefix snapshot into slot rows. Cached by
    prefix length so N same-text registrations (one per adapter in a
    multi-tenant boot) share ONE compiled program — only the forward
    prefill execution is genuinely per-adapter."""

    @functools.partial(jax.jit, donate_argnums=(0,))
    def install(cache, pre, rws):
        return jax.tree_util.tree_map(
            lambda c, p: c.at[rws, :plen].set(
                p[:, :plen].astype(c.dtype)), cache, pre)

    return install


@functools.lru_cache(maxsize=32)
def _make_paged_prefix_install(plen: int, page_size: int) -> Callable:
    """Paged-engine twin of :func:`_make_prefix_install`: scatter a
    (1, plen, …) contiguous snapshot into the hit slots' PAGES —
    ``tabs`` is the (n_rows, n_tables) page-table slice of exactly the
    rows being installed, whose prefix pages the engine allocated at
    admission. Cached by (length, page size) like its contiguous twin.
    A leaf whose pages are not ``page_size`` rows long packs several
    positions a row (the latent pool's rotary keys): the op that wrote
    it places the rows."""

    @functools.partial(jax.jit, donate_argnums=(0,))
    def install(cache, pre, tabs):
        pos = jnp.arange(plen)
        pg = tabs[:, pos // page_size]   # (n_rows, plen) pool pages
        off = jnp.broadcast_to(pos % page_size, pg.shape)  # in-page

        def put(c, p):
            vals = jnp.broadcast_to(
                p[:, :plen].astype(c.dtype),
                (tabs.shape[0], plen) + p.shape[2:])
            if c.shape[1] != page_size:
                return packed_key_write(c, pg, off, vals)
            return c.at[pg, off].set(vals)

        return jax.tree_util.tree_map(put, cache, pre)

    return install


@functools.lru_cache(maxsize=8)
def _make_prefill(module: Any, n_slots: int, chunk: int) -> Callable:
    """One C-token prefill call: feed (B, C) tokens at their per-slot
    positions through the decode-cache path. The lm_head output is
    discarded (prefill emits nothing), so XLA dead-code-eliminates the
    (B, C, vocab) projection — the call is pure KV-cache population at
    matmul (not matvec) arithmetic intensity. ``rows`` (a module with
    per-slot state alone): each row's slot and its real tokens."""
    multi = int(getattr(module, "n_adapters", 0) or 0) > 0
    paged = int(getattr(module, "kv_page_size", 0) or 0) > 0

    mutable = _mutable_collections(module)

    @functools.partial(jax.jit, donate_argnums=(1,))
    def prefill_fn(params, cache, tok_chunk, pos_chunk, aid, ptab,
                   *counts, rows=None):
        _, muts = module.apply(
            {"params": params, "cache": cache}, tok_chunk,
            positions=pos_chunk, decode=True, mutable=mutable,
            **({"adapter_ids": aid} if multi else {}),
            **({"page_tables": ptab} if paged else {}),
            **({} if rows is None else
               {"slot_ids": rows[0], "row_tokens": rows[1]}))
        # ``counts`` is the *args tuple: its length is static under jit.
        # None handed in (a prefix snapshot, a draft's mirror pass, a
        # module that names no counters): the cache alone
        if counts:  # rafiki: noqa[jax-tracer-branch] — a tuple's length
            return (muts["cache"], *_add_counts(module, counts, muts))
        return muts["cache"]

    return prefill_fn


@functools.partial(jax.jit, donate_argnums=(0,))
def _install_pages(flat: List[jnp.ndarray], idx: jnp.ndarray,
                   staged: List[jnp.ndarray]) -> List[jnp.ndarray]:
    """Shipment install, paged layout: scatter each staged leaf's
    pages into the donated cache leaves at ``idx``. Donation makes
    this an in-place write of the touched pages; the jit cache keys on
    (n_pages, leaf shapes), so one compile serves every same-length
    shipment engine-wide."""
    return [c.at[idx].set(v.astype(c.dtype))
            for c, v in zip(flat, staged)]


@functools.partial(jax.jit, donate_argnums=(0,))
def _install_rows(flat: List[jnp.ndarray], row: jnp.ndarray,
                  staged: List[jnp.ndarray]) -> List[jnp.ndarray]:
    """Shipment install, contiguous layout: write each staged leaf
    ``(covered, …)`` into the donated cache leaves at slot ``row``,
    positions ``0..covered-1``."""
    out = []
    for c, v in zip(flat, staged):
        upd = v.astype(c.dtype)[None]
        starts = (row,) + (jnp.int32(0),) * (c.ndim - 1)
        out.append(jax.lax.dynamic_update_slice(c, upd, starts))
    return out


class TextDecodeEngine:
    """Text-level wrapper: encode prompts, detokenize completions.

    ``encode(text) -> 1-D int32 ids`` and ``decode(ids) -> text`` come
    from the owning model template (see ``LlamaLoRA.make_decode_engine``).
    """

    #: the inference worker checks this before forwarding a failover
    #: request's ``forced_prefix`` (duck-typed user engines without the
    #: kwarg must get a structured rejection, not a TypeError that
    #: kills the serve thread)
    supports_resume = True
    #: ditto for the ``slo`` admission-class kwarg: the worker only
    #: forwards it to engines that declare the capability (a duck-typed
    #: user engine must degrade to classless FIFO, not TypeError)
    supports_slo = True
    #: ditto for disaggregated prefill/decode: ``submit_prefill`` /
    #: ``poll_kv`` on the prefill leg and ``submit(..., kv_blob=)`` on
    #: the decode leg — role-configured workers check this at boot so
    #: a duck-typed user engine fails the deploy, not the serve thread
    supports_kv_ship = True

    def __init__(self, engine: DecodeEngine,
                 encode: Callable[[str], np.ndarray],
                 decode: Callable[[List[int]], str],
                 max_new: int = 8, resume_sep: str = " ") -> None:
        self.engine = engine
        self._encode = encode
        self._decode = decode
        self.max_new = int(max_new)
        #: text joint between a prompt and a forced resume prefix (and
        #: between the prefix and the continuation decode): " " matches
        #: both tokenizer families — the hash tokenizer splits/joins on
        #: whitespace exactly, and the byte-BPE detok lstrips the
        #: leading space its first generated token usually carries
        self._sep = resume_sep
        self._stream_sent: Dict[Any, str] = {}  # rid -> text delivered
        #: rid -> forced resume prefix (failover re-submissions): the
        #: already-delivered text the engine re-ingests as prompt but
        #: which deltas/finals must present as generated output
        self._forced: Dict[Any, str] = {}
        #: resume requests whose prefix already covered the whole token
        #: budget: completed without touching the engine, surfaced on
        #: the next poll()
        self._forced_done: List[Tuple[Any, str]] = []

    def submit(self, request_id: Any, text: str,
               max_new: Optional[int] = None, temperature: float = 0.0,
               top_k: int = 0, top_p: float = 1.0, seed: int = 0,
               eos_id: Optional[int] = None, adapter_id: int = 0,
               forced_prefix: str = "", slo: str = "",
               kv_blob: Optional[Dict[str, Any]] = None) -> None:
        """``forced_prefix`` (streaming failover / client resume): text
        a previous worker already emitted for this request. It is
        re-ingested as part of the prompt (the engine's chunked-prefill
        path — prefix compute at matmul intensity, no decode steps),
        the token budget shrinks by the tokens it covers, and deltas /
        the final text present it as OUTPUT — the resumed stream
        continues exactly where the dead one stopped, without
        re-emitting or dropping text. Greedy continuations are
        token-exact whenever re-tokenizing prompt+prefix reproduces the
        original token boundaries (true for the whitespace tokenizer;
        byte-BPE may shift a boundary at the splice, in which case the
        predictor's replace/divergence machinery still keeps the client
        consistent)."""
        budget = self.max_new if max_new is None else int(max_new)
        if forced_prefix:
            full = text + self._sep + forced_prefix
            covered = max(0, len(self._encode(full))
                          - len(self._encode(text)))
            remaining = budget - covered
            if remaining <= 0:
                # the dead worker had already generated the whole
                # budget; only its final message was lost — complete
                # instantly with the prefix as the authoritative text
                self._forced_done.append((request_id,
                                          str(forced_prefix)))
                return
            self._forced[request_id] = str(forced_prefix)
            self._stream_sent[request_id] = str(forced_prefix)
            text, budget = full, remaining
            kv_blob = None  # a shipment covers the ORIGINAL prompt;
            # the resume prompt is longer, so re-ingest via chunked
            # prefill instead of installing mismatched rows
        kw = {}
        if kv_blob is not None:
            kw["kv_import"] = kv_blob
        self.engine.submit(request_id, self._encode(text), budget,
                           temperature=temperature, top_k=top_k,
                           top_p=top_p, seed=seed, eos_id=eos_id,
                           adapter_id=adapter_id, slo=slo, **kw)

    def submit_prefill(self, request_id: Any, text: str,
                       max_new: Optional[int] = None,
                       adapter_id: int = 0, slo: str = "") -> None:
        """Prefill-role submission (disaggregated serving): chew the
        prompt through chunked prefill and surface its KV shipment via
        :meth:`poll_kv` — no tokens are generated here; the decode leg
        installs the blob and runs the tight single-token loop."""
        self.engine.submit(request_id, self._encode(str(text)),
                           self.max_new if max_new is None
                           else int(max_new),
                           adapter_id=adapter_id, slo=slo,
                           prefill_only=True)

    def poll_kv(self) -> List[Tuple[Any, Dict[str, Any]]]:
        """Finished prefill-only shipments (see
        :meth:`DecodeEngine.poll_kv`)."""
        return self.engine.poll_kv()

    def stage_kv_blob(self, blob: Dict[str, Any]) -> Dict[str, Any]:
        """Pre-upload an arrived shipment's leaves (see
        :meth:`DecodeEngine.stage_kv_blob`)."""
        return self.engine.stage_kv_blob(blob)

    def export_prefix(self, adapter_id: int = 0):
        return self.engine.export_prefix(adapter_id=adapter_id)

    def import_prefix(self, blob, adapter_id: int = 0) -> int:
        return self.engine.import_prefix(blob, adapter_id=adapter_id)

    def _full_text(self, rid: Any, ids: List[int]) -> str:
        """The request's cumulative OUTPUT text: decoded generated ids,
        preceded by the forced resume prefix when one is active."""
        text = self._decode(ids)
        base = self._forced.get(rid)
        if base is not None:
            text = base + (self._sep + text if text else "")
        return text

    def poll(self) -> List[Tuple[Any, str]]:
        done = [(rid, self._full_text(rid, ids))
                for rid, ids in self.engine.poll()]
        done.extend(self._forced_done)
        self._forced_done = []
        for rid, _ in done:  # a finished request stops streaming state
            self._stream_sent.pop(rid, None)
            self._forced.pop(rid, None)
        return done

    def poll_partial(self) -> List[Tuple[Any, str]]:
        """(request_id, new text) for live requests since the last call.

        Each event re-detokenizes the cumulative ids and emits the text
        suffix past what was already delivered — cumulative decoding is
        the only well-formed view under byte-level BPE (a token boundary
        may split a multi-byte character, so per-token decodes are not
        concatenation-safe). Trailing replacement characters (U+FFFD —
        an incomplete UTF-8 sequence whose remaining bytes are still
        being generated) are WITHHELD until a later decode resolves
        them: emitted text comes only from byte-complete prefixes, so
        the delivered stream is append-only and deltas concatenate
        correctly. Genuinely invalid bytes (never completed) surface in
        the final text instead. Suffix-empty events are dropped."""
        out: List[Tuple[Any, str]] = []
        for rid, ids in self.engine.poll_partial():
            text = self._full_text(rid, ids).rstrip("�")
            sent = self._stream_sent.get(rid, "")
            if len(text) > len(sent) and text.startswith(sent):
                out.append((rid, text[len(sent):]))
                self._stream_sent[rid] = text
        return out

    def register_prefix(self, text: str, adapter_id: int = 0) -> int:
        """Precompute KV for a shared prompt prefix (system prompt);
        see :meth:`DecodeEngine.register_prefix`. Call before serving
        traffic (not concurrently with ``step``)."""
        return self.engine.register_prefix(self._encode(text),
                                           adapter_id=adapter_id)

    def step(self) -> int:
        return self.engine.step()

    def reset(self) -> None:
        self._stream_sent.clear()
        self._forced.clear()
        self._forced_done.clear()
        self.engine.reset()

    def close(self) -> None:
        self.engine.close()

    def reset_stats(self) -> None:
        self.engine.reset_stats()

    @property
    def busy(self) -> bool:
        return self.engine.busy

    @property
    def stats(self) -> Dict[str, int]:
        return self.engine.stats

    def stats_snapshot(self) -> Dict[str, int]:
        return self.engine.stats_snapshot()

    @property
    def span_sink(self):
        return self.engine.span_sink

    @span_sink.setter
    def span_sink(self, sink) -> None:
        # request ids pass through submit untouched, so the token
        # engine's lifecycle events carry the caller's ids directly
        self.engine.span_sink = sink
