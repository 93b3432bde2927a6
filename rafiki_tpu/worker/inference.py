"""Inference worker: one serving replica of a best trial.

Parity target: the reference's ``worker/inference.py`` (SURVEY.md §3.3):
boot by loading a trial's parameters from the ParamStore, then loop —
block-pop the query queue, batch what's pending, run ``model.predict``,
push predictions keyed by query id.

TPU-first deltas:

- **Opportunistic micro-batching** (classification path): after a
  blocking pop the worker drains whatever else is queued (up to
  ``max_batch_msgs``) and runs one forward over the union — on TPU the
  forward is a compiled program whose cost is dominated by launch + HBM
  traffic, so batching waiting queries is nearly free throughput.
  Static-shape padding happens inside the template's ``predict``
  (bucketed), not here.
- **Continuous-batching decode loop** (generation path, BASELINE.md
  config #5): when constructed with ``decode_loop=True`` and the model
  exposes ``make_decode_engine`` (e.g. ``LlamaLoRA``), the worker runs
  a slot-based decode loop instead — new requests are admitted into
  free KV-cache slots at step boundaries while earlier requests are
  mid-generation, and replies go out per-message as each message's
  queries all complete.
"""

from __future__ import annotations

import collections
import math
import os
import threading
import time
import traceback
from typing import Any, Dict, List, Optional, Tuple, Type

import numpy as np

from ..model.base import BaseModel
from ..obs import (MetricsRegistry, ObsServer, StatsMap, TraceBuffer,
                   mint_trace_id)
from ..serving.kv_transfer import normalize_role
from ..serving.queues import (EXPIRY_SKEW_TOLERANCE_S, QueueHub,
                              pack_message, unpack_message)
from ..serving.slo import SLO_CLASSES, normalize_slo
from ..store.param_store import ParamStore

#: expiry pad for the RELATIVE (ttl_s) deadline path: the residual
#: error there is the skew-estimator's convergence slack, not raw
#: cross-host clock skew, so it is a fraction of the wall-clock
#: EXPIRY_SKEW_TOLERANCE_S it replaces
TTL_EXPIRY_PAD_S = 0.5

#: prefill-role outbox give-up window: generous enough for the
#: slowest chunked prefill to finish and ship, small enough that
#: never-completing legs (engine reset dropped the slot) can't grow
#: the outbox unboundedly on a long-lived worker. A pruned leg's
#: decode side re-prefilled locally when ITS (much shorter) kv_wait_s
#: window expired — pruning loses nothing.
_KV_OUTBOX_TTL_S = 600.0


class ClockSkewEstimator:
    """Skew-compensated elapsed time since a remote wall-clock stamp.

    Every scatter payload carries ``sent_ts`` (the predictor's wall
    clock at scatter). ``now - sent_ts`` observed here is *true elapsed
    + clock skew*; since elapsed is never negative and promptly-popped
    queries have near-zero elapsed, the MINIMUM of those observations
    converges on the skew itself (one-way-delay estimation, the NTP
    trick). Subtracting it yields an elapsed estimate that is immune to
    static cross-host skew — the failure mode where a worker clock
    running ahead silently dropped every fresh query while the
    predictor only saw timeouts (ADVICE r3). The estimate relaxes
    upward very slowly so a mid-run clock step eventually re-converges
    instead of poisoning the minimum forever."""

    #: upward relaxation per observation (dimensionless fraction of the
    #: gap): ~460 observations to close 99% of a step — minutes of
    #: traffic, versus never
    RELAX = 0.01

    def __init__(self) -> None:
        self._est: Optional[float] = None

    def elapsed_since(self, sent_ts: float) -> float:
        obs = time.time() - float(sent_ts)  # true elapsed + skew
        if self._est is None or obs < self._est:
            self._est = obs
        else:
            self._est += self.RELAX * (obs - self._est)
        return obs - self._est


class InferenceWorker:
    def __init__(self, model_class: Type[BaseModel], trial_id: str,
                 knobs: dict, param_store: ParamStore, hub: QueueHub,
                 worker_id: str, max_batch_msgs: int = 16,
                 decode_loop: bool = False, max_slots: int = 8,
                 max_new_tokens: int = 8, steps_per_sync: int = 4,
                 speculate_k: int = 0, system_prefix: str = "",
                 extra_adapter_trials: Optional[List[str]] = None,
                 draft_trial_id: str = "",
                 draft_knobs: Optional[dict] = None,
                 kv_page_size: int = 0, kv_pages: int = 0,
                 paged_kernel: Optional[bool] = None,
                 default_slo: str = "",
                 role: str = "", host_kv_pages: int = 0,
                 kv_wait_s: float = 1.5, pool_id: str = "",
                 chaos: Optional[Any] = None) -> None:
        self.worker_id = worker_id
        self.hub = hub
        self.max_batch_msgs = max_batch_msgs
        #: disaggregated serving role (``unified`` default): a
        #: ``prefill`` worker chews prompts through chunked prefill and
        #: ships the finished KV pages to the decode leg's worker over
        #: the hub; a ``decode`` worker holds shipped-KV requests for
        #: up to ``kv_wait_s`` and installs the blob at admission —
        #: falling back to a local re-prefill (token-exact, just
        #: slower) when the shipment is late, lost, or mismatched.
        #: Validated at boot: a typo'd role silently serving unified
        #: would defeat the router's placement policy.
        self.role = normalize_role(role)
        self.kv_wait_s = max(0.0, float(kv_wait_s))
        #: the job's pool id (scale-out plane): keys the shared
        #: prefix-snapshot blob so one replica's prefill serves all
        self.pool_id = str(pool_id or "")
        #: decode-role holding pen: message id -> (message, monotonic
        #: give-up deadline, {qi: blob}) — submitted when every
        #: query's shipment lands or the wait window expires
        self._pending_kv: Dict[Any, List[Any]] = {}
        #: prefill-role outbox: message id -> [ship-to worker id,
        #: trace id, queries still owed, monotonic give-up deadline];
        #: poll_kv completions are forwarded against it and decrement
        #: the owed count — the entry dies at zero, or at the deadline
        #: for legs whose slots never produce a blob (engine reset,
        #: preemption), so a long-lived prefill worker's outbox stays
        #: bounded by in-flight legs instead of growing per message
        self._kv_outbox: Dict[Any, List[Any]] = {}
        #: flipped by the first held shipped-KV request: from then on
        #: the pump keeps draining the shipment queue even with
        #: nothing pending, so late blobs for already-admitted
        #: requests don't accumulate; workers that never see
        #: disaggregated traffic skip the drain entirely
        self._kv_seen_traffic = False
        #: admission class applied to requests that carry no ``slo``
        #: of their own (the per-job default; per-request override
        #: rides the scatter payload). Validated at boot: a typo'd
        #: job default must fail the deploy, not degrade silently.
        self.default_slo = normalize_slo(default_slo)
        #: visible drop accounting: silent expiry drops look identical to
        #: gather timeouts from the predictor side, so the worker keeps
        #: its own count (and logs) — the first diagnostic to check when
        #: "the predictor only sees timeouts" (clock skew, ADVICE r3).
        #: drain_rejected counts messages error-replied while draining.
        self.stats = StatsMap({"dropped_expired": 0,
                               "drain_rejected": 0,
                               # disaggregated prefill/decode: blobs
                               # shipped out (prefill role), installed
                               # from the wire (decode role), and the
                               # degradations — wait window expired /
                               # blob rejected → local re-prefill
                               "kv_ships_sent": 0,
                               "kv_imports_installed": 0,
                               "kv_wait_timeouts": 0,
                               "kv_import_fallbacks": 0,
                               # data-plane survival: 1 while the hub
                               # is unreachable past the reconnect
                               # window (the serve loop PAUSES — obs
                               # sidecar keeps answering); outages
                               # counts distinct pause episodes
                               "data_plane_down": 0,
                               "hub_outages": 0})
        self._dp_down = False
        #: deterministic fault injection (tests / chaos drills): either
        #: passed programmatically or armed via the RAFIKI_CHAOS env
        #: var; when armed, queue-level faults ride a ChaosHub wrapper
        #: and the kill-after-N-tokens trigger is checked in the decode
        #: loop. None (the default) costs nothing.
        if chaos is None:
            from ..chaos import ChaosConfig, ChaosInjector

            cfg = ChaosConfig.from_env()
            chaos = ChaosInjector(cfg) if cfg is not None else None
        self.chaos = chaos
        self.chaos_killed = False
        if self.chaos is not None:
            from ..chaos import ChaosHub

            self.hub = ChaosHub(hub, self.chaos)
        #: graceful drain: set via POST /drain on the obs sidecar or a
        #: {"control": "drain"} queue message — stop admitting, finish
        #: in-flight streams, publish `draining`, then exit the loop
        self._draining = threading.Event()
        #: skew-compensated expiry clock for the relative ttl_s
        #: deadlines (wall deadline_ts stays as the fallback)
        self._skew = ClockSkewEstimator()
        #: the obs plane: registry scraped at GET /metrics (serve_obs
        #: sidecar), trace ring at GET /debug/requests, and the request-
        #: lifecycle histograms the engine's span hook feeds
        self.metrics = MetricsRegistry()
        self.metrics.register_stats(self.stats)
        # hub reconnect/retry counters from the shared kv client layer
        # (hub_reconnects_total / hub_rpc_retries_total): the worker's
        # /metrics shows how hard the data plane made it work
        from ..native.client import CLIENT_STATS as _kv_client_stats

        self.metrics.register_stats(_kv_client_stats)
        if self.chaos is not None:
            # injected faults are observable, not a mystery: chaos_*
            # gauges ride the worker's /metrics like any counter
            self.metrics.register_stats(self.chaos.counters,
                                        prefix="chaos_")
        self.traces = TraceBuffer(512)
        self._boot_mono = time.monotonic()
        self._h_ttft = self.metrics.histogram(
            "ttft_seconds", "queued -> first generated token (seconds)")
        self._h_queue = self.metrics.histogram(
            "time_in_queue_seconds",
            "queued -> decode-slot admission (seconds)")
        self._h_e2e = self.metrics.histogram(
            "request_seconds",
            "queued -> request fully answered (seconds)")
        self._h_occupancy = self.metrics.histogram(
            "batch_occupancy", "live decode slots per engine step",
            buckets=(0, 1, 2, 4, 8, 16, 32, 64, 128))
        self._h_tps = self.metrics.histogram(
            "decode_tokens_per_s",
            "per-request generated-token throughput",
            buckets=(1, 2, 5, 10, 20, 50, 100, 200, 500, 1000, 2000,
                     5000))
        self._h_step = self.metrics.histogram(
            "decode_step_seconds",
            "one fused engine step() — admission + K decode tokens "
            "(seconds); read next to paged_kernel_mode to see the "
            "kernel-vs-gather difference on a live worker")
        self._h_kv_transfer = self.metrics.histogram(
            "kv_transfer_seconds",
            "one host-tier page transfer (evict d2h or prefetch "
            "staging) on the tier thread (seconds); persistently large"
            " values mean the tier thrashes — grow HBM pages or shrink"
            " host_kv_pages",
            buckets=(0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
                     0.1, 0.25, 0.5, 1.0, 2.5))
        # class-labeled latency histograms: the brownout ladder feeds
        # on the INTERACTIVE p95 alone, and an SLO story without
        # per-class latency evidence is unverifiable. Same metric
        # names, a `slo` label per class (the registry keys on
        # (name, labels)); the published per-class p95 gauges below
        # are what the predictor's controller actually reads.
        self._h_ttft_slo = {
            c: self.metrics.histogram(
                "ttft_seconds",
                "queued -> first generated token (seconds)",
                labels={"slo": c}) for c in SLO_CLASSES}
        self._h_e2e_slo = {
            c: self.metrics.histogram(
                "request_seconds",
                "queued -> request fully answered (seconds)",
                labels={"slo": c}) for c in SLO_CLASSES}
        # bounded per-class (timestamp, sample) windows backing the
        # PUBLISHED p95 gauges: the brownout ladder must see recovery,
        # and a lifetime-cumulative histogram quantile stays polluted
        # by an ended overload for hours (fast samples would need to
        # outnumber slow ones ~19:1 before the p95 moves). Samples
        # also age out by TIME (publish-side prune): when interactive
        # traffic stops entirely, the window must drain to empty —
        # read as cooling — instead of pinning the ladder at the last
        # overload's p95 all night. The labeled histograms above keep
        # the cumulative /metrics view.
        self._slo_ttft_win = {c: collections.deque(maxlen=256)
                              for c in SLO_CLASSES}
        self._slo_e2e_win = {c: collections.deque(maxlen=256)
                             for c in SLO_CLASSES}
        #: appends run on the serve-loop thread, but _publish_stats
        #: also runs on the obs sidecar thread (POST /drain publishes
        #: immediately) — and _window_p95 both prunes and iterates,
        #: so the windows need their own lock like every other
        #: cross-thread read in this file
        self._slo_win_lock = threading.Lock()
        #: engine request id -> (trace_id, queued monotonic, slo).
        #: Touched only by the serve-loop thread (submits, step, span
        #: hook all run there), so no lock
        self._req_obs: Dict[Any, Tuple[str, float, str]] = {}
        self._obs_server: Optional[ObsServer] = None
        self._obs_port = 0
        self._stop = threading.Event()
        self.model = model_class(**knobs)
        params = param_store.load(trial_id)
        if params is None:
            raise KeyError(f"no parameters for trial {trial_id!r}")
        self.model.load_parameters(params)
        # an (unloaded) draft twin sized from its knobs: its params +
        # cache count toward admission via the estimator's eval_shape
        # path, BEFORE any blob loads or engine builds
        draft_for_admission = None
        if draft_trial_id and decode_loop and speculate_k >= 2:
            draft_for_admission = model_class(**(draft_knobs or knobs))
        if host_kv_pages and not (decode_loop and kv_page_size):
            raise ValueError(
                "host_kv_pages requires decode_loop and kv_page_size "
                "> 0 (the host tier spills KV PAGES)")
        #: cross-worker prefix sharing: when a pool peer already
        #: published the shared prefix's KV snapshot, SKIP the local
        #: prefix prefill (build without system_prefix) and import the
        #: blob after boot — prefilled once per pool, not per replica.
        #: Single-adapter deployments only (per-adapter snapshots stay
        #: per-worker); best-effort — a hub hiccup just re-prefills.
        self._peer_prefix_blob: Optional[dict] = None
        self._system_prefix = str(system_prefix or "")
        if self.pool_id and system_prefix and decode_loop \
                and not extra_adapter_trials:
            try:
                raw = self.hub.get_blob(f"prefix:{self.pool_id}:0")
                if raw is not None:
                    self._peer_prefix_blob = unpack_message(raw)
                    system_prefix = ""  # peer's snapshot replaces the
                    #                     local prefix prefill entirely
            except Exception:  # rafiki: noqa[silent-except] — sharing
                pass           # is an optimization, never a boot gate
        if self.role != "unified" and not decode_loop:
            raise ValueError(
                f"worker role {self.role!r} requires decode_loop: the "
                "micro-batch path has no KV to disaggregate")
        self._admission_check(
            max_slots if decode_loop else 0,
            len(extra_adapter_trials or ()) if decode_loop else 0,
            draft_for_admission,
            kv_page_size=kv_page_size if decode_loop else 0,
            kv_pages=kv_pages if decode_loop else 0,
            host_kv_pages=host_kv_pages if decode_loop else 0)
        self.engine = None
        if draft_trial_id and (not decode_loop or speculate_k < 2):
            # fail loudly, like the multi-adapter misconfigurations: an
            # operator who named a draft trial believes speculation is
            # live — silently serving without it hides the mistake
            raise ValueError(
                "draft_trial_id requires decode_loop and "
                f"speculate_k >= 2 (got speculate_k={speculate_k})")
        if draft_trial_id and extra_adapter_trials:
            raise ValueError(
                "draft_trial_id is not supported with multi-adapter "
                "deployment (the stacked engine has no draft path)")
        if decode_loop and extra_adapter_trials:
            if not hasattr(self.model, "make_multi_adapter_engine"):
                # fail LOUDLY: falling back to a single-adapter engine
                # would route every adapter_id to the primary trial —
                # the wrong-tenant answer multi-adapter validation
                # exists to prevent
                raise RuntimeError(
                    f"{model_class.__name__} does not support "
                    "multi-adapter serving (no make_multi_adapter_"
                    "engine); deploy plain replicas instead")
            # multi-adapter deployment: this worker serves the PRIMARY
            # trial as adapter 0 and each extra trial as adapter 1..N —
            # one base model's HBM, one compiled step, requests routed
            # by sampling={"adapter_id": i}. The trials must share
            # every non-adapter leaf (adapters_only training); the
            # stacking validation below fails the boot loudly otherwise
            trees = [self.model._params]
            for tid in extra_adapter_trials:
                dump = param_store.load(tid)
                if dump is None:
                    raise KeyError(
                        f"no parameters for adapter trial {tid!r}")
                peer = model_class(**knobs)
                peer.load_parameters(dump)
                trees.append(peer._params)
            extra = {}
            if kv_page_size:  # only ride when set: user templates that
                # predate paged KV keep working at the defaults
                extra = {"kv_page_size": kv_page_size,
                         "kv_pages": kv_pages}
                if paged_kernel is not None:
                    extra["paged_kernel"] = bool(paged_kernel)
                if host_kv_pages:
                    extra["host_kv_pages"] = int(host_kv_pages)
            try:
                self.engine = self.model.make_multi_adapter_engine(
                    trees, max_slots=max_slots,
                    max_new_tokens=max_new_tokens,
                    steps_per_sync=steps_per_sync,
                    speculate_k=speculate_k, **extra)
            except ValueError as e:
                raise RuntimeError(
                    "multi-adapter deployment requires trials that "
                    "share one base (train them with adapters_only=True"
                    " and identical shape-relevant knobs); deploy as "
                    f"plain replicas instead: {e}") from e
            if system_prefix:
                # per-adapter snapshots: the prefix KV is a function of
                # the adapter that computed it, so every tenant gets
                # its own (same text, N different KV caches)
                for aid in range(len(trees)):
                    self.engine.register_prefix(system_prefix,
                                                adapter_id=aid)
        elif decode_loop:
            if hasattr(self.model, "make_decode_engine"):
                # optional kwargs only ride when set: user templates
                # that predate them keep working at the defaults
                extra = {}
                if speculate_k:
                    extra["speculate_k"] = speculate_k
                if system_prefix:
                    extra["system_prefix"] = system_prefix
                if kv_page_size:
                    # paged-KV serving: cache HBM scales with the page
                    # pool (live tokens), not max_slots x max_len
                    extra["kv_page_size"] = kv_page_size
                    extra["kv_pages"] = kv_pages
                    if paged_kernel is not None:
                        # explicit kernel-vs-gather override; absent =
                        # the ops-level auto rule (kernel on TPU only)
                        extra["paged_kernel"] = bool(paged_kernel)
                    if host_kv_pages:
                        # host-RAM page tier: the admission budget
                        # becomes HBM + host pages (serving/kv_tier.py)
                        extra["host_kv_pages"] = int(host_kv_pages)
                if draft_trial_id and speculate_k:
                    # draft-MODEL speculation: a second (smaller) trial
                    # drafts; its own knobs shape it (same tokenizer
                    # family enforced by the engine's vocab check)
                    d_dump = param_store.load(draft_trial_id)
                    if d_dump is None:
                        raise KeyError("no parameters for draft trial "
                                       f"{draft_trial_id!r}")
                    d_model = model_class(**(draft_knobs or knobs))
                    d_model.load_parameters(d_dump)
                    extra["draft_model"] = d_model
                self.engine = self.model.make_decode_engine(
                    max_slots=max_slots, max_new_tokens=max_new_tokens,
                    steps_per_sync=steps_per_sync, **extra)
            else:
                # the stack enables decode_loop for every LM-task model;
                # a template without an engine still serves fine through
                # the micro-batcher — degrade, don't die
                import logging

                logging.getLogger(__name__).warning(
                    "%s has no make_decode_engine; serving through the "
                    "predict() micro-batcher instead of the continuous-"
                    "batching decode loop", model_class.__name__)
        if self.role != "unified" and not getattr(
                self.engine, "supports_kv_ship", False):
            # fail the DEPLOY, not the serve thread: a role-configured
            # worker whose engine cannot extract/install KV shipments
            # would silently serve unified and defeat the placement
            raise ValueError(
                f"worker role {self.role!r} requires an engine with "
                "KV shipment support (supports_kv_ship); this "
                "deployment's engine has none")
        if self.engine is not None:
            # engine counters surface on /metrics under their BARE
            # names (kv_pages_used, admission_stalls, …) — the hub
            # publish below keeps the engine_ prefix for back-compat
            st = self.engine.stats
            if hasattr(st, "snapshot"):
                self.metrics.register_stats(st)
            else:  # duck-typed user engine with a plain dict
                self.metrics.register_stats(lambda: dict(st))
            if hasattr(self.engine, "span_sink"):
                # request-lifecycle events -> trace spans + histograms
                self.engine.span_sink = self._engine_span
            tier = getattr(getattr(self.engine, "engine", self.engine),
                           "tier", None)
            if tier is not None:
                # host-tier transfers feed the worker's latency
                # histogram (observed on the tier thread — the
                # registry's instruments are locked)
                tier.observe_transfer = self._h_kv_transfer.observe
        self._warmup()
        self._share_prefix_snapshot()

    def _admission_check(self, max_slots: int, n_extra_adapters: int,
                         draft=None, kv_page_size: int = 0,
                         kv_pages: int = 0,
                         host_kv_pages: int = 0) -> None:
        """Refuse a deployment whose serving footprint (params + KV
        cache + stacked adapters + draft params/cache + working set)
        exceeds the device's HBM, BEFORE any engine build/compile —
        the serving twin of the train worker's check. Templates opt in
        by exposing ``estimate_serving_device_bytes``; the limit
        resolution is shared (``worker.admission``). Micro-batch
        deployments (no decode loop) pass ``max_slots=0``: no engine
        means no KV cache to charge. A paged-KV deployment
        (``kv_page_size > 0``) is budgeted at its PAGE POOL, not
        max_slots × max_len — the admission headroom the block-table
        cache exists to create."""
        est = getattr(self.model, "estimate_serving_device_bytes", None)
        if est is None:
            return
        from .admission import resolve_device_limit

        limit = resolve_device_limit()
        if not limit:
            return
        try:
            kwargs = {"max_slots": max_slots,
                      "n_extra_adapters": n_extra_adapters}
            if draft is not None:
                kwargs["draft"] = draft
            if kv_page_size:  # only when set: estimators that predate
                # paged KV keep admitting their deployments
                kwargs["kv_page_size"] = kv_page_size
                kwargs["kv_pages"] = kv_pages
                if host_kv_pages:
                    # host tier: validated by the estimator (mirrors
                    # the engine rule) and reported as host RAM — it
                    # never counts toward the HBM total below
                    kwargs["host_kv_pages"] = host_kv_pages
            budget = est(**kwargs)
            total = int(budget["total"])
        except Exception as e:  # an estimator bug must never block an
            # admissible deployment — but it must be VISIBLE: silently
            # skipping here disables serving admission control
            # fleet-wide until workers start OOMing (ADVICE.md r5)
            import logging

            logging.getLogger(__name__).warning(
                "serving admission check skipped: "
                "estimate_serving_device_bytes raised %r", e,
                exc_info=True)
            return
        if total > limit:
            raise ValueError(
                "serving admission control: estimated "
                f"{total / 2**30:.2f}GiB footprint exceeds the "
                f"{limit / 2**30:.2f}GiB device limit (breakdown: "
                f"{ {k: round(v / 2**30, 3) for k, v in budget.items()} }"
                " GiB); lower max_slots/max_len or enable "
                "quantize_int8/kv_cache_int8")

    def _warmup(self) -> None:
        """Pre-compile the serving path at boot so the FIRST request
        doesn't pay XLA compilation (seconds to minutes on TPU)."""
        import logging

        try:
            if self.engine is not None:
                # one dummy token through the fused decode step
                self.engine.submit("__warmup__", "warmup", max_new=1)
                while self.engine.busy:
                    self.engine.step()
                self.engine.poll()  # drop the dummy completion
                # don't count the dummy in served-traffic metrics;
                # engines with capacity gauges (paged-KV pool size)
                # scrub counters only — duck-typed user engines without
                # reset_stats get the plain zeroing
                if hasattr(self.engine, "reset_stats"):
                    self.engine.reset_stats()
                else:
                    st = self.engine.stats
                    st.update({k: 0 for k in list(st)})
            else:
                self.model.warmup()
        except Exception:  # noqa: BLE001 — slower first request, not a
            logging.getLogger(__name__).warning(  # dead worker
                "serving warmup failed; first request pays the compile",
                exc_info=True)
            if self.engine is not None:
                # a failed step may have consumed the donated cache and
                # left the dummy occupying a slot: rebuild device state
                # so the loop doesn't admit real requests into a broken
                # engine
                self.engine.reset()

    def _share_prefix_snapshot(self) -> None:
        """Cross-worker prefix sharing (scale-out pools): a shared
        system prefix prefilled by ONE replica serves every replica of
        the job. The replica that found a peer's published blob at
        boot skipped its own prefix prefill entirely and installs the
        blob here; the first replica (no blob yet) publishes the
        snapshot it just computed. Both snapshots are bit-identical
        (same module/params/tokenizer) so which replica wins the
        publish race is immaterial; best-effort by design — any
        failure leaves a locally-computed snapshot serving."""
        if not self.pool_id or self.engine is None \
                or not self._system_prefix:
            return
        exp = getattr(self.engine, "export_prefix", None)
        imp = getattr(self.engine, "import_prefix", None)
        if exp is None or imp is None:
            return
        import logging

        key = f"prefix:{self.pool_id}:0"
        if self._peer_prefix_blob is not None:
            blob, self._peer_prefix_blob = self._peer_prefix_blob, None
            try:
                imp(blob)
                self.stats.inc("kv_imports_installed")
            except Exception:  # noqa: BLE001 — a bad/stale peer blob
                # must not leave the worker prefix-less: fall back to
                # computing the snapshot locally (what an unshared
                # boot would have done)
                logging.getLogger(__name__).warning(
                    "peer prefix snapshot rejected; registering the "
                    "prefix locally", exc_info=True)
                self.engine.register_prefix(self._system_prefix)
            return
        try:
            blob = exp()
            if blob is not None and self.hub.get_blob(key) is None:
                self.hub.put_blob(key, pack_message(blob))
        except Exception:  # noqa: BLE001 — publishing is a peer
            # optimization; this worker's own snapshot already serves
            logging.getLogger(__name__).warning(
                "prefix snapshot publish failed", exc_info=True)

    def stop(self) -> None:
        self._stop.set()
        if self._obs_server is not None:
            self._obs_server.stop()
            self._obs_server = None
        close = getattr(self.engine, "close", None)
        if close is not None:
            # tiered engines own a transfer thread + pinned host pool;
            # micro-batch engines have no close and need none
            close()

    def drain(self) -> None:
        """Begin a graceful drain: stop admitting new requests (they
        get an immediate structured ``draining`` rejection the
        predictor fails over on), finish every in-flight request —
        including streams — then exit the serve loop cleanly (the
        process exits 0: a drained worker is a completed one, not a
        crash to respawn). Idempotent; safe from any thread (the obs
        sidecar's /drain handler and the queue control path both land
        here)."""
        if self._draining.is_set():
            return
        import logging

        logging.getLogger(__name__).info(
            "%s draining: finishing in-flight work, rejecting new",
            self.worker_id)
        self._draining.set()
        # publish immediately so the predictor's breaker board learns
        # of the drain from stats, not only from rejection replies
        self._publish_stats()

    @property
    def draining(self) -> bool:
        return self._draining.is_set()

    def serve_obs(self, host: str = "127.0.0.1",
                  port: int = 0) -> Tuple[str, int]:
        """Start the observability sidecar (``GET /metrics`` Prometheus
        text, ``GET /debug/requests?n=K`` trace records, ``POST
        /drain``) on a daemon thread; returns its (host, port). The
        serve loop never touches it — scrapes read the same locked
        registry the loop writes, and drain flips an Event the loop
        polls."""
        self._obs_server = ObsServer(self.metrics, self.traces,
                                     host=host, port=port)
        # the drain control endpoint (rolling restarts): mounted on the
        # sidecar because the worker itself is a queue consumer with no
        # HTTP surface of its own
        self._obs_server.http.route(
            "POST", "/drain",
            lambda _m, _b, _h: (self.drain() or
                                (200, {"ok": True, "draining": True})))
        host, port = self._obs_server.start()
        # GIL-atomic int store read by the serve loop's stats
        # publisher; a stale 0 only delays the obs_port advertisement
        # by one publication
        self._obs_port = port  # rafiki: noqa[shared-state-race]
        return host, port

    #: loop iterations between stats publications to the hub
    STATS_EVERY = 50
    #: how long published counters stay trustworthy: the loop publishes
    #: at least every STATS_EVERY x poll_timeout seconds (~25s at the
    #: defaults), so an uptime_s that has not advanced for this long
    #: means a dead/hung/partitioned worker, not a slow one
    STALE_AFTER_S = 60.0

    def _publish_stats(self) -> None:
        """Push this worker's counters to the hub so the predictor's
        /health can surface them (silent expiry drops are otherwise
        indistinguishable from gather timeouts on the predictor side).

        Snapshots are taken through the obs StatsMaps' own locks — the
        only race-free read while the engine thread mutates (iterating
        the live dict here used to be able to blow up with "dictionary
        changed size during iteration" under load)."""
        stats = self.stats.snapshot()
        stats["role"] = self.role  # disaggregated placement: the
        # router excludes prefill-role workers from serving selection
        # and targets them for the prefill leg
        stats["draining"] = self._draining.is_set()  # breaker-board
        # scatter exclusion during rolling restarts; the respawned
        # worker's fresh False is what re-admits the id
        stats["published_at"] = time.time()  # for humans; staleness
        # rides the MONOTONIC pair below — a wall-clock step (NTP, VM
        # migration) must neither grey out a healthy worker nor let a
        # dead one's counters pose as current
        stats["uptime_s"] = time.monotonic() - self._boot_mono
        stats["stale_after_s"] = self.STALE_AFTER_S
        if self._obs_port:
            stats["obs_port"] = self._obs_port  # where /metrics lives
        if self.engine is not None:
            snap = (self.engine.stats_snapshot()
                    if hasattr(self.engine, "stats_snapshot")
                    else dict(self.engine.stats))
            stats.update({f"engine_{k}": v for k, v in snap.items()})
            # bucket-derived latency summaries (dashboard TTFT/e2e)
            stats["ttft_p50_s"] = self._h_ttft.quantile(0.50)
            stats["ttft_p95_s"] = self._h_ttft.quantile(0.95)
            stats["e2e_p50_s"] = self._h_e2e.quantile(0.50)
            stats["e2e_p95_s"] = self._h_e2e.quantile(0.95)
            # queue-wait p95: the router's cleanest "this worker is
            # behind" signal (TTFT includes prefill length, queue wait
            # is pure backlog)
            stats["queue_p95_s"] = self._h_queue.quantile(0.95)
            # per-class latency gauges: the predictor's brownout
            # ladder steps on slo_interactive_ttft_p95_s; the rest
            # make the SLO tradeoff visible per class on /health.
            # WINDOWED (recent 256 samples), not the cumulative
            # histogram quantile — the ladder must de-escalate when
            # the overload actually ends, not hours later
            with self._slo_win_lock:
                for c in SLO_CLASSES:
                    stats[f"slo_{c}_ttft_p95_s"] = _window_p95(
                        self._slo_ttft_win[c])
                    stats[f"slo_{c}_e2e_p95_s"] = _window_p95(
                        self._slo_e2e_win[c])
        try:
            self.hub.put_worker_stats(self.worker_id, stats)
        except Exception:  # rafiki: noqa[silent-except] —
            pass           # observability must never kill the loop

    def _engine_span(self, event: str, rid: Any, attrs: dict) -> None:
        """Decode-engine lifecycle hook: admitted / prefill /
        first_token / decode_mark / done events become trace spans, and
        the queued→X durations feed the latency histograms. Runs on the
        serve-loop thread (the engine's step caller), so the rid→trace
        map needs no lock; unknown rids (the warmup dummy) are
        ignored."""
        entry = self._req_obs.get(rid)
        if entry is None:
            return
        tid, t_queued, slo = entry
        now = time.monotonic()
        if event == "admitted":
            if not attrs.get("resumed"):
                # a preempt-resume RE-admission is not queue wait: the
                # gap since submit includes the victim's own
                # pre-preemption generation time, and queue_p95_s is
                # the router's least-loaded input — a worker doing
                # preemptions (correctly protecting interactive) must
                # not read as backlogged for it
                self._h_queue.observe(now - t_queued)
            self.traces.add_span(tid, "admitted", worker=self.worker_id,
                                 **attrs)
        elif event == "first_token":
            self._h_ttft.observe(now - t_queued)
            h = self._h_ttft_slo.get(slo)
            if h is not None:
                h.observe(now - t_queued)
                with self._slo_win_lock:
                    self._slo_ttft_win[slo].append((now,
                                                    now - t_queued))
            self.traces.add_span(tid, "first_token")
        elif event == "done":
            dt = now - t_queued
            self._h_e2e.observe(dt)
            h = self._h_e2e_slo.get(slo)
            if h is not None:
                h.observe(dt)
                with self._slo_win_lock:
                    self._slo_e2e_win[slo].append((now, dt))
            tokens = attrs.get("tokens") or 0
            if tokens and dt > 0:
                self._h_tps.observe(tokens / dt)
            self.traces.add_span(tid, "done", **attrs)
            self._req_obs.pop(rid, None)
        else:
            # incl. `preempted`: the span joins the timeline but the
            # rid entry stays — the victim resumes under the same id
            self.traces.add_span(tid, event, **attrs)

    def _count_dropped(self, n: int) -> None:
        if n <= 0:
            return
        import logging

        total = self.stats.inc("dropped_expired", n)
        # log the first drop and then every 100th: one line is enough to
        # diagnose skew, a line per query would flood under overload
        if total == n or total % 100 < n:
            logging.getLogger(__name__).warning(
                "%s dropped %d expired quer%s (%d total) — if the "
                "predictor only reports timeouts, check clock skew "
                "between predictor and worker hosts",
                self.worker_id, n, "y" if n == 1 else "ies", total)

    def _reject_expired(self, m: dict) -> None:
        """Answer a past-deadline query with a structured ``expired``
        rejection instead of a silent drop: the predictor records a
        skipped vote (unary gather) or triggers stream failover
        IMMEDIATELY, instead of burning the remaining gather budget
        waiting on silence. The drop counter and its diagnostic log
        line stay — `dropped_expired` growing alongside `expired`
        replies is still the clock-skew tell (ADVICE r3)."""
        self._count_dropped(1)
        if "id" not in m:
            return
        tid = str(m.get("trace_id") or "")
        if tid:  # the drop is visible in the trace, not just a
            # counter — joins the predictor's record
            self.traces.start(tid, request_id=str(m.get("id") or ""),
                              span="expired", worker=self.worker_id)
        self.hub.push_prediction(m["id"], pack_message(
            {"id": m["id"], "worker_id": self.worker_id,
             "predictions": [], "expired": True,
             "error": "query expired in transit "
                      "(deadline exceeded before pop)"}))

    def _handle_control(self, m: dict) -> None:
        """Control messages ride the ordinary query queue (``{"control":
        "drain"}``): the queue is the one channel every deployment
        shape shares, HTTP sidecar or not."""
        cmd = str(m.get("control") or "")
        if cmd == "drain":
            self.drain()
        else:
            import logging

            logging.getLogger(__name__).warning(
                "%s ignoring unknown control message %r",
                self.worker_id, cmd)

    def _reject_draining(self, m: dict) -> None:
        """Answer a message popped while draining with an immediate
        structured rejection: the predictor fails the request over to a
        healthy replica instead of timing out on a queue nobody will
        serve."""
        if "id" not in m:
            return
        self.stats.inc("drain_rejected")
        tid = str(m.get("trace_id") or "")
        if tid:
            self.traces.start(tid, request_id=str(m.get("id") or ""),
                              span="drain_rejected",
                              worker=self.worker_id)
        self.hub.push_prediction(m["id"], pack_message(
            {"id": m["id"], "worker_id": self.worker_id,
             "predictions": [], "error": "worker draining",
             "draining": True}))

    def _drain_reject_queued(self) -> None:
        """Flush the query queue with drain rejections (non-blocking)."""
        raw = self.hub.pop_query(self.worker_id, 0.0)
        while raw is not None:
            m = unpack_message(raw)
            if not m.get("control"):
                self._reject_draining(m)
            raw = self.hub.pop_query(self.worker_id, 0.0)

    # ---- data-plane outage handling ----
    #: ceiling on the pause between hub retries while the data plane
    #: is down — long enough not to spin, short enough that the worker
    #: notices the respawned kvd within a beat of its WAL replay
    HUB_OUTAGE_PAUSE_S = 0.5

    def _hub_outage_pause(self, err: Exception,
                          poll_timeout: float) -> None:
        """The kvd is unreachable past the client's reconnect window:
        PAUSE the serve loop instead of crashing into a respawn storm.
        The obs sidecar keeps answering /metrics and /health the whole
        time (it never touches the hub), `data_plane_down` flips to 1,
        and in-flight engine state stays seated — when the supervisor's
        respawn-with-replay brings the kvd back, the next loop tick
        picks up exactly where it paused."""
        import logging

        if not self._dp_down:
            self._dp_down = True
            self.stats.set("data_plane_down", 1)
            self.stats.inc("hub_outages")
            logging.getLogger(__name__).warning(
                "%s: data plane unreachable (%s) — serve loop paused "
                "(health stays up; retrying every %.1fs)",
                self.worker_id, err,
                min(self.HUB_OUTAGE_PAUSE_S, max(poll_timeout, 0.05)))
        self._stop.wait(min(self.HUB_OUTAGE_PAUSE_S,
                            max(poll_timeout, 0.05)))

    def _hub_ok(self) -> None:
        """A hub op reached the kvd again: clear the outage flag."""
        if self._dp_down:
            import logging

            self._dp_down = False
            self.stats.set("data_plane_down", 0)
            logging.getLogger(__name__).warning(
                "%s: data plane reachable again — serve loop resumed",
                self.worker_id)
            self._publish_stats()  # fresh liveness beats the stale
            #                        pre-outage publish immediately

    # ---- the loop ----
    def run(self, poll_timeout: float = 0.5,
            max_iterations: Optional[int] = None) -> None:
        if self.role == "prefill":
            # prefill is throughput work; decode is latency work. On a
            # co-located host the prompt chew must never preempt a
            # decode loop's step, so the prefill serve thread runs
            # niced (Linux niceness is per-thread; pid 0 = this
            # thread). Best-effort — a host that refuses leaves both
            # threads at default priority.
            try:
                os.setpriority(os.PRIO_PROCESS, 0, 10)
            except (AttributeError, OSError):
                pass
        if self.engine is not None:
            return self._run_decode_loop(poll_timeout, max_iterations)
        n = 0
        while not self._stop.is_set():
            if max_iterations is not None and n >= max_iterations:
                break
            n += 1
            if n % self.STATS_EVERY == 1:  # incl. first iteration:
                self._publish_stats()      # fresh boots appear at once
            try:
                if self._draining.is_set():
                    # micro-batch serving has no in-flight state
                    # between iterations: reject what is queued, leave
                    self._drain_reject_queued()
                    break
                first = self.hub.pop_query(self.worker_id, poll_timeout)
                self._hub_ok()
                if first is None:
                    continue
                messages = [unpack_message(first)]
                while len(messages) < self.max_batch_msgs:
                    more = self.hub.pop_query(self.worker_id, 0.0)
                    if more is None:
                        break
                    messages.append(unpack_message(more))
                serve = []
                for m in messages:
                    if m.get("control"):
                        self._handle_control(m)
                    else:
                        serve.append(m)
                live = []
                for m in serve:
                    if _expired(m, skew_est=self._skew):
                        self._reject_expired(m)
                    else:
                        live.append(m)
                if live:
                    # messages popped alongside a drain control
                    # preceded the drain: they are in-flight and served
                    self._serve_batch(live)
            except ConnectionError as e:
                # data plane unreachable past the reconnect window:
                # pause and retry — health stays up on the obs sidecar
                self._hub_outage_pause(e, poll_timeout)
        self._publish_stats()  # final counters visible after stop

    def _run_decode_loop(self, poll_timeout: float,
                         max_iterations: Optional[int]) -> None:
        """Continuous batching: admit queued messages into engine slots
        between steps; reply per message once all its queries finish.

        One loop iteration = (drain the queue, admit, one engine step,
        harvest). While the engine is busy the queue pop is non-blocking
        so decoding never stalls on an empty queue.

        Data-plane outages (a hub op exhausting its reconnect window)
        PAUSE the loop here — in-flight engine state, the inflight
        table, and streaming ids all survive the pause, so when the
        supervisor's respawn-with-replay brings the kvd back the loop
        resumes decoding the same streams; a delta pushed into the
        dead window is healed by the final predictions message (the
        client's replace/tail contract)."""
        # message id -> [n_pending, {query_index: text}]
        inflight: dict = {}
        streaming: set = set()  # message ids that asked for token deltas
        state = {"n": 0}
        while not self._stop.is_set():
            try:
                self._decode_serve(inflight, streaming, state,
                                   poll_timeout, max_iterations)
                break  # served to completion (stop/drain/iterations)
            except ConnectionError as e:
                self._hub_outage_pause(e, poll_timeout)
        if self.chaos_killed:
            return  # injected sudden death: no final publish either
        self._publish_stats()  # final counters visible after stop

    def _decode_serve(self, inflight: dict, streaming: set,
                      state: dict, poll_timeout: float,
                      max_iterations: Optional[int]) -> None:
        while not self._stop.is_set():
            n = state["n"]
            if max_iterations is not None and n >= max_iterations:
                break
            n = state["n"] = n + 1
            if n % self.STATS_EVERY == 1:  # incl. first iteration
                self._publish_stats()
            # held shipped-KV requests count as busy: the loop must
            # keep pumping the shipment queue instead of parking on an
            # empty query queue while a blob is in flight
            busy = self.engine.busy or bool(self._pending_kv)
            raw = self.hub.pop_query(self.worker_id,
                                     0.0 if busy else poll_timeout)
            self._hub_ok()
            while raw is not None:
                m = unpack_message(raw)
                if m.get("control"):
                    self._handle_control(m)
                    raw = self.hub.pop_query(self.worker_id, 0.0)
                    continue
                if self._draining.is_set():
                    # draining: in-flight requests keep decoding below,
                    # new arrivals get an immediate structured
                    # rejection the predictor fails over on
                    self._reject_draining(m)
                    raw = self.hub.pop_query(self.worker_id, 0.0)
                    continue
                if _expired(m, skew_est=self._skew):
                    self._reject_expired(m)
                    raw = self.hub.pop_query(self.worker_id, 0.0)
                    continue
                if m.get("prefill_for"):
                    # the PREFILL leg of a disaggregated stream: chew
                    # the prompt, ship the KV pages to the decode
                    # worker named in the payload. Never replied to —
                    # the decode leg's local re-prefill covers every
                    # failure mode here
                    self._handle_prefill_leg(m)
                elif m.get("kv_from") and self._can_import_kv():
                    # the DECODE leg: a prefill worker is computing
                    # this prompt's KV — hold admission for up to
                    # kv_wait_s so the shipment can skip our prefill
                    mid = m["id"]
                    self._kv_seen_traffic = True
                    self._pending_kv[mid] = [
                        m, time.monotonic() + self.kv_wait_s, {},
                        time.monotonic()]
                else:
                    if m.get("kv_from"):
                        # can't hold for the shipment (kv_wait_s=0 or
                        # no shipment-capable engine) but a prefill
                        # worker WILL push blobs for this request: the
                        # pump must keep draining the shipment queue
                        # (dropping unmatched blobs) or the multi-MB
                        # pushes accumulate unboundedly
                        self._kv_seen_traffic = True
                    self._admit_decode_message(m, inflight, streaming)
                raw = self.hub.pop_query(self.worker_id, 0.0)
            self._pump_kv_shipments(inflight, streaming)
            stepped = self.engine.busy
            if stepped:
                try:
                    t_step = time.monotonic()
                    n_live = self.engine.step()
                    self._h_step.observe(time.monotonic() - t_step)
                    self._h_occupancy.observe(n_live)
                except Exception:
                    err = traceback.format_exc()
                    for mid in list(inflight):
                        self.hub.push_prediction(mid, pack_message(
                            {"id": mid, "worker_id": self.worker_id,
                             "predictions": [], "error": err}))
                        del inflight[mid]
                    streaming.clear()
                    # every in-flight request's timeline ends HERE, not
                    # in silence: the reset below preempts all occupants
                    for _rid, (tid, _t, _slo) in list(
                            self._req_obs.items()):
                        self.traces.add_span(tid, "preempted",
                                             error="engine step failed")
                    self._req_obs.clear()
                    # a failed step may have consumed the donated cache:
                    # drop every occupant and rebuild device state, or
                    # the loop hot-spins on a permanently broken engine
                    self.engine.reset()
                    continue
                if self.chaos is not None and self.chaos.should_kill(
                        int(self.engine.stats.get("tokens_generated",
                                                  0) or 0)):
                    # injected sudden death: exit WITHOUT replying,
                    # streaming, or publishing — exactly what a killed
                    # process looks like to the rest of the stack (the
                    # fused step that crossed the threshold never gets
                    # its tokens out)
                    import logging

                    logging.getLogger(__name__).warning(
                        "%s chaos-killed after %s generated tokens",
                        self.worker_id,
                        self.chaos.cfg.kill_after_tokens)
                    self.chaos_killed = True
                    return
                if streaming and hasattr(self.engine, "poll_partial"):
                    # per-message delta events between steps: the reply
                    # queue carries them ahead of the final predictions
                    # message (pushes are FIFO per query id)
                    deltas: dict = {}
                    for (mid, qi), delta in self.engine.poll_partial():
                        if mid in streaming:
                            deltas.setdefault(mid, {})[str(qi)] = delta
                    for mid, d in deltas.items():
                        self.hub.push_prediction(mid, pack_message(
                            {"id": mid, "worker_id": self.worker_id,
                             "delta": d}))
            # harvest runs even when the engine is idle: a resume whose
            # forced prefix covered the whole token budget completes
            # without ever occupying a slot (TextDecodeEngine's
            # instant-done path)
            for (mid, qi), text in self.engine.poll():
                entry = inflight.get(mid)
                if entry is None:
                    continue
                entry[1][qi] = text
                if len(entry[1]) >= entry[0]:
                    preds = [entry[1].get(i) for i in range(entry[0])]
                    self.hub.push_prediction(mid, pack_message(
                        {"id": mid, "worker_id": self.worker_id,
                         "predictions": preds}))
                    for i in range(entry[0]):  # instant-done requests
                        # emit no engine `done` span to clear these
                        self._req_obs.pop((mid, i), None)
                    del inflight[mid]
                    streaming.discard(mid)
            self._ship_finished_prefill()
            if self._draining.is_set() and not inflight \
                    and not self._pending_kv and not self.engine.busy:
                break  # drain complete: every in-flight stream answered

    # ---- disaggregated prefill/decode (see serving/kv_transfer.py) --
    def _can_import_kv(self) -> bool:
        """May this worker hold a request for a KV shipment? Any
        shipment-capable engine qualifies (a unified worker benefits
        the same way when the router chose to disaggregate); a
        zero wait window disables holding entirely."""
        return (self.kv_wait_s > 0
                and getattr(self.engine, "supports_kv_ship", False))

    def _handle_prefill_leg(self, m: dict) -> None:
        """Run a disaggregated request's PREFILL leg: submit each query
        prefill-only and remember where the finished KV blobs ship
        (:meth:`_ship_finished_prefill`). Fire-and-forget by contract —
        on ANY local failure the decode worker's wait window expires
        and it re-prefills locally (token-exact), so this path only
        logs, never replies."""
        import logging

        ship_to = str(m.get("prefill_for") or "")
        sub = getattr(self.engine, "submit_prefill", None)
        if not ship_to or sub is None or self._draining.is_set() \
                or _expired(m, skew_est=self._skew):
            return
        qs = m.get("queries")
        qs = list(qs) if not isinstance(qs, (list, tuple)) else qs
        samp = _safe_sampling(m.get("sampling"))
        tid = str(m.get("trace_id") or "") or mint_trace_id()
        try:
            slo = normalize_slo(m.get("slo"), default=self.default_slo)
        except ValueError:
            slo = self.default_slo
        kwargs = {"slo": slo}
        if samp.get("adapter_id"):
            # the KV is a function of the adapter that computes it —
            # the decode side validates the blob against the request's
            kwargs["adapter_id"] = samp["adapter_id"]
        self.traces.start(tid, request_id=str(m.get("id") or ""),
                          span="prefill_leg", worker=self.worker_id,
                          ship_to=ship_to, n_queries=len(qs))
        try:
            for qi, text in enumerate(qs):
                sub((m["id"], qi), str(text), **kwargs)
        except ValueError as e:
            logging.getLogger(__name__).warning(
                "%s prefill leg rejected (%s); decode worker will "
                "re-prefill locally", self.worker_id, e)
            return
        self._kv_outbox[m["id"]] = [
            ship_to, tid, len(qs),
            time.monotonic() + _KV_OUTBOX_TTL_S]

    def _ship_finished_prefill(self) -> None:
        """Forward completed prefill-only KV blobs to their decode
        workers. Costs one no-op call on workers with no prefill
        traffic (the engine's done list is empty)."""
        poll = getattr(self.engine, "poll_kv", None)
        if poll is None:
            return
        for (mid, qi), blob in poll():
            entry = self._kv_outbox.get(mid)
            if entry is None:
                continue
            ship_to, tid = entry[0], entry[1]
            entry[2] -= 1  # shipped OR failed, this query is settled
            if entry[2] <= 0:
                del self._kv_outbox[mid]
            try:
                self.hub.push_kv(ship_to, pack_message(
                    {"id": mid, "qi": int(qi), "blob": blob,
                     "from": self.worker_id}))
                self.stats.inc("kv_ships_sent")
                self.traces.add_span(tid, "kv_shipped", qi=int(qi),
                                     nbytes=int(blob.get("nbytes", 0)
                                                or 0))
            except Exception:  # noqa: BLE001 — a failed shipment is
                # the decode side's local re-prefill, not our crash
                import logging

                logging.getLogger(__name__).warning(
                    "%s KV shipment to %s failed", self.worker_id,
                    ship_to, exc_info=True)
        if self._kv_outbox:
            # legs whose slots will never produce a blob (engine
            # reset, preemption of a prefill-only slot) must not
            # accumulate forever; the decode side's wait window
            # expired into a local re-prefill long ago
            now = time.monotonic()
            for mid in [k for k, e in self._kv_outbox.items()
                        if now > e[3]]:
                del self._kv_outbox[mid]

    def _kv_stage_budget_ok(self) -> bool:
        """Eagerly device-stage an arriving KV blob only when it will
        install soon. With the engine's admission queue backed up, a
        staged blob sits device-RESIDENT for its whole wait — a burst
        of disaggregated arrivals on a saturated decode worker would
        pin queue-depth × blob-size HBM the unified path never pays.
        Unstaged blobs install from their host bytes at seat time:
        exactly as correct, just without the upload/step overlap."""
        if len(self._pending_kv) > 4:
            return False
        st = self.engine.stats
        return not any(st.get(f"queued_{c}", 0)
                       for c in ("interactive", "batch", "background"))

    def _pump_kv_shipments(self, inflight: dict, streaming: set) -> None:
        """Decode-leg intake: drain arrived KV shipments into held
        requests, admit every request whose blobs are complete, and
        expire wait windows into local re-prefills. Runs once per loop
        iteration, non-blocking; free when nothing is pending."""
        if not self._pending_kv and not self._kv_seen_traffic:
            return
        now = time.monotonic()
        raw = self.hub.pop_kv(self.worker_id, 0.0)
        while raw is not None:
            try:
                ship = unpack_message(raw)
                mid, qi = ship["id"], int(ship["qi"])
                blob = ship["blob"]
            except Exception:  # noqa: BLE001 — a torn shipment is a
                # degradation (local re-prefill), never a serve-thread
                # crash
                import logging

                logging.getLogger(__name__).warning(
                    "%s discarding undecodable KV shipment",
                    self.worker_id, exc_info=True)
                blob = None
                mid = qi = None
            if mid is not None and mid in self._pending_kv \
                    and blob is not None:
                stage = getattr(self.engine, "stage_kv_blob", None)
                if stage is not None and self._kv_stage_budget_ok():
                    try:
                        # device staging starts NOW, overlapping the
                        # in-flight step: admission installs a blob
                        # whose h2d copies already ran
                        blob = stage(blob)
                    except Exception:  # rafiki: noqa[silent-except] —
                        pass           # staging is an optimization
                self._pending_kv[mid][2][qi] = blob
            raw = self.hub.pop_kv(self.worker_id, 0.0)
        for mid in list(self._pending_kv):
            m, deadline, blobs, t_queued = self._pending_kv[mid]
            qs = m.get("queries")
            n = len(qs) if isinstance(qs, (list, tuple)) else 1
            if len(blobs) >= n:
                del self._pending_kv[mid]
                self._admit_decode_message(m, inflight, streaming,
                                           kv_blobs=blobs,
                                           t_queued=t_queued)
            elif now >= deadline or self._draining.is_set():
                # shipment late/lost (or we are draining and must not
                # wait): degrade to a local re-prefill — token-exact,
                # the stream just pays the prefill it hoped to skip
                del self._pending_kv[mid]
                self.stats.inc("kv_wait_timeouts")
                self._admit_decode_message(m, inflight, streaming,
                                           t_queued=t_queued)
        if self._pending_kv and not self.engine.busy:
            # nothing to decode while the blob is in flight: yield the
            # CPU briefly instead of hot-spinning the loop, but stay
            # far under shipment latency so installs are prompt
            time.sleep(0.002)

    def _admit_decode_message(self, m: dict, inflight: dict,
                              streaming: set,
                              kv_blobs: Optional[Dict[int, Any]] = None,
                              t_queued: Optional[float] = None) -> None:
        """Admit one popped message into the engine (the decode loop's
        submission path, shared by immediate admission and the
        deferred shipped-KV path). ``kv_blobs``: per-query-index KV
        shipments to install instead of prefilling; a blob the engine
        rejects degrades that query to a local re-prefill."""
        qs = m["queries"]
        qs = list(qs) if not isinstance(qs, (list, tuple)) else qs
        if not qs:  # answer empty messages immediately, like
            # _serve_batch does — nothing will ever poll() for them
            self.hub.push_prediction(m["id"], pack_message(
                {"id": m["id"], "worker_id": self.worker_id,
                 "predictions": []}))
            return
        tid = str(m.get("trace_id") or "") or mint_trace_id()
        if t_queued is None:
            t_queued = time.monotonic()
        self.traces.start(tid, request_id=str(m["id"]),
                          span="queued",
                          worker=self.worker_id,
                          n_queries=len(qs))
        samp = _safe_sampling(m.get("sampling"))
        # admission class: per-request override riding the
        # payload, else the job default. Defensive like
        # _safe_sampling: the predictor validates, but a
        # malformed value must degrade to the default,
        # never raise inside the serve loop
        try:
            slo = normalize_slo(m.get("slo"),
                                default=self.default_slo)
        except ValueError:
            slo = self.default_slo
        if "max_new" in samp:
            # per-request generation length, clamped by the
            # worker's configured cap: a client must not be
            # able to occupy a slot for longer than the
            # operator budgeted. getattr: duck-typed user
            # engines without a cap must not let a client
            # field kill the serve thread
            samp["max_new"] = min(
                samp["max_new"],
                getattr(self.engine, "max_new",
                        samp["max_new"]))
        fp = m.get("forced_prefix")
        fp = fp if isinstance(fp, dict) else {}
        if fp:
            self.traces.add_span(
                tid, "resumed",
                prefix_chars=sum(len(str(v))
                                 for v in fp.values()))
        try:
            if fp and not getattr(self.engine,
                                  "supports_resume",
                                  False):
                # checked BEFORE any submit (a per-query
                # check would leak the message's earlier
                # queries into the engine when a later one
                # rejects) — and structured, never a
                # TypeError that kills the thread
                raise ValueError(
                    "engine does not support stream "
                    "resume (forced_prefix)")
            for qi, text in enumerate(qs):
                kwargs = dict(samp)
                prefix = str(fp.get(str(qi), "") or "")
                if prefix:
                    kwargs["forced_prefix"] = prefix
                if getattr(self.engine, "supports_slo",
                           False):
                    # capability-gated like forced_prefix:
                    # a duck-typed user engine without the
                    # kwarg serves classless FIFO instead
                    # of dying on a TypeError
                    kwargs["slo"] = slo
                # _engine_span mutates this map too, but it is the
                # engine's span_sink callback and runs on this same
                # serve-loop thread — the model can't resolve callback
                # registration, so it sees a second context
                self._req_obs[(m["id"], qi)] = (  # rafiki: noqa[shared-state-race]
                    tid, t_queued, slo)
                blob = None if kv_blobs is None else kv_blobs.get(qi)
                if blob is not None and not prefix:
                    try:
                        self.engine.submit((m["id"], qi), str(text),
                                           kv_blob=blob, **kwargs)
                        self.stats.inc("kv_imports_installed")
                        self.traces.add_span(tid, "kv_installed",
                                             qi=qi)
                        continue
                    except ValueError:
                        # mismatched/corrupt shipment: degrade THIS
                        # query to a local re-prefill; a genuine
                        # submit error re-raises below and rejects
                        # the message as before
                        self.stats.inc("kv_import_fallbacks")
                self.engine.submit((m["id"], qi), str(text),
                                   **kwargs)
        except ValueError as e:
            # e.g. adapter_id out of range on a multi-
            # adapter engine: reject the whole message —
            # serving a different fine-tune than requested
            # would be a correct-looking wrong answer
            for qi in range(len(qs)):
                self._req_obs.pop((m["id"], qi), None)
            self.traces.add_span(tid, "rejected",
                                 error=str(e))
            self.hub.push_prediction(m["id"], pack_message(
                {"id": m["id"],
                 "worker_id": self.worker_id,
                 "predictions": [], "error": str(e)}))
        else:
            inflight[m["id"]] = [len(qs), {}]
            if m.get("stream"):
                streaming.add(m["id"])

    def _serve_batch(self, messages: List[dict]) -> None:
        # flatten all messages' queries into one forward pass
        t0 = time.monotonic()
        counts = []
        flat: List[Any] = []
        for m in messages:
            qs = m["queries"]
            qs = list(qs) if not isinstance(qs, (list, tuple)) else qs
            counts.append(len(qs))
            flat.extend(qs)
            tid = str(m.get("trace_id") or "")
            if tid:  # join the predictor's trace (micro-batch path has
                # no slot lifecycle — one queued + one served span)
                self.traces.start(tid, request_id=str(m.get("id") or ""),
                                  span="queued", worker=self.worker_id,
                                  n_queries=len(qs))
        try:
            preds = self.model.predict(flat)
            err = ""
        except Exception:
            preds = []
            err = traceback.format_exc()
        # split results back per message and reply on per-query-id queues
        ofs = 0
        dt = time.monotonic() - t0
        for m, c in zip(messages, counts):
            chunk = preds[ofs:ofs + c] if not err else []
            ofs += c
            reply = {"id": m["id"], "worker_id": self.worker_id,
                     "predictions": _to_plain(chunk)}
            if err:
                reply["error"] = err
            self.hub.push_prediction(m["id"], pack_message(reply))
            self._h_e2e.observe(dt)
            tid = str(m.get("trace_id") or "")
            if tid:
                self.traces.add_span(
                    tid, "error" if err else "served",
                    latency_s=round(dt, 4))


#: published-p95 samples older than this stop counting: an idle class
#: must read as recovered (empty window → 0.0 → ladder cooling), not
#: as its last overload forever
SLO_WINDOW_MAX_AGE_S = 60.0


def _window_p95(samples: "collections.deque",
                max_age_s: float = SLO_WINDOW_MAX_AGE_S) -> float:
    """Nearest-rank p95 over a bounded recent-(timestamp, value)
    window, pruning entries older than ``max_age_s`` first (append
    order is time order, so the prune is a popleft loop). Same
    quantile rule as the predictor's `nearest_rank`, kept local so
    the worker doesn't import the predictor module. Empty window →
    0.0, which the brownout ladder reads as cooling."""
    cutoff = time.monotonic() - max_age_s
    while samples and samples[0][0] < cutoff:
        samples.popleft()
    if not samples:
        return 0.0
    vals = sorted(v for _t, v in samples)
    n = len(vals)
    return vals[max(0, min(n - 1, math.ceil(0.95 * n) - 1))]


def _require_dict_or_none(value: Any, name: str) -> Optional[dict]:
    """Config values that must be a JSON object when present: silently
    coercing a malformed one would hide an operator mistake until an
    opaque shape error at first dispatch."""
    if value is None:
        return None
    if not isinstance(value, dict):
        raise ValueError(f"{name} must be a JSON object, got "
                         f"{type(value).__name__}")
    return value


def _safe_sampling(samp: Any) -> dict:
    """Client-supplied sampling params, coerced defensively: a malformed
    value (e.g. {"temperature": "hot"}) must degrade that request to the
    nearest valid config — never raise inside the decode loop, where an
    escaped exception kills the worker thread and every later request
    times out (one bad request = persistent denial of service)."""
    if not isinstance(samp, dict):
        samp = {}

    import math

    def num(key: str, cast, default):
        try:
            v = cast(samp.get(key, default))
        except (TypeError, ValueError, OverflowError):
            # OverflowError: int(float("inf")) — inf is legal msgpack,
            # and an escaped exception here kills the serve thread
            return default
        # NaN/inf would split behavior between the host's greedy-vs-
        # sampling program gate (NaN > 0 is False) and the device's
        # where(temp <= 0) select (also False) — same request, different
        # path depending on batch mix. Finite or default.
        return v if math.isfinite(v) else default

    out = {"temperature": num("temperature", float, 0.0),
           "top_k": num("top_k", int, 0),
           "top_p": num("top_p", float, 1.0),
           "seed": num("seed", int, 0)}
    eos = num("eos_id", int, None)  # absent/malformed → None
    if eos is not None and eos >= 0:
        out["eos_id"] = eos
    aid = num("adapter_id", int, 0)  # multi-adapter engines: which
    if aid:  # forward any non-default id, INCLUDING negatives — the
        # engine rejects out-of-range values and the caller gets an
        # error reply; silently mapping -1 to adapter 0 would be the
        # correct-looking wrong-tenant answer the validation exists for
        out["adapter_id"] = aid
    mn = num("max_new", int, 0)  # per-request generation length; the
    if mn and mn > 0:            # worker clamps to its configured cap
        out["max_new"] = mn      # (capacity protection) at submit time
    return out


def _expired(msg: dict, skew_s: float = EXPIRY_SKEW_TOLERANCE_S,
             skew_est: Optional[ClockSkewEstimator] = None) -> bool:
    """The predictor stamps each query with its gather deadline; a
    worker that pops it too late must drop it — the answer would land
    in a discarded reply queue and leak there forever (and the forward
    pass would be wasted compute).

    **Preferred path** (payloads carrying the relative ``ttl_s`` +
    ``sent_ts`` pair and a ``skew_est``): elapsed-since-scatter comes
    from the :class:`ClockSkewEstimator` — cross-host wall-clock skew
    cancels, so the pad shrinks from ``EXPIRY_SKEW_TOLERANCE_S`` to
    ``TTL_EXPIRY_PAD_S`` and a worker clock running minutes ahead no
    longer silently drops every fresh query.

    **Fallback** (old payloads / no estimator): the wall-clock
    ``deadline_ts`` judged on this host's clock, padded by ``skew_s``
    because deadline_ts is the PREDICTOR's wall clock (ADVICE r3):
    without the margin, cross-machine clock skew beyond the gather
    timeout makes a worker silently drop every query while the
    predictor only sees timeouts. The cost is at most one wasted
    forward per truly-late query; reply-queue TTLs are padded against
    the same constant."""
    import time

    ttl = msg.get("ttl_s")
    sent = msg.get("sent_ts")
    if (skew_est is not None and ttl is not None and sent is not None
            and isinstance(ttl, (int, float))
            and isinstance(sent, (int, float))):
        return skew_est.elapsed_since(float(sent)) \
            > float(ttl) + TTL_EXPIRY_PAD_S
    ts = msg.get("deadline_ts")
    return ts is not None and time.time() > float(ts) + skew_s  # rafiki: noqa[taint-wall-clock-flow] — the documented wall-clock FALLBACK (old payloads); ttl_s+skew_est above is the sanctioned path


def _tristate(v: Any) -> Optional[bool]:
    """Config value → the ``paged_kernel`` tri-state: absent /
    blank / ``"auto"`` mean None (the ops-level backend rule
    decides); anything else coerces to a hard bool override. One
    parse for the worker config AND the admin ``PAGED_KERNEL``
    budget key — two diverging coercions of the same value would be
    a config-dependent dispatch bug."""
    if v is None:
        return None
    if isinstance(v, str):
        s = v.strip().lower()
        if s in ("", "auto"):
            return None
        return s in ("1", "true", "on", "yes")
    return bool(v)


def _to_plain(preds: List[Any]) -> List[Any]:
    """Predictions as a list of plain lists/scalars (msgpack-safe)."""
    out = []
    for p in preds:
        if isinstance(p, np.ndarray):
            out.append(p.tolist())
        elif hasattr(p, "tolist"):
            out.append(np.asarray(p).tolist())
        else:
            out.append(p)
    return out


def main(argv: Optional[list] = None) -> int:
    """Service entrypoint: ``python -m rafiki_tpu.worker.inference``."""
    import argparse
    import json

    from ..parallel.multihost import initialize_from_env
    from ..utils.platform import apply_platform_env, log_devices

    apply_platform_env()  # before any jax backend initializes
    initialize_from_env()  # multi-host rendezvous (no-op if unconfigured)
    log_devices("inference worker")

    from ..model.base import load_model_class
    from ..serving.queues import KVQueueHub

    parser = argparse.ArgumentParser()
    parser.add_argument("--config", required=True,
                        help="JSON: {model_file, model_class, trial_id, "
                             "knobs, param_store_uri, kv_host, kv_port, "
                             "worker_id}")
    args = parser.parse_args(argv)
    with open(args.config) as f:
        cfg = json.load(f)
    with open(cfg["model_file"], "rb") as f:
        model_class = load_model_class(f.read(), cfg["model_class"])
    worker = InferenceWorker(
        model_class=model_class, trial_id=cfg["trial_id"],
        knobs=cfg.get("knobs", {}),
        param_store=ParamStore.from_uri(cfg["param_store_uri"]),
        hub=KVQueueHub(cfg["kv_host"], int(cfg["kv_port"])),
        worker_id=cfg["worker_id"],
        decode_loop=bool(cfg.get("decode_loop")),
        max_slots=int(cfg.get("max_slots", 8)),
        steps_per_sync=int(cfg.get("steps_per_sync", 4)),
        max_new_tokens=int(cfg.get("max_new_tokens", 8)),
        speculate_k=int(cfg.get("speculate_k", 0)),
        system_prefix=str(cfg.get("system_prefix", "")),
        extra_adapter_trials=list(cfg.get("extra_adapter_trials") or []),
        draft_trial_id=str(cfg.get("draft_trial_id", "")),
        draft_knobs=_require_dict_or_none(cfg.get("draft_knobs"),
                                          "draft_knobs"),
        kv_page_size=int(cfg.get("kv_page_size", 0)),
        kv_pages=int(cfg.get("kv_pages", 0)),
        paged_kernel=_tristate(cfg.get("paged_kernel")),
        default_slo=str(cfg.get("default_slo", "")),
        role=str(cfg.get("role", "")),
        host_kv_pages=int(cfg.get("host_kv_pages", 0)),
        kv_wait_s=float(cfg.get("kv_wait_s", 1.5)),
        pool_id=str(cfg.get("pool_id", "")))
    # observability sidecar: /metrics + /debug/requests on an ephemeral
    # (or configured) port, written to obs_port_file for the operator
    obs_host, obs_port = worker.serve_obs(
        cfg.get("obs_host", "127.0.0.1"), int(cfg.get("obs_port", 0)))
    if cfg.get("obs_port_file"):
        with open(cfg["obs_port_file"], "w") as f:
            f.write(str(obs_port))
    print(f"inference worker {worker.worker_id} serving "
          f"(obs on {obs_host}:{obs_port})", flush=True)
    worker.run()
    if worker.chaos_killed:
        # a chaos-killed worker must look ERRORED to the control plane
        # (non-zero rc → ServicesManager respawns it), not drained
        print(f"inference worker {worker.worker_id} chaos-killed",
              flush=True)
        return 31
    if worker.draining:
        print(f"inference worker {worker.worker_id} drained cleanly",
              flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
