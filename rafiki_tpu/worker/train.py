"""Train worker: the per-sub-mesh trial loop.

Parity target: the reference's ``worker/train.py`` (SURVEY.md §3.1): loop
until the advisor's budget is exhausted — get a proposal, build the model
template with the proposed knobs, train, evaluate, report the score, save
parameters. One worker per TPU sub-mesh replaces one container per GPU.

TPU-first deltas:
- The worker passes its sub-mesh devices into ``TrainContext`` so templates
  pjit over exactly the chips they own (device multi-tenancy, SURVEY.md §7).
- BOHB rung semantics ride the same loop: ``budget_scale`` scales epochs,
  ``warm_start_trial_id`` resumes a promoted trial from its own lower-rung
  checkpoint in the ParamStore.
- ``should_continue`` gives the advisor a per-epoch early-stop hook
  (preemption-friendly: the last completed epoch is always checkpointable).
"""

from __future__ import annotations

import math
import time
import traceback
from typing import Any, Dict, List, Optional, Tuple, Type

from ..model.base import BaseModel, TrainContext
from ..model.log import ModelLogger
from ..obs import (MetricsRegistry, ObsServer, TraceBuffer,
                   mint_trace_id)
from ..store.param_store import ParamStore

#: substrings marking infra-class failures in exception text. The gRPC/XLA
#: status names cover the TPU runtime's device-loss vocabulary
#: (jaxlib raises XlaRuntimeError with "UNAVAILABLE: ..."-style messages);
#: "preempt" covers scheduler/maintenance-event wording.
_PREEMPTION_MARKERS = ("UNAVAILABLE", "RESOURCE_EXHAUSTED",
                       "DEADLINE_EXCEEDED", "DATA_LOSS", "ABORTED",
                       "preempt")


def classify_trial_error(e: BaseException) -> str:
    """``"preemption"`` (infra fault — resumable on healthy hardware) vs
    ``"deterministic"`` (code/knob bug — resume would reproduce the
    crash). Drives :meth:`MetaStore.claim_trial_for_resume` eligibility:
    only preemption-class ERRORED rows may be claimed by peers."""
    if isinstance(e, (FileNotFoundError, IsADirectoryError,
                      NotADirectoryError, PermissionError)):
        # path-shaped OSErrors are config bugs (wrong dataset path,
        # missing blob) — every peer would hit the identical error
        return "deterministic"
    if isinstance(e, (OSError, MemoryError, EOFError)):
        return "preemption"
    msg = f"{type(e).__name__}: {e}"
    if any(m in msg for m in _PREEMPTION_MARKERS):
        return "preemption"
    return "deterministic"


class TrainWorker:
    """Runs trials against an advisor (in-proc object or HTTP client —
    both expose propose/feedback/trial_errored)."""

    def __init__(self, model_class: Type[BaseModel], advisor: Any,
                 train_dataset_path: str, val_dataset_path: str,
                 param_store: Optional[ParamStore] = None,
                 meta_store: Optional[Any] = None,
                 sub_train_job_id: str = "", model_id: str = "",
                 devices: Optional[List[Any]] = None,
                 worker_id: str = "worker-0",
                 profile_dir: Optional[str] = None,
                 knob_overrides: Optional[dict] = None,
                 checkpoint_interval_s: float = 30.0) -> None:
        self.model_class = model_class
        self.advisor = advisor
        self.train_dataset_path = train_dataset_path
        self.val_dataset_path = val_dataset_path
        self.param_store = param_store or ParamStore()
        self.meta_store = meta_store
        self.sub_train_job_id = sub_train_job_id
        self.model_id = model_id
        self.devices = devices
        self.worker_id = worker_id
        self.profile_dir = profile_dir
        #: job-level knob pins (train_args["knob_overrides"]) merged over
        #: every proposal — how a job fixes e.g. max_len or batch_size
        #: regardless of what the advisor samples
        self.knob_overrides = dict(knob_overrides or {})
        #: min seconds between mid-trial checkpoints; <=0 disables them
        self.checkpoint_interval_s = checkpoint_interval_s
        #: liveness beacon period while a trial trains (threaded, so
        #: long epochs don't read as death)
        self.heartbeat_interval_s = 5.0
        #: a RUNNING trial with no heartbeat for this long is an orphan
        self.orphan_stale_s = 60.0
        #: lifetime cap on resumed orphans (bounds ping-pong when a
        #: resumed trial keeps crashing deterministically across workers)
        self.max_resumes = 16
        self._resumes_done = 0
        #: trial ids created by THIS process (self-resume exclusion that
        #: still lets a restarted worker reclaim its pre-restart orphan)
        self._own_trial_ids: set = set()
        self.trials_run = 0
        #: obs plane: per-trial wall/epoch timing + throughput so the
        #: advisor's trials become comparable on MORE than loss — the
        #: same registry/trace surfaces (/metrics, /debug/requests via
        #: serve_obs) every other service exposes
        self.metrics = MetricsRegistry()
        self.traces = TraceBuffer(256)
        self._h_trial = self.metrics.histogram(
            "trial_seconds", "trial wall time, train+eval (seconds)",
            buckets=(1, 2, 5, 10, 30, 60, 120, 300, 600, 1800, 3600,
                     7200, 14400))
        self._h_epoch = self.metrics.histogram(
            "epoch_seconds", "gap between epoch metric records",
            buckets=(0.1, 0.25, 0.5, 1, 2, 5, 10, 30, 60, 120, 300,
                     600, 1800))
        self._c_completed = self.metrics.counter(
            "trials_completed", "trials that finished with a score")
        self._c_errored = self.metrics.counter(
            "trials_errored", "trials that raised")
        self._g_tps = self.metrics.gauge(
            "last_trial_tokens_per_s",
            "token throughput of the last completed trial (LM only)")
        self._g_mfu = self.metrics.gauge(
            "last_trial_est_mfu",
            "estimated model-FLOPs utilization of the last trial")
        self._obs_server: Optional[ObsServer] = None

    def serve_obs(self, host: str = "127.0.0.1",
                  port: int = 0) -> Tuple[str, int]:
        """Start the observability sidecar (``GET /metrics``,
        ``GET /debug/requests`` — trial timelines) on a daemon thread."""
        self._obs_server = ObsServer(self.metrics, self.traces,
                                     host=host, port=port)
        return self._obs_server.start()

    def stop_obs(self) -> None:
        if self._obs_server is not None:
            self._obs_server.stop()
            self._obs_server = None

    # ---- one trial ----
    def run_trial(self, proposal) -> Optional[float]:
        from ..advisor.base import TrialResult

        from ..model.knob import shape_signature

        if self.knob_overrides:
            proposal.knobs = {**proposal.knobs, **self.knob_overrides}
        if proposal.meta.get("resumed_from") and \
                proposal.warm_start_trial_id and \
                "share_params" in self.model_class.get_knob_config():
            # AFTER the override merge: a job-level share_params pin must
            # not silently drop the resume's warm start (the reduced
            # budget only makes sense on top of the checkpoint)
            proposal.knobs = {**proposal.knobs, "share_params": True}
        # resumed trials: the row records the ORIGINAL budget_scale (so a
        # later re-resume computes remainders against the true total);
        # only the in-context scale is reduced by progress already made
        base_frac = float(proposal.meta.get("resume_frac_done") or 0.0)
        ctx_budget_scale = proposal.budget_scale * max(0.0, 1.0 - base_frac)
        if self.meta_store is not None:
            trial_id = self.meta_store.create_trial(
                self.sub_train_job_id, proposal.trial_no,
                model_id=self.model_id, knobs=proposal.knobs,
                worker_id=self.worker_id,
                budget_scale=proposal.budget_scale,
                shape_sig=shape_signature(
                    self.model_class.get_knob_config(), proposal.knobs))["id"]
        else:
            trial_id = f"{self.worker_id}-t{proposal.trial_no}"
        self._own_trial_ids.add(trial_id)

        logger = ModelLogger()
        obs_acc: Dict[str, Any] = {"tokens": 0, "epochs": 0,
                                   "last_t": None}

        def _sink(rec) -> None:
            # obs first (epoch timing / token accounting), then the
            # MetaStore forward the dashboard reads
            self._observe_log_record(rec, obs_acc)
            if self.meta_store is not None:
                self.meta_store.add_trial_log(trial_id, rec.kind,
                                              rec.data, rec.time)

        logger.sink = _sink
        t_start = time.monotonic()
        trace_id = self.traces.start(
            mint_trace_id(), request_id=trial_id, span="trial_start",
            trial_no=proposal.trial_no, worker=self.worker_id)

        # heartbeat covers the trial row's ENTIRE time in RUNNING state —
        # including the final (possibly multi-GB) parameter save — so a
        # live finishing trial can never look orphaned to a peer
        hb_stop = self._start_heartbeat(trial_id)
        try:
            try:
                self.model_class.validate_knobs(proposal.knobs)
                model = self.model_class(**proposal.knobs)
                self._admission_check(model)
                shared = None
                if proposal.warm_start_trial_id:
                    shared = self.param_store.load(
                        proposal.warm_start_trial_id)
                    if shared is None:
                        # big-model trials checkpoint SHARDED (SURVEY
                        # §5.4) — hand the template a lazy restore
                        # handle instead of assembling the tree here
                        shared = self.param_store.sharded_ref(
                            proposal.warm_start_trial_id)
                trial_profile_dir = None
                if self.profile_dir:
                    import os

                    trial_profile_dir = os.path.join(self.profile_dir,
                                                     trial_id)
                    os.makedirs(trial_profile_dir, exist_ok=True)
                ctx = TrainContext(devices=self.devices,
                                   budget_scale=ctx_budget_scale,
                                   shared_params=shared, logger=logger,
                                   trial_id=trial_id,
                                   profile_dir=trial_profile_dir)
                ckpt_key = f"ckpt-{trial_id}"
                if self.checkpoint_interval_s > 0:
                    self._wire_checkpointing(ctx, ckpt_key, base_frac,
                                             proposal, shared)
                if trial_profile_dir:
                    # per-trial jax.profiler trace (SURVEY.md §5.1):
                    # XLA/HLO timing + (on TPU) hardware counters,
                    # viewable in TensorBoard / Perfetto
                    import jax

                    with jax.profiler.trace(trial_profile_dir):
                        model.train(self.train_dataset_path, ctx)
                else:
                    model.train(self.train_dataset_path, ctx)
                score = float(model.evaluate(self.val_dataset_path))

                blob = model.dump_parameters()
                self._record_trial_obs(logger, trace_id, t_start,
                                       obs_acc, blob, score)
                self.param_store.save(trial_id, blob)
                model.destroy()
                fenced_out = False
                if self.meta_store is not None:
                    # fenced completion: False = a resume claimant already
                    # TERMINATED this row (we were presumed dead during a
                    # long stall) — our duplicate must NOT double-feed the
                    # advisor for this trial_no
                    fenced_out = not self.meta_store.mark_trial_completed(
                        trial_id, score, params_saved=True)
                try:
                    # cleanup is best-effort AFTER the terminal mark: a
                    # kv hiccup here must not void a finished trial
                    self.param_store.delete(ckpt_key)
                    self.param_store.delete(f"{ckpt_key}-meta")
                except Exception:  # rafiki: noqa[silent-except]
                    pass
                if not fenced_out:
                    try:
                        self.advisor.feedback(TrialResult(
                            trial_no=proposal.trial_no,
                            knobs=proposal.knobs,
                            score=score, trial_id=trial_id,
                            budget_scale=proposal.budget_scale,
                            meta=proposal.meta))
                    except Exception:  # noqa: BLE001
                        # a resumed trial may outlive its advisor's
                        # bracket state (advisor restarted with the
                        # stack); the score is already durable in the
                        # MetaStore, which is what deployment reads
                        if not proposal.meta.get("resumed_from"):
                            raise
                self.trials_run += 1
                return score
            except Exception as e:  # trial fault isolation (SURVEY §5.3)
                self._c_errored.inc()
                self.traces.add_span(trace_id, "trial_errored",
                                     error=f"{type(e).__name__}: {e}"[:200],
                                     error_class=classify_trial_error(e))
                fenced_out = False
                if self.meta_store is not None:
                    fenced_out = not self.meta_store.mark_trial_errored(
                        trial_id, f"{e}\n{traceback.format_exc()}",
                        error_class=classify_trial_error(e))
                if not fenced_out:
                    try:
                        self.advisor.trial_errored(proposal.trial_no)
                    except Exception:  # rafiki: noqa[silent-except]
                        # — a dead/restarted advisor must not kill the
                        # surviving worker; the error is durable in
                        # the MetaStore either way
                        pass
                return None
        finally:
            hb_stop()

    def _observe_log_record(self, rec, obs_acc: Dict[str, Any]) -> None:
        """Watch the trial's metric stream: every ``values`` record
        carrying a loss marks an epoch boundary — the inter-record gap
        is the live step-time signal — and templates that report a
        per-epoch ``tokens`` count (the LM loop does) accumulate it for
        throughput/MFU at trial end."""
        if rec.kind != "values" or "loss" not in rec.data:
            return
        now = time.monotonic()
        if obs_acc["last_t"] is not None:
            self._h_epoch.observe(now - obs_acc["last_t"])
        obs_acc["last_t"] = now
        obs_acc["epochs"] += 1
        tokens = rec.data.get("tokens")
        if isinstance(tokens, (int, float)) and tokens > 0:
            obs_acc["tokens"] += int(tokens)

    def _record_trial_obs(self, logger: ModelLogger, trace_id: str,
                          t_start: float, obs_acc: Dict[str, Any],
                          blob: Any, score: float) -> None:
        """Per-trial throughput record: wall seconds always; tokens/s
        and estimated MFU when the template reported per-epoch token
        counts (MFU ≈ 6·N·tokens/s over the device peak — the standard
        dense-LM approximation; an ESTIMATE, labeled as such). Logged
        through the trial's own logger so it lands in the MetaStore
        next to the loss curve — the advisor's trials become comparable
        on throughput, not just loss."""
        dt = time.monotonic() - t_start
        self._h_trial.observe(dt)
        self._c_completed.inc()
        vals: Dict[str, Any] = {"trial_seconds": round(dt, 3),
                                "epochs_logged": obs_acc["epochs"]}
        if obs_acc["tokens"] and dt > 0:
            tps = obs_acc["tokens"] / dt
            vals["tokens_per_s"] = round(tps, 1)
            self._g_tps.set(tps)
            n_params = _count_blob_params(blob)
            # tokens/s is FLEET-wide (the trial shards over this
            # worker's whole sub-mesh), so the denominator is the
            # sub-mesh's aggregate peak, not one chip's
            devs = self.devices
            if devs is None:
                try:
                    import jax

                    devs = jax.local_devices()
                except (ImportError, RuntimeError):
                    devs = None
            peak = _device_peak_flops(devs) * max(1, len(devs or ()))
            if n_params and peak:
                mfu = 6.0 * n_params * tps / peak
                vals["est_mfu"] = round(mfu, 5)
                self._g_mfu.set(mfu)
        try:
            logger.log(**vals)
        except Exception:  # noqa: BLE001 — a meta-store hiccup on the
            import logging  # throughput record must not void the trial

            logging.getLogger(__name__).warning(
                "trial throughput record failed", exc_info=True)
        self.traces.add_span(trace_id, "trial_done",
                             score=round(score, 6), **vals)

    def _admission_check(self, model) -> None:
        """Refuse a trial whose ESTIMATED per-device train footprint
        exceeds the chips' HBM, before any compile/allocation — an OOM
        mid-trial wastes the whole slot and reads as a mystery fault.

        Templates opt in by exposing ``estimate_device_budget(n) ->
        {..., "total": bytes}`` (the Llama template computes it from
        real shape math — ``estimate_train_device_bytes``). The limit
        comes from the accelerator's own ``memory_stats()["bytes_limit"]``
        (TPU/GPU) or the ``RAFIKI_DEVICE_HBM_BYTES`` env override (CPU
        runs have elastic host memory, so without the override the
        check is skipped there). A refusal raises ValueError — a
        deterministic-class trial error (resume would refuse again)."""
        est = getattr(model, "estimate_device_budget", None)
        if est is None:
            return
        import jax

        from .admission import resolve_device_limit

        devs = self.devices or jax.local_devices()
        limit = resolve_device_limit(devs)
        if not limit:
            return
        try:
            budget = est(len(devs))
            total = int(budget["total"])
        except Exception as e:  # an estimator bug must never block an
            # admissible trial — but it must be VISIBLE: silently
            # skipping here disables train admission control
            # fleet-wide until trials start OOMing (ADVICE.md r5)
            import logging

            logging.getLogger(__name__).warning(
                "train admission check skipped: "
                "estimate_device_budget raised %r", e, exc_info=True)
            return
        if total > limit:
            raise ValueError(
                "admission control: estimated "
                f"{total / 2**30:.2f}GiB/device train footprint "
                f"exceeds the {limit / 2**30:.2f}GiB device limit "
                f"(breakdown: { {k: round(v / 2**30, 2) for k, v in budget.items()} } GiB); "
                "shrink batch_size/max_len or enable remat/loss_chunk/"
                "grad_accum/model_parallel")

    def _wire_checkpointing(self, ctx, ckpt_key: str, base_frac: float,
                            proposal, shared) -> None:
        """Attach throttled epoch-boundary checkpointing to ``ctx``.

        The blob factory only runs when a save actually happens.
        ``frac_done`` rides in a tiny sidecar entry (NOT inside the blob —
        warm-start consumers expect ``dump_parameters()``'s exact shape)
        and is always GLOBAL progress: a resumed trial's template reports
        fractions of its REMAINING budget, which are mapped back onto the
        original total so chained resumes stay correct.

        A resumed trial is also pre-seeded with the orphan's checkpoint
        under its OWN key, so if this attempt dies before its first
        throttled save, the warm state is still reachable from this
        trial's row (the orphan's row is already TERMINATED and will
        never be scanned again)."""
        import time as _time

        if proposal.meta.get("resumed_from") and shared is not None:
            # bytes-level copy: no msgpack re-encode of a possibly
            # multi-GB tree that was deserialized moments ago (sharded
            # checkpoints copy at the directory level)
            if not self.param_store.copy(proposal.warm_start_trial_id,
                                         ckpt_key):
                self.param_store.copy_sharded(
                    proposal.warm_start_trial_id, ckpt_key)
            if base_frac > 0:
                self.param_store.save(f"{ckpt_key}-meta",
                                      {"frac_done": base_frac})

        last_save = [_time.monotonic()]

        def save_checkpoint(make_blob, frac_done=None, tree=None) -> None:
            """``tree`` (optional): the template's LIVE (sharded device)
            pytree — saved per-shard + async when the store supports it,
            so no host materializes the full tree (SURVEY §5.4); without
            it (or on mem/kv backends) the zero-arg ``make_blob``
            whole-tree path runs as before."""
            now = _time.monotonic()
            if now - last_save[0] < self.checkpoint_interval_s:
                return
            if tree is None or \
                    not self.param_store.save_sharded_async(ckpt_key,
                                                            tree):
                self.param_store.save(ckpt_key, make_blob())
            if frac_done is not None:
                global_frac = base_frac + float(frac_done) * (1 - base_frac)
                self.param_store.save(f"{ckpt_key}-meta",
                                      {"frac_done": global_frac})
            last_save[0] = now

        ctx.checkpoint = save_checkpoint

    def _start_heartbeat(self, trial_id: str):
        """Stamp the trial row every few seconds while training so peers
        can tell a preempted trial from a live slow one. Returns a
        stopper."""
        if self.meta_store is None:
            return lambda: None
        import threading

        stop = threading.Event()

        def beat() -> None:
            while not stop.wait(self.heartbeat_interval_s):
                try:
                    self.meta_store.heartbeat_trial(trial_id)
                except Exception:  # rafiki: noqa[silent-except]
                    pass           # never kill the trial

        t = threading.Thread(target=beat, daemon=True,
                             name=f"hb-{trial_id[:8]}")
        t.start()
        return stop.set

    # ---- preemption recovery ----
    def resume_orphaned_trials(self) -> int:
        """Finish trials a dead worker left behind (SURVEY.md §5.3).

        Orphan = status ERRORED with ``error_class='preemption'`` (infra
        fault recorded by a live worker — device loss, OOM), or RUNNING
        with a stale heartbeat, i.e. process death (a live owner stamps
        every ``heartbeat_interval_s``; the staleness test is enforced
        INSIDE the atomic claim, so a live peer's trial cannot be
        hijacked and exactly one claimant wins). Deterministic ERRORED
        rows — a code/knob crash — are never resumed: re-running them
        reproduces the crash (ADVICE r3 medium). With a ``ckpt-<id>``
        blob the trial resumes warm under the same knobs and trial_no,
        training only the remaining budget recorded at checkpoint time;
        without one (killed before the first throttled save) it re-runs
        cold — either way no zombie RUNNING rows remain.
        """
        if self.meta_store is None or self._resumes_done >= self.max_resumes:
            return 0
        import json as _json

        from ..advisor.base import Proposal

        n = 0
        for t in self.meta_store.get_trials_of_sub_train_job(
                self.sub_train_job_id):
            if t["status"] not in ("RUNNING", "ERRORED"):
                continue
            if t["status"] == "ERRORED" and \
                    t.get("error_class") != "preemption":
                continue  # deterministic crash — the claim would refuse
                # anyway; skip the doomed UPDATE round-trip
            if t["id"] in self._own_trial_ids:
                # trials from THIS process's lifetime: own failures are
                # code errors, not preemption, and a worker must never
                # loop resuming its own deterministic crash. (Keyed by
                # trial id, not worker_id — a RESTARTED worker with the
                # same deterministic name has an empty set and correctly
                # reclaims its pre-restart orphan.)
                continue
            if self._resumes_done >= self.max_resumes:
                break  # bound cross-worker ping-pong on persistent bugs
            if not self.meta_store.claim_trial_for_resume(
                    t["id"], self.worker_id,
                    stale_after_s=self.orphan_stale_s):
                continue  # live heartbeat, or another worker won
            ckpt_key = f"ckpt-{t['id']}"
            has_ckpt = self.param_store.exists(ckpt_key) or \
                self.param_store.exists_sharded(ckpt_key)
            frac = 0.0
            if has_ckpt:
                meta = self.param_store.load(f"{ckpt_key}-meta")
                if meta and meta.get("frac_done"):
                    frac = float(meta["frac_done"])
            knobs = t["knobs"]
            if isinstance(knobs, str):
                knobs = _json.loads(knobs)
            # the new row keeps the ORIGINAL budget_scale; run_trial
            # reduces only the in-context budget by frac and pre-seeds
            # the new trial's own checkpoint from the orphan's, so a
            # crashed resume is itself resumable at the right progress
            score = self.run_trial(Proposal(
                trial_no=int(t["trial_no"]), knobs=knobs,
                budget_scale=float(t["budget_scale"] or 1.0),
                warm_start_trial_id=ckpt_key if has_ckpt else "",
                meta={"resumed_from": t["id"],
                      "resume_frac_done": frac}))
            if score is not None:
                # delete the orphan's blob only on a COMPLETED resume: a
                # failed attempt may have died before the pre-seed copied
                # it, and this TERMINATED row's ckpt is then the only
                # warm state left (a successful pre-seed makes it merely
                # redundant — a bounded, harmless leak on failure)
                try:
                    self.param_store.delete(ckpt_key)
                    self.param_store.delete(f"{ckpt_key}-meta")
                except Exception:  # rafiki: noqa[silent-except]
                    pass  # cleanup must never kill the worker loop
            self._resumes_done += 1
            n += 1
        return n

    # ---- gang trial mode (rafiki_tpu/tuning) ----
    def run_gang(self, gang_size: int,
                 max_trials: Optional[int] = None) -> int:
        """Gang-compiled trial mode for small-zoo templates: K proposals
        train as K lanes of one vmapped jit step (one compile per static
        knob bucket), the advisor is driven through its batched verbs,
        and ASHA rungs cull lanes in place. Reports one TrialResult per
        lane (MetaStore row + ParamStore blob each, so deployment and
        the dashboard see gang trials exactly like process trials) and
        publishes ``gang_lanes_active`` / ``gang_lanes_culled_total`` /
        ``trials_per_hour`` / ``gang_samples_per_s`` through this
        worker's ObsServer. Falls back to the process loop for templates
        without a gang spec."""
        from ..model.knob import shape_signature
        from ..tuning import GangEngine, supports_gang

        if not supports_gang(self.model_class):
            import logging

            logging.getLogger(__name__).warning(
                "%s has no gang spec; gang_size=%d ignored, running "
                "sequential trials", self.model_class.__name__, gang_size)
            return self.run(max_trials)

        knob_config = self.model_class.get_knob_config()

        def on_result(result, blob) -> None:
            trial_id = result.trial_id
            if self.meta_store is not None:
                row = self.meta_store.create_trial(
                    self.sub_train_job_id, result.trial_no,
                    model_id=self.model_id, knobs=result.knobs,
                    worker_id=self.worker_id,
                    budget_scale=result.budget_scale,
                    shape_sig=shape_signature(knob_config, result.knobs))
                trial_id = row["id"]
            self.param_store.save(trial_id, blob)
            if self.meta_store is not None:
                self.meta_store.mark_trial_completed(
                    trial_id, result.score, params_saved=True)
            self._c_completed.inc()
            self.trials_run += 1

        def admission_check(knobs, k) -> Optional[str]:
            """HBM admission for one gang bucket: the whole gang is ONE
            program on one device slot, so the estimate must cover K
            adapter/optimizer lanes plus the broadcast base — with the
            bucket's ``remat_policy`` trading activation bytes for
            recompute (why a denied bucket can re-admit at
            remat_policy="full"). Returns a refusal reason (the bucket
            then runs sequentially, each trial re-checked by the
            per-trial admission gate) or None to admit."""
            import jax

            from .admission import resolve_device_limit

            devs = self.devices or jax.local_devices()
            limit = resolve_device_limit(devs)
            if not limit:
                return None
            model = self.model_class(**knobs)
            est = getattr(model, "estimate_device_budget", None)
            if est is None:
                return None
            try:
                try:
                    budget = est(len(devs), gang_size=k)
                except TypeError:
                    return None  # estimator predates gang budgets
                total = int(budget["total"])
            except Exception as e:  # estimator bug: visible, not fatal
                import logging

                logging.getLogger(__name__).warning(
                    "gang admission check skipped: "
                    "estimate_device_budget raised %r", e, exc_info=True)
                return None
            if total > limit:
                gib = {key: round(v / 2**30, 2)
                       for key, v in budget.items()}
                return (f"estimated {total / 2**30:.2f}GiB footprint for "
                        f"a {k}-lane gang exceeds the "
                        f"{limit / 2**30:.2f}GiB device limit "
                        f"(breakdown: {gib} GiB); set remat_policy="
                        "'full'/'policy' to trade activation HBM for "
                        "recompute, or shrink the gang")
            return None

        engine = GangEngine(
            self.model_class, self.advisor, self.train_dataset_path,
            self.val_dataset_path, gang_size=gang_size, mode="gang",
            knob_overrides=self.knob_overrides, metrics=self.metrics,
            on_result=on_result, admission_check=admission_check)
        self.gang_engine = engine  # introspection: buckets, refusals
        results = engine.run(max_trials)
        return len(results)

    # ---- the loop ----
    def run(self, max_trials: Optional[int] = None) -> int:
        """Pull proposals until the advisor says stop; returns #trials.

        Orphan pickup happens at startup, between proposals, AND in a
        bounded linger after the advisor is exhausted — a peer preempted
        moments ago has a trial that only turns claimably stale after
        ``orphan_stale_s``, and exiting immediately would strand it as a
        zombie the job finalizer can't resolve.
        """
        n = self.resume_orphaned_trials()
        while max_trials is None or n < max_trials:
            proposal = self.advisor.propose()
            if not proposal.is_valid:
                break
            self.run_trial(proposal)
            n += 1
            n += self.resume_orphaned_trials()
        n += self._linger_for_orphans()
        return n

    def _linger_for_orphans(self) -> int:
        """Wait (bounded) for peers' RUNNING trials to either finish or
        turn stale, resuming any that do. A live peer ends the linger
        early by completing; a dead one becomes claimable within
        ``orphan_stale_s``."""
        if self.meta_store is None:
            return 0
        import time as _time

        deadline = _time.monotonic() + self.orphan_stale_s \
            + 2 * self.heartbeat_interval_s
        n = 0
        while _time.monotonic() < deadline:
            # "not mine" = not created by THIS process — a respawned
            # replacement shares its dead predecessor's worker_id, and
            # the predecessor's mid-flight trial is exactly what it is
            # here to pick up
            peers_running = any(
                t["status"] == "RUNNING"
                and t["id"] not in self._own_trial_ids
                for t in self.meta_store.get_trials_of_sub_train_job(
                    self.sub_train_job_id))
            if not peers_running:
                break
            n += self.resume_orphaned_trials()
            _time.sleep(min(2.0, self.heartbeat_interval_s))
        return n


def _count_blob_params(blob: Any) -> int:
    """Leaf-element count of a dumped parameter tree (numpy arrays in
    nested dicts/lists) — no jax import needed."""
    if hasattr(blob, "shape"):
        try:
            return int(math.prod(blob.shape))
        except (TypeError, ValueError):
            return 0
    if isinstance(blob, dict):
        return sum(_count_blob_params(v) for v in blob.values())
    if isinstance(blob, (list, tuple)):
        return sum(_count_blob_params(v) for v in blob)
    return 0


#: bf16 peak FLOP/s per chip by device_kind substring (first match
#: wins, so the more specific names come first). Used only for the
#: est_mfu label — an estimate feeding trial comparisons, not billing.
_PEAK_FLOPS_BF16 = (
    ("v6", 918e12), ("v5p", 459e12), ("v5", 197e12),
    ("v4", 275e12), ("v3", 123e12), ("v2", 45e12),
)


def _device_peak_flops(devices: Optional[List[Any]] = None) -> float:
    """Per-device peak FLOP/s: the ``RAFIKI_DEVICE_PEAK_FLOPS`` env
    override wins (how CPU runs get a nonzero MFU denominator in
    tests), else a device_kind lookup; unknown hardware → 0, which
    suppresses the MFU estimate rather than fabricating one."""
    import os

    env = os.environ.get("RAFIKI_DEVICE_PEAK_FLOPS", "")
    if env:
        try:
            return max(0.0, float(env))
        except ValueError:
            return 0.0
    try:
        if devices is None:
            import jax

            devices = jax.local_devices()
        kind = str(getattr(devices[0], "device_kind", "") or "").lower()
    except (ImportError, IndexError, RuntimeError):
        return 0.0
    for key, flops in _PEAK_FLOPS_BF16:
        if key in kind:
            return flops
    return 0.0


def main(argv: Optional[list] = None) -> int:
    """Service entrypoint: ``python -m rafiki_tpu.worker.train``.

    Spawned by the ServicesManager with a JSON config file; connects to the
    advisor service over HTTP and to the shared stores.
    """
    import argparse
    import json

    from ..parallel.multihost import initialize_from_env
    from ..utils.platform import apply_platform_env, log_devices

    apply_platform_env()  # before any jax backend initializes
    initialize_from_env()  # multi-host rendezvous (no-op if unconfigured)
    log_devices("train worker")

    from ..advisor.service import AdvisorClient
    from ..model.base import load_model_class
    from ..store.meta_store import MetaStore

    parser = argparse.ArgumentParser()
    parser.add_argument("--config", required=True,
                        help="JSON: {advisor_url, model_file, model_class, "
                             "train_dataset, val_dataset, param_store_uri, "
                             "meta_store_path, sub_train_job_id, worker_id}")
    args = parser.parse_args(argv)
    with open(args.config) as f:
        cfg = json.load(f)

    with open(cfg["model_file"], "rb") as f:
        model_class = load_model_class(f.read(), cfg["model_class"])
    meta_store = (MetaStore(cfg["meta_store_path"])
                  if cfg.get("meta_store_path") else None)
    worker = TrainWorker(
        model_class=model_class,
        advisor=AdvisorClient(cfg["advisor_url"]),
        train_dataset_path=cfg["train_dataset"],
        val_dataset_path=cfg["val_dataset"],
        param_store=ParamStore.from_uri(cfg.get("param_store_uri", "mem://")),
        meta_store=meta_store,
        sub_train_job_id=cfg.get("sub_train_job_id", ""),
        model_id=cfg.get("model_id", ""),
        worker_id=cfg.get("worker_id", "worker-0"),
        profile_dir=cfg.get("profile_dir"),
        knob_overrides=cfg.get("knob_overrides"),
        checkpoint_interval_s=float(
            cfg.get("checkpoint_interval_s", 30.0)))
    # observability sidecar: /metrics (trial/epoch timing, MFU gauges)
    # + /debug/requests (per-trial timelines)
    obs_host, obs_port = worker.serve_obs(
        cfg.get("obs_host", "127.0.0.1"), int(cfg.get("obs_port", 0)))
    if cfg.get("obs_port_file"):
        with open(cfg["obs_port_file"], "w") as f:
            f.write(str(obs_port))
    print(f"train worker {worker.worker_id} obs on "
          f"{obs_host}:{obs_port}", flush=True)
    try:
        gang_size = int(cfg.get("gang_size") or 0)
        n = worker.run_gang(gang_size) if gang_size >= 1 else worker.run()
    finally:
        worker.stop_obs()
    print(f"train worker {worker.worker_id} done: {n} trials", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
