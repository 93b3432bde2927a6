"""Request-path fault tolerance, driven by deterministic fault injection.

Covers the four tentpole layers of the fault-tolerance substrate:
per-worker circuit breakers (closed/open/half-open, scatter-time
skipping, all-open fast-fail 503), streaming failover (chaos-killed
worker mid-stream → resumed on a healthy replica with token-exact
output), graceful drain (in-flight streams finish, new work is
rejected structured, the loop exits 0) with ``rolling_restart``
orchestration, and the ``rafiki_tpu.chaos`` injectors themselves
(seeded determinism). Plus the deadline-skew satellite
(``ttl_s``/``sent_ts`` judged through the worker's skew estimator) and
the client SDK satellite (503 retry honoring ``retry_after_s``, typed
``StreamInterrupted`` + auto-resume).
"""

import threading
import time

import pytest

from rafiki_tpu.chaos import ChaosConfig, ChaosHub, ChaosInjector
from rafiki_tpu.models.llama_lora import LlamaLoRA
from rafiki_tpu.serving.breaker import (CLOSED, HALF_OPEN, OPEN,
                                        BreakerBoard)
from rafiki_tpu.serving.predictor import Predictor, PredictorService
from rafiki_tpu.serving.queues import (InProcQueueHub, pack_message,
                                       unpack_message)
from rafiki_tpu.store.param_store import ParamStore
from rafiki_tpu.worker.inference import (ClockSkewEstimator,
                                         InferenceWorker, _expired)

from test_decode_engine import KNOBS  # noqa: F401 — shared knobs


# ---------------------------------------------------------------- breakers

class _Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def test_breaker_state_machine():
    """closed → (threshold misses) → open → (cooldown) → half-open
    probe → success closes / failure re-opens with doubled cooldown."""
    clk = _Clock()
    b = BreakerBoard(["w0", "w1"], fail_threshold=2, cooldown_s=1.0,
                     max_cooldown_s=8.0, now=clk)
    assert b.targets() == ["w0", "w1"]
    b.record_failure("w0")
    assert b.state("w0") == CLOSED  # one miss < threshold
    b.record_failure("w0")
    assert b.state("w0") == OPEN
    assert b.targets() == ["w1"]    # open worker skipped at scatter
    assert int(b.counters["breaker_trips"]) == 1
    # a success resets the OTHER worker's streak independently
    b.record_failure("w1")
    b.record_success("w1")
    b.record_failure("w1")
    assert b.state("w1") == CLOSED
    # cooldown elapses: exactly one probe is admitted
    clk.t += 1.01
    assert sorted(b.targets()) == ["w0", "w1"]  # probe issued here
    assert b.state("w0") == HALF_OPEN
    assert "w0" not in b.targets()  # probe outstanding: no second one
    # failed probe re-opens with doubled cooldown
    b.record_failure("w0")
    assert b.state("w0") == OPEN
    clk.t += 1.5
    assert "w0" not in b.targets()  # 2.0s cooldown now
    clk.t += 0.6
    assert "w0" in b.targets()
    b.record_success("w0")          # probe answered: recovered
    assert b.state("w0") == CLOSED
    assert int(b.counters["breaker_recoveries"]) == 1


def test_breaker_retry_after_and_stale_and_drain():
    clk = _Clock()
    b = BreakerBoard(["w0", "w1"], fail_threshold=1, cooldown_s=2.0,
                     now=clk)
    b.record_failure("w0")
    b.record_stale("w1")  # monotonic-staleness feed force-opens
    assert int(b.counters["breaker_stale_trips"]) == 1
    assert b.targets() == []
    # retry_after = time to the earliest probe
    assert abs(b.retry_after_s() - 2.0) < 1e-6
    clk.t += 1.5
    assert abs(b.retry_after_s() - 0.5) < 1e-6
    # draining workers are excluded without being failures
    b2 = BreakerBoard(["a", "b"], now=clk)
    b2.set_draining("a", True)
    assert b2.targets() == ["b"]
    assert b2.state("a") == CLOSED
    b2.set_draining("a", False)
    assert b2.targets() == ["a", "b"]


# ------------------------------------------------------------- chaos core

def test_chaos_config_parse_and_env():
    cfg = ChaosConfig.parse("kill_after_tokens=8, drop_reply_p=0.25; "
                            "seed=7")
    assert cfg.kill_after_tokens == 8 and cfg.drop_reply_p == 0.25
    assert cfg.seed == 7 and cfg.armed
    with pytest.raises(ValueError):
        ChaosConfig.parse("drop_replyp=0.5")  # typo'd knob fails loudly
    assert ChaosConfig.from_env({"RAFIKI_CHAOS": ""}) is None
    assert ChaosConfig.from_env({}) is None
    got = ChaosConfig.from_env({"RAFIKI_CHAOS": "delay_queue_s=0.01"})
    assert got is not None and got.delay_queue_s == 0.01


def test_chaos_injector_deterministic_and_hub_faults():
    """Same seed + same traffic order = same faults; drops/corruption
    are counted; pops pass through untouched."""
    def run(seed):
        inj = ChaosInjector(ChaosConfig(drop_reply_p=0.5, seed=seed))
        hub = ChaosHub(InProcQueueHub(), inj)
        outcomes = []
        for i in range(32):
            hub.push_prediction("q", b"x%d" % i)
            outcomes.append(hub.pop_prediction("q", 0.0) is not None)
        return outcomes, int(inj.counters["replies_dropped"])

    a, dropped_a = run(3)
    b, dropped_b = run(3)
    c, _ = run(4)
    assert a == b                      # seeded: replayable
    assert a != c                      # different seed: different run
    assert 0 < dropped_a < 32 and dropped_a == dropped_b

    # corruption flips a byte but still delivers
    inj = ChaosInjector(ChaosConfig(corrupt_payload_p=1.0, seed=1))
    hub = ChaosHub(InProcQueueHub(), inj)
    hub.push_prediction("q", b"\x00\x00")
    got = hub.pop_prediction("q", 0.0)
    assert got is not None and got != b"\x00\x00"
    assert int(inj.counters["payloads_corrupted"]) == 1
    # kill trigger latches at the threshold
    inj = ChaosInjector(ChaosConfig(kill_after_tokens=3))
    assert not inj.should_kill(2)
    assert inj.should_kill(3) and inj.should_kill(99)


def test_corrupted_reply_skipped_in_unary_gather():
    """A corrupted reply payload is one replica's bad answer: the
    gather records the error and keeps the other replica's vote."""
    hub = InProcQueueHub()
    pred = Predictor(hub, ["w0", "w1"], gather_timeout=5.0)

    def worker(wid, corrupt):
        raw = hub.pop_query(wid, 5.0)
        msg = unpack_message(raw)
        data = pack_message({"id": msg["id"], "worker_id": wid,
                             "predictions": [[1.0]]})
        if corrupt:
            data = b"\xc1" + data  # 0xc1: never-used msgpack byte
        hub.push_prediction(msg["id"], data)

    ts = [threading.Thread(target=worker, args=("w0", True), daemon=True),
          threading.Thread(target=worker, args=("w1", False),
                           daemon=True)]
    for t in ts:
        t.start()
    preds, info = pred.predict([[0.0]], timeout=5.0)
    assert info["workers_answered"] == 1
    assert preds == [[1.0]]
    assert any("undecodable" in e for e in info["errors"])


# ----------------------------------------------------- fast-fail (503)

def test_all_breakers_open_fast_fails_structured_503():
    """With every worker dead: the first gather burns its (real)
    timeout and trips the breakers; the next request fast-fails in
    ~zero time with a structured 503 + retry_after_s; after the
    cooldown a probe is re-admitted."""
    hub = InProcQueueHub()
    # long cooldown: the breakers must stay open through the whole
    # test's HTTP leg (probe re-admission is unit-tested with the
    # injectable clock above)
    pred = Predictor(hub, ["w0", "w1"], gather_timeout=30.0,
                     breaker_fail_threshold=1, breaker_cooldown_s=60.0)
    _, info = pred.predict([[1.0]], timeout=1.1)
    assert info["workers_answered"] == 0 and not info.get("fast_fail")
    t0 = time.monotonic()
    preds, info = pred.predict([[1.0]], timeout=20.0)
    dt = time.monotonic() - t0
    assert dt < 0.5, f"fast-fail burned {dt:.2f}s of a 20s budget"
    assert preds == [] and info["fast_fail"]
    assert info["retry_after_s"] > 0
    assert info["workers_asked"] == 0
    assert info["workers_skipped"] == 2
    assert int(pred._c_fast_fail.value) == 1
    # the HTTP front maps it to a structured 503
    from rafiki_tpu.utils.http import HttpStatusError, json_request

    svc = PredictorService(pred)
    host, port = svc.start()
    try:
        with pytest.raises(HttpStatusError) as ei:
            json_request("POST", f"http://{host}:{port}/predict",
                         {"queries": [[1.0]], "timeout": 20.0})
        assert ei.value.status == 503
        assert ei.value.payload["retry_after_s"] > 0
        # breaker/fast-fail counters are visible on /metrics
        import urllib.request

        text = urllib.request.urlopen(
            f"http://{host}:{port}/metrics", timeout=5).read().decode()
        assert "breaker_trips 2" in text
        # two fast-fails by now: the direct predict() + the HTTP one
        assert "requests_fast_failed 2" in text
        assert "breaker_open_workers 2" in text
    finally:
        svc.stop()


def test_adaptive_budget_misses_do_not_trip_breakers():
    """Misses under a collapsed ADAPTIVE budget (or a tiny explicit
    timeout) are the latency controller shedding stragglers, not death:
    they must not feed the breakers (BREAKER_MIN_TIMEOUT_S gate)."""
    hub = InProcQueueHub()
    pred = Predictor(hub, ["w0"], gather_timeout=30.0,
                     breaker_fail_threshold=1)
    for _ in range(3):
        _, info = pred.predict([[1.0]], timeout=0.05)
        assert info["workers_answered"] == 0
    assert pred.breakers.state("w0") == CLOSED
    assert int(pred.breakers.counters["breaker_trips"]) == 0


def test_drained_workers_readmitted_without_health_polls():
    """The draining exclusion must self-clear from the respawned
    worker's published stats on the REQUEST path: a predictor used
    purely via predict() (no /health consumer) must not fast-fail
    forever after a rolling restart."""
    hub = InProcQueueHub()
    pred = Predictor(hub, ["w0"], gather_timeout=5.0)
    pred.breakers.set_draining("w0", True)
    # the respawned worker published fresh stats (draining=False)
    hub.put_worker_stats("w0", {"draining": False, "uptime_s": 1.0,
                                "stale_after_s": 60.0})

    def worker():
        raw = hub.pop_query("w0", 5.0)
        msg = unpack_message(raw)
        hub.push_prediction(msg["id"], pack_message(
            {"id": msg["id"], "worker_id": "w0",
             "predictions": [[1.0]]}))

    threading.Thread(target=worker, daemon=True).start()
    preds, info = pred.predict([[0.0]], timeout=5.0)
    assert not info.get("fast_fail")
    assert info["workers_answered"] == 1 and preds == [[1.0]]


# ------------------------------------------------ streaming failover

def _boot_lm_worker(trained, store, hub, wid, **kw):
    worker = InferenceWorker(LlamaLoRA, "t0", KNOBS, store, hub, wid,
                             decode_loop=True, max_slots=4,
                             max_new_tokens=6, **kw)
    th = threading.Thread(target=worker.run, daemon=True)
    th.start()
    return worker, th


@pytest.fixture()
def lm_store(trained):
    store = ParamStore.from_uri("mem://")
    store.save("t0", trained.dump_parameters())
    return store


def _collect_stream(events_iter):
    events = list(events_iter)
    acc = ""
    for ev in events[:-1]:
        assert set(ev) == {"delta"}, ev
        acc += "".join(ev["delta"].values())
    return events, acc


def test_stream_failover_token_exact_on_worker_kill(trained, lm_store):
    """THE acceptance chaos test: a worker chaos-killed mid-stream
    (deltas already delivered) fails over to a healthy replica which
    re-ingests the delivered text as a forced prefix — the stream
    completes with output exactly equal to a no-fault run: nothing
    duplicated, nothing lost."""
    # no-fault reference
    hub = InProcQueueHub()
    ref, ref_t = _boot_lm_worker(trained, lm_store, hub, "ref")
    try:
        events, acc = _collect_stream(Predictor(
            hub, ["ref"], gather_timeout=120.0).predict_stream(
                ["tok1 tok2 tok3"], timeout=60.0))
        expected = events[-1]["predictions"]
        assert acc == expected[0]
    finally:
        ref.stop()
        ref_t.join(timeout=10)

    # faulty fleet: w0 dies after 3 generated tokens (steps_per_sync=1
    # so deltas stream out BEFORE the death — the resume path, not a
    # clean retry), w1 healthy
    hub = InProcQueueHub()
    chaos = ChaosInjector(ChaosConfig(kill_after_tokens=3))
    w0, t0_ = _boot_lm_worker(trained, lm_store, hub, "w0",
                              steps_per_sync=1, chaos=chaos)
    w1, t1_ = _boot_lm_worker(trained, lm_store, hub, "w1")
    # the silence window also bounds how long the SURVIVOR may take to
    # its first delta — a first compile of its resume path, on a host
    # shared with five other xdist workers — so it is not cut to the
    # bone: at 1 s a loaded run tripped w1's breaker too
    pred = Predictor(hub, ["w0", "w1"], gather_timeout=120.0,
                     stream_silence_timeout_s=5.0,
                     breaker_fail_threshold=1)
    try:
        events, acc = _collect_stream(
            pred.predict_stream(["tok1 tok2 tok3"], timeout=60.0))
        final = events[-1]
        assert final.get("done") and "error" not in final, final
        assert final["predictions"] == expected
        assert acc == expected[0], (acc, expected)
        assert final["info"]["failovers"] == 1
        assert w0.chaos_killed
        assert int(pred._c_failover.value) == 1
        assert pred.breakers.state("w0") == OPEN
        # the chaos injection is visible on the worker's metrics
        assert int(chaos.counters["kills"]) == 1
    finally:
        w1.stop()
        t1_.join(timeout=10)
        t0_.join(timeout=10)


def test_stream_resumable_error_and_client_side_resume(trained,
                                                       lm_store):
    """With NO healthy worker left after the kill, the stream ends in a
    structured resumable event (qid + partial + retry_after_s); feeding
    the partial back as ``resume_partial`` against a healthy fleet
    completes the generation without re-delivering the partial text."""
    hub = InProcQueueHub()
    ref, ref_t = _boot_lm_worker(trained, lm_store, hub, "ref")
    try:
        events, _ = _collect_stream(Predictor(
            hub, ["ref"], gather_timeout=120.0).predict_stream(
                ["tok1 tok2 tok3"], timeout=60.0))
        expected = events[-1]["predictions"]
    finally:
        ref.stop()
        ref_t.join(timeout=10)

    hub = InProcQueueHub()
    chaos = ChaosInjector(ChaosConfig(kill_after_tokens=3))
    w0, t0_ = _boot_lm_worker(trained, lm_store, hub, "w0",
                              steps_per_sync=1, chaos=chaos)
    pred = Predictor(hub, ["w0"], gather_timeout=120.0,
                     stream_silence_timeout_s=1.0,
                     breaker_fail_threshold=1)
    events, acc = _collect_stream(
        pred.predict_stream(["tok1 tok2 tok3"], timeout=60.0))
    t0_.join(timeout=10)
    final = events[-1]
    assert final["done"] and final.get("resumable"), final
    assert final["retry_after_s"] > 0 and final.get("qid")
    assert final["partial"][0] == acc and acc, final
    assert expected[0].startswith(acc) and acc != expected[0]
    assert int(pred._c_resumable.value) == 1

    # client-driven resume against a healthy fleet: the stream picks
    # up where it stopped — deltas continue PAST the partial and the
    # final text is exactly the no-fault answer
    hub2 = InProcQueueHub()
    w1, t1_ = _boot_lm_worker(trained, lm_store, hub2, "w1")
    try:
        pred2 = Predictor(hub2, ["w1"], gather_timeout=120.0)
        events2, acc2 = _collect_stream(pred2.predict_stream(
            ["tok1 tok2 tok3"], timeout=60.0,
            resume_partial=final["partial"]))
        final2 = events2[-1]
        assert "error" not in final2
        assert final2["predictions"] == expected
        assert acc + acc2 == expected[0], (acc, acc2, expected)
    finally:
        w1.stop()
        t1_.join(timeout=10)


# ------------------------------------------------------ graceful drain

def test_drain_finishes_inflight_stream_and_exits(trained, lm_store):
    """Drain mid-stream: the in-flight stream completes (zero dropped
    streams), new messages get structured draining rejections the
    predictor fails over on, the loop exits cleanly, and the published
    stats carry the draining flag into the breaker board."""
    hub = InProcQueueHub()
    w0, t0_ = _boot_lm_worker(trained, lm_store, hub, "w0",
                              steps_per_sync=1)
    w1, t1_ = _boot_lm_worker(trained, lm_store, hub, "w1")
    pred = Predictor(hub, ["w0", "w1"], gather_timeout=120.0)
    try:
        events = []
        got_first = threading.Event()

        def consume():
            for ev in pred.predict_stream(["tok1 tok2 tok3"],
                                          timeout=60.0):
                events.append(ev)
                got_first.set()

        th = threading.Thread(target=consume, daemon=True)
        th.start()
        assert got_first.wait(timeout=30), "no first delta"
        w0.drain()  # mid-stream: round-robin pinned this stream to w0
        th.join(timeout=60)
        final = events[-1]
        assert final.get("done") and "error" not in final, final
        assert final["predictions"][0]
        t0_.join(timeout=30)
        assert not t0_.is_alive(), "drained worker loop must exit"
        assert not w0.chaos_killed

        # the predictor learns the drain from published stats and
        # excludes the worker from scatter
        s = pred.stats()
        assert s["workers"]["w0"]["draining"] is True
        assert s["breakers"]["w0"]["draining"] is True
        assert pred.breakers.targets() == ["w1"]

        # new streams route around the drained id and still answer
        events2, acc2 = _collect_stream(
            pred.predict_stream(["tok4"], timeout=60.0))
        assert events2[-1].get("predictions")
    finally:
        w1.stop()
        t1_.join(timeout=10)


def test_drain_via_queue_control_message(trained, lm_store):
    """The {"control": "drain"} queue message drains a worker with no
    HTTP reachability; queued requests behind it get structured
    rejections (counted), and the loop exits. Messages are queued
    BEFORE the loop runs so the pop order is deterministic."""
    hub = InProcQueueHub()
    worker = InferenceWorker(LlamaLoRA, "t0", KNOBS, lm_store, hub,
                             "w0", decode_loop=True, max_slots=4,
                             max_new_tokens=6)
    hub.push_query("w0", pack_message({"control": "drain"}))
    # a request queued BEHIND the drain control: rejected, not starved
    hub.push_query("w0", pack_message(
        {"id": "q1", "queries": ["tok1"],
         "deadline_ts": time.time() + 60.0}))
    worker.run(poll_timeout=0.1)  # returns: drain-complete breaks it
    assert worker.draining
    reply = unpack_message(hub.pop_prediction("q1", 5.0))
    assert reply["draining"] and "draining" in reply["error"]
    assert int(worker.stats["drain_rejected"]) == 1


def test_drain_endpoint_on_obs_sidecar(trained, lm_store):
    """POST /drain on the obs sidecar (what rolling_restart uses)."""
    from rafiki_tpu.utils.http import json_request

    hub = InProcQueueHub()
    w0, t0_ = _boot_lm_worker(trained, lm_store, hub, "w0")
    host, port = w0.serve_obs()
    try:
        out = json_request("POST", f"http://{host}:{port}/drain", {},
                           timeout=5.0)
        assert out == {"ok": True, "draining": True}
        t0_.join(timeout=30)
        assert not t0_.is_alive() and w0.draining
    finally:
        w0.stop()


# ------------------------------------------------- rolling restart

def test_rolling_restart_drains_and_replaces_workers(tmp_path):
    """ServicesManager.rolling_restart over drainable child processes:
    each worker is drained (obs /drain), exits 0, and is replaced one
    at a time; slots are conserved and the counter advances."""
    from rafiki_tpu.admin.services_manager import ServicesManager
    from rafiki_tpu.constants import ServiceType
    from rafiki_tpu.parallel.mesh import DeviceSpec
    from rafiki_tpu.store.meta_store import MetaStore

    meta = MetaStore(str(tmp_path / "meta.db"))
    user = meta.create_user("op@x", "pw", "ADMIN")
    tj = meta.create_train_job(user["id"], "app", 1,
                               "LANGUAGE_MODELING", {"TRIAL_COUNT": 1},
                               "d1", "d2")
    ij = meta.create_inference_job(user["id"], tj["id"])
    meta.update_inference_job(ij["id"], status="RUNNING")
    mgr = ServicesManager(meta, str(tmp_path / "wd"), slot_size=1,
                          platform="cpu",
                          devices=[DeviceSpec(id=0), DeviceSpec(id=1)])
    try:
        old = []
        for i in range(2):
            wid = f"dw-{i}"
            old.append(mgr._spawn(
                "rafiki_tpu.chaos.dummy_service",
                {"worker_id": wid, "drain_linger_s": 0.2,
                 "obs_port_file": str(tmp_path / "wd"
                                      / f"{wid}.obs_port")},
                ServiceType.INFERENCE_WORKER,
                slot=mgr.allocator.acquire(),
                inference_job_id=ij["id"]))
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline and not all(
                (tmp_path / "wd" / f"dw-{i}.obs_port").exists()
                for i in range(2)):
            time.sleep(0.05)
        out = mgr.rolling_restart(ij["id"], drain_timeout=30.0)
        assert len(out["restarted"]) == 2
        live = [s for s in mgr.services.values()
                if s.service_type == ServiceType.INFERENCE_WORKER]
        assert len(live) == 2 and all(s.alive() for s in live)
        assert not ({s.service_id for s in old}
                    & {s.service_id for s in live})
        # the drained originals exited CLEANLY (rc 0: drain, not crash)
        assert all(s.proc.returncode == 0 for s in old)
        assert mgr.respawn_stats()["rolling_restarts_done"] == 2
        assert mgr.allocator.free_count() == 0  # slots conserved
        with pytest.raises(KeyError):
            mgr.rolling_restart("no-such-job")
    finally:
        mgr.stop_all()


# -------------------------------------------- deadline skew (ttl_s)

def test_ttl_expiry_survives_worker_clock_skew():
    """A worker clock running AHEAD used to silently drop every fresh
    query once skew beat the wall pad; the relative ttl_s judged
    through the skew estimator serves them, while genuinely expired
    messages still drop with a far smaller pad."""
    est = ClockSkewEstimator()
    now = time.time()
    skew = 10.0  # predictor's clock is 10s behind this worker's
    fresh = {"deadline_ts": now - skew + 2.0, "ttl_s": 2.0,
             "sent_ts": now - skew}
    # wall fallback (old behavior): drops the FRESH query
    assert _expired(fresh) is True
    # ttl path: skew cancels, the query serves
    assert _expired(fresh, skew_est=est) is False
    # with the baseline established, true expiry still drops: sent 4s
    # of real elapsed ago against a 2s ttl
    stale = {"deadline_ts": now - skew + 2.0, "ttl_s": 2.0,
             "sent_ts": now - skew - 4.0}
    assert _expired(stale, skew_est=est) is True
    # payloads without the relative pair keep the wall behavior
    assert _expired({"deadline_ts": now + 60.0}, skew_est=est) is False
    assert _expired({"deadline_ts": now - 60.0}, skew_est=est) is True
    assert _expired({}, skew_est=est) is False


def test_clock_skew_estimator_converges_on_minimum():
    est = ClockSkewEstimator()
    base = time.time()
    # observations = skew(5s) + queueing noise; min converges on 5
    for delay in (3.0, 0.5, 1.5, 0.0, 2.0):
        est.elapsed_since(base - 5.0 - delay + (time.time() - base))
    # a fresh message now reads ~its true queueing delay
    got = est.elapsed_since(time.time() - 5.0 - 1.0)
    assert 0.5 < got < 1.6, got


# ------------------------------------------------- client SDK satellite

def test_client_predict_retries_structured_503():
    """One retry, honoring retry_after_s — then success."""
    from rafiki_tpu.client.client import Client
    from rafiki_tpu.utils.http import JsonHttpService

    calls = []

    def handler(_m, _b, _h):
        calls.append(time.monotonic())
        if len(calls) == 1:
            return 503, {"error": "all breakers open",
                         "retry_after_s": 0.3}
        return 200, {"predictions": [[1.0]], "info": {}}

    http = JsonHttpService()
    http.route("POST", "/predict", handler)
    host, port = http.start()
    try:
        client = Client.__new__(Client)
        client.timeout = 10.0
        out = client.predict(f"http://{host}:{port}", [[0.0]])
        assert out == [[1.0]]
        assert len(calls) == 2
        assert calls[1] - calls[0] >= 0.28  # honored retry_after_s
    finally:
        http.stop()


def test_client_stream_auto_resume_and_typed_event():
    """First stream ends resumable → the SDK re-requests with the
    partial as ``resume`` and splices the continuation; with resumes
    exhausted the terminal event is a typed StreamInterrupted."""
    import json as _json

    from rafiki_tpu.client.client import Client, StreamInterrupted
    from rafiki_tpu.utils.http import JsonHttpService, StreamResponse

    bodies = []

    def handler(_m, body, _h):
        bodies.append(body)

        def sse(events):
            for ev in events:
                yield b"data: " + _json.dumps(ev).encode() + b"\n\n"

        if len(bodies) == 1:
            return 200, StreamResponse(sse([
                {"delta": {"0": "par"}},
                {"done": True, "error": "no healthy worker",
                 "resumable": True, "qid": "q1", "partial": ["par"],
                 "retry_after_s": 0.05}]))
        return 200, StreamResponse(sse([
            {"delta": {"0": "tial"}},
            {"done": True, "predictions": ["partial"],
             "info": {}}]))

    http = JsonHttpService()
    http.route("POST", "/predict_stream", handler)
    host, port = http.start()
    try:
        client = Client.__new__(Client)
        client.timeout = 10.0
        events = list(client.predict_stream(
            f"http://{host}:{port}", ["q"], auto_resume=1))
        # the resumable event is swallowed; deltas splice seamlessly
        assert [e for e in events if isinstance(e, dict)
                and "delta" in e] == [{"delta": {"0": "par"}},
                                      {"delta": {"0": "tial"}}]
        assert events[-1]["predictions"] == ["partial"]
        assert bodies[1]["resume"] == ["par"]  # partial handed back

        # exhausted resumes: typed terminal event, duck-dict compatible
        bodies.clear()
        events = list(client.predict_stream(
            f"http://{host}:{port}", ["q"], auto_resume=0))
        term = events[-1]
        assert isinstance(term, StreamInterrupted)
        assert term.done and term.resumable
        assert term.partial == ["par"] and term.qid == "q1"
        assert term.get("done") is True  # dict-style access works
        assert term["partial"] == ["par"]
    finally:
        http.stop()


# --------------------------------- TextDecodeEngine forced prefix

def test_text_engine_forced_prefix_instant_done():
    """A resume whose prefix already covers the whole token budget
    completes without touching the engine (the lost-final-message
    case)."""
    from rafiki_tpu.serving.decode_engine import TextDecodeEngine

    class StubEngine:
        def __init__(self):
            self.submitted = []

        def submit(self, *a, **k):
            self.submitted.append((a, k))

        def poll(self):
            return []

        def poll_partial(self):
            return []

    import numpy as np

    stub = StubEngine()
    eng = TextDecodeEngine(
        stub, lambda t: np.zeros(len(t.split()), np.int32),
        lambda ids: "", max_new=2)
    assert eng.supports_resume
    # prefix of 2 words == the whole budget: instant done
    eng.submit("r", "p1 p2", forced_prefix="g1 g2")
    assert stub.submitted == []
    assert eng.poll() == [("r", "g1 g2")]
    assert eng.poll() == []
    # prefix of 1 word: budget shrinks to 1, prompt carries the prefix
    eng.submit("r2", "p1 p2", max_new=2, forced_prefix="g1")
    (args, kwargs) = stub.submitted[0]
    assert len(args[1]) == 3  # p1 p2 g1 re-ingested as prompt
    assert args[2] == 1       # one token left to generate
