"""Phase spans (``rafiki_tpu.obs.SPANS``, docs/observability.md "Phase
spans"): the ring itself, the decode engine's turn tiled by its leaf
spans with the counters cut at the same boundaries, the request
instants written with no sink wired, the train loop's spans, and the
``/debug/spans`` route. The engine legs ride the session ``trained`` LM
like the rest of the serving suite.
"""

import sys
import threading
import time

import numpy as np
import pytest

from rafiki_tpu.obs import SPANS, SpanRing, TraceBuffer

# ------------------------------------------------------------- the ring


def test_span_ring_is_bounded_and_stamps_time_ns():
    ring = SpanRing(maxlen=8)
    before = time.time_ns()
    for i in range(20):
        with ring.span("work", i=i):
            pass
    after = time.time_ns()
    recs = ring.snapshot()
    assert len(ring) == len(recs) == 8
    assert [r[6]["i"] for r in recs] == list(range(12, 20))  # newest win
    assert [r[4] for r in recs] == list(range(13, 21))       # seq from 1
    for name, t0, t1, parent, seq, key, attrs in recs:
        assert name == "work" and before <= t0 <= t1 <= after
        assert parent == 0 and key is None


def test_nesting_gives_parent_seq_and_instants_carry_a_key():
    ring = SpanRing()
    with ring.span("turn") as turn:
        with ring.span("admit") as admit:
            at = ring.instant("req.admitted", key="r1", slot=3)
        with ring.span("prep"):
            pass
        turn.set(live=2)
        turn.set(path="scan")
    free = ring.instant("reset")
    elsewhere = ring.instant("req.done", "r1", turn.seq, tokens=5)
    by_seq = {r[4]: r for r in ring.snapshot()}
    assert by_seq[admit.seq][3] == turn.seq
    assert by_seq[at][3] == admit.seq and by_seq[at][5] == "r1"
    assert by_seq[at][1] == by_seq[at][2]  # an instant: t1 == t0
    assert by_seq[at][6] == {"slot": 3}
    assert by_seq[turn.seq][3] == 0
    assert by_seq[turn.seq][6] == {"live": 2, "path": "scan"}
    assert by_seq[free][3] == 0 and by_seq[free][6] is None
    assert by_seq[elsewhere][3] == turn.seq  # an explicit parent
    # a parent closes after its children, so follows them in the ring;
    # snapshot() sorts by seq (the order spans OPENED in)
    assert [r[0] for r in ring.snapshot()][:3] == ["turn", "admit",
                                                   "req.admitted"]
    # the open span's own stamps stay readable once it has closed
    assert turn.t1 >= turn.t0 > 0


def test_self_time_of_a_span_with_two_children():
    recs = [("turn", 100, 200, 0, 1, None, None),
            ("a", 110, 140, 1, 2, None, None),
            ("b", 150, 190, 1, 3, None, None),
            ("mark", 120, 120, 2, 4, "r", None)]
    assert SpanRing.self_time(recs) == {1: 30, 2: 30, 3: 40, 4: 0}
    # a child whose parent is not among the records subtracts nothing
    assert SpanRing.self_time(recs[1:]) == {2: 30, 3: 40, 4: 0}


def test_snapshot_since_until_keeps_what_overlaps():
    ring = SpanRing()
    stamps = []
    for _ in range(5):
        with ring.span("s") as s:
            time.sleep(0.002)
        stamps.append((s.t0, s.t1))
    assert len(ring.snapshot()) == 5
    lo, hi = stamps[1][1], stamps[3][0]  # span 1's end .. span 3's start
    got = ring.snapshot(lo, hi)
    assert [(r[1], r[2]) for r in got] == stamps[1:4]
    assert ring.snapshot(since_ns=stamps[4][1] + 1) == []
    assert len(ring.snapshot(until_ns=stamps[0][0])) == 1


def test_appends_from_two_threads_lose_nothing():
    ring = SpanRing(maxlen=100_000)
    n, errors = 5000, []

    def work(tag):
        try:
            for i in range(n):
                with ring.span(tag) as outer:
                    inner = ring.instant(tag + ".i")
                assert outer.seq < inner
        except Exception as e:  # noqa: BLE001 — surfaced below
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(t,))
                   for t in ("a", "b", "c")]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(old)
    assert not errors
    recs = ring.snapshot()
    assert len(recs) == 3 * 2 * n
    assert len({r[4] for r in recs}) == len(recs)  # no seq handed out twice
    by_seq = {r[4]: r for r in recs}
    for r in recs:  # nesting is per thread: an instant's parent is its own
        if r[0].endswith(".i"):
            assert by_seq[r[3]][0] == r[0][:-2]


def test_trace_buffer_records_carry_unix_start():
    buf = TraceBuffer()
    before = time.time_ns()
    tid = buf.start("t1", request_id="r")
    rec = buf.get(tid)
    assert before <= rec["t0_unix_ns"] <= time.time_ns()
    assert rec["uptime_s"] >= 0 and rec["spans"][0]["name"] == "queued"


# ----------------------------------------------------------- the engine

LEAVES = {"engine.admit", "engine.prefill_prep", "engine.prefill_dispatch",
          "engine.decode_prep", "engine.decode_dispatch",
          "engine.sync_wait", "engine.harvest"}


def _drive(eng, requests):
    """Submit, then step until idle. Returns the ring's records since."""
    since = time.time_ns()
    for rid, prompt, max_new in requests:
        eng.submit(rid, np.asarray(prompt, np.int32), max_new)
    for _ in range(256):
        if not eng.busy:
            break
        eng.step()
        eng.poll_partial()
    assert not eng.busy
    return SPANS.snapshot(since)


REQUESTS = [("r1", [1, 5, 9], 3), ("r2", [1, 7, 11, 13, 2, 4, 6], 12),
            ("r3", [1, 2], 5)]


@pytest.mark.parametrize("speculate_k", [0, 3], ids=["scan", "spec"])
def test_every_turn_is_tiled_by_its_leaves(trained, speculate_k):
    # a thread the scheduler preempts BETWEEN two leaves leaves a gap that
    # no span covers: beside five other test workers about one drive in
    # twenty misses the 95% line on some turn of a millisecond. What is
    # structural is asserted on every drive; the share, on one of three
    for _ in range(3):
        thin = _tiled_drive(trained, speculate_k)
        if not thin:
            break
    assert not thin, thin


def _tiled_drive(trained, speculate_k):
    """One engine, one drive, every assertion but the covered share: the
    turns that fell short of it are returned."""
    from rafiki_tpu.serving.decode_engine import DecodeEngine

    thin = []
    eng = DecodeEngine(trained._module(), trained._params, max_slots=2,
                       max_len=32, speculate_k=speculate_k)
    eng.reset_stats()
    recs = _drive(eng, REQUESTS)
    turns = [r for r in recs if r[0] == "engine.turn"]
    assert len(turns) >= 3
    paths = {t[6]["path"] for t in turns}
    assert ("spec" in paths) if speculate_k else (paths == {"scan"})
    kids = {}
    for r in recs:
        if r[0] in LEAVES:
            kids.setdefault(r[3], []).append(r)
    for name, t0, t1, _parent, seq, _key, attrs in turns:
        leaves = sorted(kids[seq], key=lambda r: r[1])
        for a, b in zip(leaves, leaves[1:]):
            assert a[2] <= b[1]  # one after another
        assert t0 <= leaves[0][1] and leaves[-1][2] <= t1  # none outside
        covered = sum(r[2] - r[1] for r in leaves)
        if covered < 0.95 * (t1 - t0):
            thin.append((attrs, covered, t1 - t0))
        assert attrs["prefill_calls"] == sum(
            r[0] == "engine.prefill_dispatch" for r in leaves)
        assert set(attrs) == {"live", "admitted", "prefill_calls", "path"}
    # the first turn admitted two requests and prefilled them in one call
    assert turns[0][6]["admitted"] == 2 and turns[0][6]["live"] == 2
    # the counters are cut where the spans are
    snap = eng.stats_snapshot()
    total = sum(t[2] - t[1] for t in turns)
    waited = sum(r[2] - r[1] for r in recs if r[0] == "engine.sync_wait")
    assert snap["turns"] == len(turns)
    assert snap["sync_wait_ns"] == waited
    assert snap["turn_host_ns"] + snap["sync_wait_ns"] == total
    # the caller's calls are spans too, outside every turn
    outside = [r for r in recs if r[0] in ("engine.submit", "engine.poll")]
    assert {r[0] for r in outside} == {"engine.submit", "engine.poll"}
    assert all(r[3] == 0 for r in outside)
    assert any(r[0] == "engine.stats_reset"
               for r in SPANS.snapshot(turns[0][1] - 10**10, turns[0][1]))
    return thin


def test_request_instants_reach_the_ring_with_no_sink(trained):
    from rafiki_tpu.serving.decode_engine import DecodeEngine

    eng = DecodeEngine(trained._module(), trained._params, max_slots=2,
                       max_len=32)
    assert eng.span_sink is None
    recs = _drive(eng, REQUESTS)
    done = dict(eng.poll())
    turn_seqs = {r[4] for r in recs if r[0] == "engine.turn"}
    submits = {r[4] for r in recs if r[0] == "engine.submit"}
    for rid, _prompt, max_new in REQUESTS:
        assert len(done[rid]) == max_new
        mine = {r[0]: r for r in recs if r[5] == rid}
        assert set(mine) == {"req.submitted", "req.admitted", "req.prefill",
                             "req.first_token", "req.done"}
        order = [mine[n][1] for n in ("req.submitted", "req.admitted",
                                      "req.prefill", "req.first_token",
                                      "req.done")]
        assert order == sorted(order)
        assert mine["req.submitted"][3] in submits
        for n in ("req.admitted", "req.prefill", "req.first_token",
                  "req.done"):
            assert mine[n][3] in turn_seqs  # parent: the turn, not a leaf
        assert mine["req.done"][6] == {"tokens": max_new}
    # r3 waited for a lane: its queue wait spans r1's whole service
    by = {r[5]: r for r in recs if r[0] == "req.admitted"}
    first_done = min(r[1] for r in recs if r[0] == "req.done")
    assert by["r3"][1] >= first_done


def test_the_sink_still_receives_exactly_the_events_it_did(trained):
    """The sequence the parent commit's engine handed its sink for this
    scenario (recorded before the spans went in), decode_mark included:
    ``submitted`` goes to the ring alone."""
    from rafiki_tpu.serving import decode_engine as de

    eng = de.DecodeEngine(trained._module(), trained._params, max_slots=2,
                          max_len=32)
    events = []
    eng.span_sink = lambda ev, rid, attrs: events.append(
        (ev, rid, dict(attrs)))
    _drive(eng, REQUESTS)
    adm = {"slo": "interactive", "resumed": False}
    assert events == [
        ("admitted", "r1", {"slot": 0, "prompt_tokens": 3, **adm}),
        ("admitted", "r2", {"slot": 1, "prompt_tokens": 7, **adm}),
        ("prefill", "r1", {"prompt_tokens": 3}),
        ("prefill", "r2", {"prompt_tokens": 7}),
        ("first_token", "r1", {}),
        ("first_token", "r2", {}),
        ("done", "r1", {"tokens": 3}),
        ("admitted", "r3", {"slot": 0, "prompt_tokens": 2, **adm}),
        ("prefill", "r3", {"prompt_tokens": 2}),
        ("first_token", "r3", {}),
        ("done", "r3", {"tokens": 5}),
        ("done", "r2", {"tokens": 12}),
    ]
    # the periodic decode_mark is the sink's alone: never in the ring
    since = time.time_ns()
    slot = de._Slot("m", np.asarray([1], np.int32), 64)
    slot.first_tokened = True
    eng._mark_progress(slot, de.SPAN_DECODE_MARK_EVERY - 1,
                       de.SPAN_DECODE_MARK_EVERY)
    assert events[-1] == ("decode_mark", "m",
                          {"tokens": de.SPAN_DECODE_MARK_EVERY})
    assert not [r for r in SPANS.snapshot(since) if r[5] == "m"]


# ------------------------------------------------------- the train loop


def _old_train_epoch(step, state, host_batches, sync_every=8):
    """``train_epoch`` as it was before the spans: the reference."""
    import jax

    losses = []
    for batch in host_batches:
        state, loss = step(state, batch)
        losses.append(loss)
        if sync_every and len(losses) % sync_every == 0:
            jax.block_until_ready(loss)
    if not losses:
        return state, float("nan")
    return state, float(np.mean([float(l) for l in losses]))


@pytest.mark.parametrize("n_batches,sync_every", [(5, 2), (8, 8), (0, 8)])
def test_train_epoch_spans_and_results(n_batches, sync_every):
    import jax
    import jax.numpy as jnp

    from rafiki_tpu.model import train_epoch

    @jax.jit
    def update(w, x):
        loss = jnp.mean((x @ w) ** 2)
        return w - 0.1 * jax.grad(lambda w: jnp.mean((x @ w) ** 2))(w), loss

    def step(state, batch):
        return update(state, batch["x"])

    rng = np.random.default_rng(0)
    batches = [{"x": rng.normal(size=(4, 3)).astype(np.float32)}
               for _ in range(n_batches)]
    w0 = jnp.ones((3,), jnp.float32)
    want_state, want_loss = _old_train_epoch(step, w0, iter(batches),
                                             sync_every)
    since = time.time_ns()
    state, loss = train_epoch(step, w0, iter(batches),
                              sync_every=sync_every)
    recs = [r for r in SPANS.snapshot(since) if r[0].startswith("train.")]
    np.testing.assert_array_equal(np.asarray(state), np.asarray(want_state))
    assert loss == want_loss or (np.isnan(loss) and np.isnan(want_loss))
    count = {}
    for r in recs:
        count[r[0]] = count.get(r[0], 0) + 1
    assert count.get("train.feed", 0) == n_batches
    assert count.get("train.dispatch", 0) == n_batches
    assert count.get("train.sync", 0) == n_batches // sync_every
    assert count["train.epoch"] == 1 and count["train.epoch_end"] == 1
    epoch = next(r for r in recs if r[0] == "train.epoch")
    assert epoch[6] == {"steps": n_batches}
    leaves = sorted((r for r in recs if r[3] == epoch[4]),
                    key=lambda r: r[1])
    assert len(leaves) == len(recs) - 1  # every other span is its child
    assert [r[0] for r in leaves[:2]] == (
        ["train.feed", "train.dispatch"] if n_batches
        else ["train.epoch_end"])
    assert leaves[-1][0] == "train.epoch_end"
    assert epoch[1] <= leaves[0][1] and leaves[-1][2] <= epoch[2]


# -------------------------------------------------------- /debug/spans


def test_debug_spans_route_serves_the_newest():
    from rafiki_tpu.obs import MetricsRegistry, ObsServer
    from rafiki_tpu.utils.http import json_request

    with SPANS.span("route.test", n=1):
        SPANS.instant("route.mark", key=("job", 7))
    srv = ObsServer(MetricsRegistry())
    host, port = srv.start()
    try:
        got = json_request("GET", f"http://{host}:{port}/debug/spans?n=2")
        assert got["count"] == 2
        newest, older = got["spans"]  # newest first
        assert newest["name"] == "route.mark"
        assert newest["key"] == "('job', 7)"
        assert newest["t0_unix_ns"] == newest["t1_unix_ns"]
        assert older["name"] == "route.test" and older["attrs"] == {"n": 1}
        assert newest["parent_seq"] == older["seq"]
        assert json_request(
            "GET", f"http://{host}:{port}/debug/spans?n=0")["spans"] == []
        with pytest.raises(Exception):
            json_request("GET", f"http://{host}:{port}/debug/spans?n=x")
    finally:
        srv.stop()
