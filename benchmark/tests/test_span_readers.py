"""The two span readers on synthetic program records laid over the recorded
device events of ``data/trace_events.json`` (PR 27's serving cell; moved
5 ms into the session here: one ``jit_step_fn`` execution 5 .. 56.3 ms, one
``jit_prefill_fn`` 60.3 .. 88.4 ms), and the session's bounds read back from
a trace made here."""

import json
import os
import time

import pytest

from benchmark import trace_reduce as tr
from benchmark.readers import span_idle_cover as cover
from benchmark.readers import span_stat

DATA = os.path.join(os.path.dirname(__file__), "data", "trace_events.json")
MS = 1_000_000
SERVE = {"prefix": "engine.", "dispatch": "engine.decode_dispatch",
         "wait": "engine.sync_wait", "module_pattern": "^jit_step_fn$"}


@pytest.fixture(scope="module")
def device():
    rec = json.load(open(DATA))
    lines = {k: [(n, s + 5 * MS, d) for n, s, d in v]
             for k, v in rec["lines"].items()}
    return lines[tr.OPS_LINE], lines[tr.MODULES_LINE]


def _turn(spans, shift=0):
    """One ``engine.turn`` (seq 1) tiled by ``spans`` = [(name, t0, t1)]."""
    lo, hi = min(s[1] for s in spans), max(s[2] for s in spans)
    out = [(n, a + shift, b + shift, 1, i + 2, None, None)
           for i, (n, a, b) in enumerate(spans)]
    return out + [("engine.turn", lo + shift, hi + shift, 0, 1, None, None)]


def test_idle_fully_inside_one_leaf_reads_100(device):
    ops, mods = device
    # the sync wait spans both programs: every gap between two device ops
    # lies inside it, and the launch's own few hundred microseconds inside
    # the dispatch span
    recs = _turn([("engine.decode_dispatch", 4 * MS, int(4.3 * MS)),
                  ("engine.sync_wait", int(4.3 * MS), 92 * MS)])
    value, line = cover.cover(recs, ops, mods, SERVE, 100 * MS, 0.2)
    assert value == pytest.approx(100.0)
    assert line["clock"] == "same_origin"
    assert line["bracket_violation_us"] == 0
    # the step program ends at 56.32 ms, its wait closes at 92 ms
    assert line["clock_residual_us"] == pytest.approx(
        (56.322138 - 92.0) * 1e3)
    assert line["unnamed_idle_s"] == pytest.approx(0) \
        and line["caller_idle_s"] == pytest.approx(0)
    names = line["spans"]
    assert names["engine.sync_wait"]["idle_s"] \
        + names["engine.decode_dispatch"]["idle_s"] \
        == pytest.approx(line["interior_idle_s"])
    assert names["engine.turn"]["leaf"] is False \
        and names["engine.turn"]["self_s"] == pytest.approx(0)
    # interior idle + the profiler's edges = what trace_idle reads
    busy = tr.busy_union_ns(ops) / 1e9
    assert line["interior_idle_s"] + line["edge_idle_s"] \
        == pytest.approx(0.2 - busy)


def test_idle_splits_between_leaf_hole_and_caller(device):
    ops, mods = device
    # the gap 56.32 .. 60.31 ms between the two programs: 1 ms of it in
    # harvest, 1 ms in a hole of the turn's tiling, the rest after the turn
    recs = _turn([("engine.decode_dispatch", 4 * MS, int(4.5 * MS)),
                  ("engine.sync_wait", int(4.5 * MS), int(56.4 * MS)),
                  ("engine.harvest", int(56.4 * MS), int(57.4 * MS)),
                  ("engine.admit", int(58.4 * MS), int(58.4 * MS) + 1)])
    value, line = cover.cover(recs, ops, mods, SERVE, 100 * MS)
    gap = (55311851 - 51322138) / 1e9
    assert line["spans"]["engine.harvest"]["idle_s"] == pytest.approx(1e-3)
    assert line["unnamed_idle_s"] == pytest.approx(1e-3, rel=1e-3)
    assert line["spans"]["engine.turn"]["idle_s"] \
        == pytest.approx(line["unnamed_idle_s"])
    in_wait = line["spans"]["engine.sync_wait"]["idle_s"]
    assert line["caller_idle_s"] == pytest.approx(
        line["interior_idle_s"] - in_wait - 2e-3
        - line["spans"]["engine.decode_dispatch"]["idle_s"], abs=1e-9)
    assert line["caller_idle_s"] > gap - 2.2e-3
    assert 0 < value < 100
    assert line["edge_idle_s"] is None  # no window given


def test_spans_an_hour_off_read_none_through_the_bracket_test(device):
    ops, mods = device
    hour = 3600 * 1000 * MS
    recs = _turn([("engine.decode_dispatch", 4 * MS, int(4.5 * MS)),
                  ("engine.sync_wait", int(4.5 * MS), 57 * MS),
                  ("engine.harvest", 57 * MS, 95 * MS)], hour)
    value, line = cover.cover(recs, ops, mods, SERVE, 2 * hour)
    # an offset that satisfies both brackets exists (the hour), but it is
    # no alignment error of the profiler's: nothing, never a wrong share
    assert value is None and line["clock"] == "unaligned"
    assert line["bracket_violation_us"] > 3.5e9
    # within the tolerance the smallest shift that puts the program's start
    # at its launch's opening is fitted, and said so
    recs = _turn([("engine.decode_dispatch", int(5.7 * MS), 6 * MS),
                  ("engine.sync_wait", 6 * MS, 58 * MS),
                  ("engine.harvest", 58 * MS, 95 * MS)])
    value, line = cover.cover(recs, ops, mods, SERVE, 100 * MS)
    assert line["clock"] == "fitted" and value == pytest.approx(100.0)
    assert line["fitted_offset_us"] == pytest.approx(700.0)
    assert line["bracket_violation_us"] == pytest.approx(700.0)
    # a dispatch span that opens AFTER its program began under every
    # offset the wait allows: nothing, never a wrong share
    recs = _turn([("engine.decode_dispatch", 60 * MS, 61 * MS),
                  ("engine.sync_wait", 61 * MS, 62 * MS)], hour)
    value, line = cover.cover(recs, ops, mods, SERVE, 2 * hour)
    assert value is None and line["clock"] == "unaligned"
    # training has no end bracket to bound a fit: an hour off is nothing,
    # a program seen 0.2 ms before its launch opened is moved by that much
    train = {"prefix": "train.", "dispatch": "train.dispatch",
             "module_pattern": "^jit_step_fn$"}
    recs = [("train.dispatch", hour, hour + MS, 1, 2, None, None),
            ("train.epoch", hour, hour + 90 * MS, 0, 1, None, None)]
    value, line = cover.cover(recs, ops, mods, train, 2 * hour)
    assert value is None and line["clock"] == "unaligned"
    recs = [("train.dispatch", 5 * MS + 200_000, 6 * MS, 1, 2, None, None),
            ("train.epoch", 4 * MS, 95 * MS, 0, 1, None, None)]
    value, line = cover.cover(recs, ops, mods, train, 100 * MS)
    assert line["clock"] == "fitted" and value is not None
    assert line["fitted_offset_us"] == pytest.approx(200.0)
    assert line["bracket_violation_us"] == pytest.approx(200.0)
    # and spans that pair with no execution at all
    recs = _turn([("engine.harvest", MS, 2 * MS)])
    value, line = cover.cover(recs, ops, mods, SERVE, 100 * MS)
    assert value is None and "do not pair up" in line["why"]


def _instants(n, gap_ns):
    out = []
    for i in range(n):
        out.append(("req.submitted", 1000 * i, 1000 * i, 0, 2 * i + 1,
                    f"r{i}", None))
        out.append(("req.admitted", 1000 * i + gap_ns * (i + 1),
                    1000 * i + gap_ns * (i + 1), 0, 2 * i + 2, f"r{i}",
                    None))
    return out


def test_span_stat_pairs_percentiles_and_sums():
    pair = {"from": "req.submitted", "to": "req.admitted", "stat": "p95",
            "scale": 1e-6}
    assert span_stat.stat_of(_instants(9, MS), pair, 0, 10**12) is None
    got = span_stat.stat_of(_instants(10, MS), pair, 0, 10**12)
    assert got == pytest.approx(9.55)  # p95 of 1..10 ms
    # only the ``to`` has to lie in the stretch; a key's second ``to``
    # without a fresh ``from`` is no sample
    recs = _instants(10, MS) + [("req.admitted", 50 * MS, 50 * MS, 0, 99,
                                 "r0", None)]
    assert len(span_stat.gaps_ns(recs, "req.submitted", "req.admitted",
                                 5 * MS, 10**12)) == 6
    spans = [("train.feed", 10 * i, 10 * i + 4, 1, 2 * i + 2, None, None)
             for i in range(5)] \
        + [("train.dispatch", 10 * i + 4, 10 * i + 6, 1, 2 * i + 3, None,
            None) for i in range(5)]
    per = {"span": "train.feed", "stat": "sum", "per": "train.dispatch"}
    assert span_stat.stat_of(spans, per, 0, 100) == pytest.approx(4.0)
    # the fifth pair straddles the stretch's end: out of both counts
    assert span_stat.stat_of(spans, per, 0, 43) == pytest.approx(4.0)
    assert span_stat.stat_of(spans, {"span": "train.feed", "stat": "mean"},
                             0, 100) == pytest.approx(4.0)
    assert span_stat.stat_of([], per, 0, 100) is None
    assert span_stat.stat_of(spans, {"span": "nope", "stat": "sum"},
                             0, 100) is None


def _reset(t):
    return ("engine.stats_reset", t, t, 0, 10**6 + t, None, None)


def test_the_stretch_is_the_whole_window_bounded_by_the_ring_alone():
    S = 1000 * MS
    # a span bounds its own stretch: the newest one
    epochs = [("train.epoch", 0, 5, 0, 1, None, None),
              ("train.epoch", 7, 12, 0, 2, None, None)]
    assert span_stat.stretch(epochs, "train.epoch", {}) == (7, 12)
    assert span_stat.stretch(epochs, "nope", {}) is None
    # an instant opens the window, which runs for window_s: the only reset
    # of an untraced run, the last but one where the traced stretch that
    # follows the window opened with another
    run = {"window_s": 45.0, "traced": {}}
    assert span_stat.stretch([_reset(S)], "engine.stats_reset", run) \
        == (S, 46 * S)
    traced = {"window_s": 45.0, "traced": {"turns": 38}}
    assert span_stat.stretch([_reset(S), _reset(46 * S + 5)],
                             "engine.stats_reset", traced) == (S, 46 * S)
    # the ring has lost the window's opening: nothing, not the wrong window
    assert span_stat.stretch([_reset(46 * S + 5)], "engine.stats_reset",
                             traced) is None


def test_read_takes_every_request_of_the_window_and_needs_no_trace(
        monkeypatch, capsys):
    S = 1000 * MS
    # 280 requests over a 45 s window, 18 more in the 3 s traced stretch
    # after it with ten times the wait: the metric reads the window's
    recs = [_reset(S)]
    for i in range(298):
        t = S + i * 160 * MS
        wait = (1 + i % 10) * MS * (10 if t > 46 * S else 1)
        recs += [("req.submitted", t, t, 0, 2 * i + 1, i, None),
                 ("req.admitted", t + wait, t + wait, 0, 2 * i + 2, i, None)]
    recs.append(_reset(46 * S + 1))
    monkeypatch.setattr(span_stat, "program_records",
                        lambda *a: sorted(recs, key=lambda r: r[1]))
    spec = {"name": "queue_wait_p95_ms", "unit": "ms", "params": {
        "from": "req.submitted", "to": "req.admitted", "stat": "p95",
        "scale": 1e-6, "stretch": "engine.stats_reset"}}
    run = {"window_s": 45.0, "traced": {"turns": 38}}  # and no "trace"
    assert span_stat.read(spec, run) == pytest.approx(10.0)
    line = json.loads(capsys.readouterr().out)
    assert line["line"] == "span_stat" and line["n"] == 282
    assert line["stretch_s"] == pytest.approx(45.0)
    assert 5.0 <= line["p50"] <= 5.5
    monkeypatch.setattr(span_stat, "program_records", lambda *a: None)
    assert span_stat.read(spec, run) is None  # a program without the ring


def test_session_bounds_are_on_time_ns_clock(tmp_path):
    """A trace made here carries its start and stop in ``time.time_ns()``'s
    domain, and the reader's glue finds them; without device planes the
    idle reader says nothing."""
    import jax
    import jax.numpy as jnp

    from benchmark import harness
    from rafiki_tpu.obs import SPANS

    tracer = harness.Tracer(str(tmp_path / "trace"))
    before = time.time_ns()
    tracer.start()
    with SPANS.span("train.feed"):
        jnp.ones((64, 64)).block_until_ready()
    with SPANS.span("train.dispatch"):
        jax.block_until_ready(jnp.ones((64, 64)) @ jnp.ones((64, 64)))
    tracer.stop()
    after = time.time_ns()
    start, stop = cover.session_bounds(tr.find_xplane(tracer.trace_dir))
    assert before <= start < stop <= after
    inside = SPANS.snapshot(start, stop)
    assert [r[0] for r in inside][-2:] == ["train.feed", "train.dispatch"]
    assert span_stat.stat_of(
        inside, {"span": "train.feed", "stat": "sum",
                 "per": "train.dispatch", "scale": 1e-6}, start, stop) > 0
    summary = tr.TraceSummary.from_dir(tracer.trace_dir, tracer.window_s)
    assert cover.read({"name": "x", "params": {}},
                      {"trace": summary}) is None
