"""``span_idle_per_turn`` on synthetic engine spans laid over the recorded
device events of ``data/trace_events.json`` (PR 27's serving cell, moved 5 ms
into the session as ``test_span_readers.py`` does: one ``jit_step_fn``
execution 5 .. 56.322 ms, one ``jit_prefill_fn`` 60.312 .. 88.359 ms)."""

import json
import os

import pytest

from benchmark import harness
from benchmark import trace_reduce as tr
from benchmark.readers import span_idle_cover as cover
from benchmark.readers import span_idle_per_turn as reader

DATA = os.path.join(os.path.dirname(__file__), "data", "trace_events.json")
MS = 1_000_000
STEP_END = 56_322_138
PREFILL_START = 60_311_851
PARAMS = harness.load_json("metrics", "host_exposed_ms_per_turn.json")[
    "params"]


@pytest.fixture(scope="module")
def device():
    rec = json.load(open(DATA))
    lines = {k: [(n, s + 5 * MS, d) for n, s, d in v]
             for k, v in rec["lines"].items()}
    return lines[tr.OPS_LINE], lines[tr.MODULES_LINE]


def _records(tree, shift=0):
    """``tree`` = [(name, t0_ms, t1_ms, [children])] -> ring records, each
    child under its parent, as ``SpanRing`` keeps them."""
    out, seq = [], [0]

    def add(node, parent):
        name, t0, t1, kids = node
        seq[0] += 1
        me = seq[0]
        out.append((name, int(t0 * MS) + shift, int(t1 * MS) + shift,
                    parent, me, None, None))
        for kid in kids:
            add(kid, me)

    for node in tree:
        add(node, 0)
    return out


def _turn(dispatch_open=4.0, ready_close=56.4, prefill_open=59.9):
    """One turn tiled as the engine tiles it (each launch 0.5 / 0.3 ms from
    its opening), with a hole from the prefill's launch to 89 ms (the
    turn's own tail) and the caller's poll after it."""
    launched = dispatch_open + 0.5
    return [
        ("engine.turn", 3.0, 89.0, [
            ("engine.admit", 3.0, 3.5, []),
            ("engine.decode_prep", 3.5, dispatch_open, [
                ("engine.plan", 3.5, 3.7, []),
                ("engine.upload", 3.7, dispatch_open, [])]),
            ("engine.decode_dispatch", dispatch_open, launched, []),
            ("engine.sync_wait", launched, 57.0, [
                ("engine.sync_ready", launched, ready_close, []),
                ("engine.sync_pull", ready_close, 57.0, [])]),
            ("engine.harvest", 57.0, 58.0, []),
            ("engine.prefill_prep", 58.0, prefill_open, [
                ("engine.plan", 58.0, 59.0, []),
                ("engine.page_map", 59.0, 59.5, []),
                ("engine.upload", 59.5, prefill_open, [])]),
            ("engine.prefill_dispatch", prefill_open, prefill_open + 0.3,
             [])]),
        ("engine.poll", 94.0, 95.0, [])]


def test_idle_splits_by_leaf_and_sync_ready_is_left_out(device):
    ops, mods = device
    value, line = reader.per_turn(_records(_turn()), ops, mods, PARAMS,
                                  100 * MS)
    assert line["clock"] == "same_origin" and line["offset_us"] == 0
    # the device could move 0.41 ms earlier (the prefill's start to its
    # launch's opening: tighter than the step's 1 ms) or 77.9 us later
    # (the step's end to sync_ready's close)
    assert line["slack_us"] == pytest.approx(
        [(PREFILL_START - 59.9 * MS) / 1e3, 77.862])
    assert line["brackets"] == {"lower": 2, "upper": 1}
    assert line["turns"] == 1
    leaf = line["leaf_idle_ms_per_turn"]
    # before the first op (5.128 ms) every host leaf is wholly idle
    for name, ms in (("engine.admit", 0.5), ("engine.decode_dispatch", 0.5),
                     ("engine.harvest", 1.0), ("engine.page_map", 0.5),
                     ("engine.sync_pull", 0.6), ("engine.poll", 1.0)):
        assert leaf[name] == pytest.approx(ms), name
    assert leaf["engine.plan"] == pytest.approx(0.2 + 1.0)
    assert leaf["engine.upload"] == pytest.approx(0.3 + 0.4)
    assert leaf["engine.prefill_dispatch"] == pytest.approx(0.3, abs=1e-5)
    # the chip's own launch (4.5 .. 5.128) and its in-program gaps
    assert leaf["engine.sync_ready"] > 0.628
    # the leaves, the hole in the turn and the caller's part add up
    assert sum(leaf.values()) + line["unnamed_idle_ms_per_turn"] \
        + line["caller_idle_ms_per_turn"] \
        == pytest.approx(line["interior_idle_ms_per_turn"])
    # the hole: the prefill's launch to its program's first op, the
    # program's own gaps, its end to the turn's
    busy = cover.union([(s, s + d) for _n, s, d in ops if d > 0])
    hole = cover.complement(busy, 60.2 * MS, 89.0 * MS)
    assert line["unnamed_idle_ms_per_turn"] == pytest.approx(
        sum(b - a for a, b in hole) / MS)
    assert hole[0][0] == 60.2 * MS and hole[0][1] > PREFILL_START
    assert hole[-1] == (88_359_142, 89.0 * MS)
    assert line["caller_idle_ms_per_turn"] == pytest.approx(5.0)
    # the value: every leaf but the wait for the chip
    assert value == pytest.approx(
        sum(leaf.values()) - leaf["engine.sync_ready"])
    assert value == line["host_exposed_ms_per_turn"]


@pytest.mark.parametrize("offset_us", [400.0, -250.0])
def test_a_known_offset_is_recovered(device, offset_us):
    ops, mods = device
    # the dispatch opens exactly as the program starts and sync_ready
    # closes exactly as it ends: one offset fits, the one the device's
    # events were moved away from
    off = int(offset_us * 1e3)
    moved_ops = [(n, s - off, d) for n, s, d in ops]
    moved_mods = [(n, s - off, d) for n, s, d in mods]
    recs = _records(_turn(dispatch_open=5.0, ready_close=STEP_END / MS,
                          prefill_open=PREFILL_START / MS))
    value, line = reader.per_turn(recs, moved_ops, moved_mods, PARAMS,
                                  100 * MS)
    assert line["clock"] == "fitted"
    assert line["offset_us"] == pytest.approx(offset_us, abs=1e-3)
    assert line["slack_us"] == pytest.approx([0.0, 0.0], abs=1e-3)
    exact, _ = reader.per_turn(recs, ops, mods, PARAMS, 100 * MS)
    assert value == pytest.approx(exact)


def test_prefill_dispatches_tighten_the_fit(device):
    ops, mods = device
    # the prefill's launch opens 0.2 ms after its program is seen to start:
    # the device's events move 0.2 ms later, inside the decode brackets
    recs = _records(_turn(ready_close=56.9,
                          prefill_open=PREFILL_START / MS + 0.2))
    _value, line = reader.per_turn(recs, ops, mods, PARAMS, 100 * MS)
    assert line["clock"] == "fitted"
    assert line["offset_us"] == pytest.approx(200.0, abs=1e-3)


def test_unaligned_or_unpaired_clocks_read_none(device):
    ops, mods = device
    hour = 3600 * 1000 * MS
    # spans an hour off: only an hour's offset would fit
    value, line = reader.per_turn(_records(_turn(), hour), ops, mods,
                                  PARAMS, 2 * hour)
    assert value is None and line["clock"] == "unaligned"
    # sync_ready closes 2.02 ms before the program ends, so the device
    # would have to move 2.02 ms earlier, and the prefill's launch opened
    # only 0.41 ms before its program started: no offset fits both
    value, line = reader.per_turn(_records(_turn(ready_close=54.3)), ops,
                                  mods, PARAMS, 100 * MS)
    assert value is None and line["clock"] == "unaligned"
    assert line["bracket_gap_us"] == pytest.approx(
        2022.138 - (PREFILL_START - 59.9 * MS) / 1e3)
    # a program from before the split: no sync_ready spans to pair with
    parent = [r for r in _records(_turn())
              if r[0] not in ("engine.sync_ready", "engine.sync_pull")]
    value, line = reader.per_turn(parent, ops, mods, PARAMS, 100 * MS)
    assert value is None and "do not pair up" in line["why"]
    value, line = reader.per_turn([], ops, mods, PARAMS, 100 * MS)
    assert value is None


def test_read_without_a_trace_says_nothing():
    spec = harness.load_json("metrics", "host_exposed_ms_per_turn.json")
    assert reader.read(spec, {}) is None
