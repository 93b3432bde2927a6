"""trace_reduce on a small recorded trace: the busy union and a kernel's
summed time. ``data/trace_events.json`` holds the first events of the
device lines of a real trace of the serving cell (PR 27), names kept."""

import json
import os

from benchmark import trace_reduce as tr

DATA = os.path.join(os.path.dirname(__file__), "data", "trace_events.json")


def test_busy_union_by_hand():
    ev = [("a", 0, 10), ("b", 5, 10), ("c", 30, 5), ("d", 31, 1),
          ("e", 50, 0)]
    assert tr.busy_union_ns(ev) == 15 + 5


def test_attribution_and_kernel_sum_by_hand():
    mods = [("jit_step_fn(1)", 0, 100), ("jit_prefill_fn(2)", 200, 50)]
    ops = [("%fusion.1 = f32[] fusion()", 1, 10),
           ('%custom-call.3 = bf16[] custom-call(), custom_call_target='
            '"tpu_custom_call"', 20, 7),
           ('%custom-call.3 = bf16[] custom-call(), custom_call_target='
            '"tpu_custom_call"', 40, 8),
           ('%custom-call.9 = bf16[] custom-call(), custom_call_target='
            '"tpu_custom_call"', 210, 5),
           ("%copy.2 = f32[] copy()", 160, 3)]
    assert tr.kernel_time_ns(ops, mods, "^jit_step_fn$",
                             "tpu_custom_call") == (15.0, 2)
    assert tr.kernel_time_ns(ops, mods, "^jit_prefill_fn$",
                             "tpu_custom_call") == (5.0, 1)
    top = dict(map(tuple, tr.top_device_ops(ops, mods)))
    assert top["jit_step_fn/custom-call:tpu_custom_call"] == 15e-9
    assert top["?/copy"] == 3e-9
    gaps = dict(map(tuple, tr.longest_gaps(ops, mods)))
    assert gaps == {"before:jit_prefill_fn": 100e-9}


def test_program_mfu_by_hand():
    """Two executions of 10 ms and 30 ms that ingested 40 tokens a call at
    1e9 operations a token, on a chip of 1e13 a second: 4e10 / 0.02 / 1e13."""
    from benchmark import costs
    from benchmark.readers import trace_program_mfu

    mods = [("jit_prefill_fn(7)", 0, 10e6), ("jit_step_fn(1)", 10e6, 50e6),
            ("jit_prefill_fn(7)", 60e6, 30e6)]
    run = {"trace": tr.TraceSummary({0: {tr.MODULES_LINE: mods}}, 0.1),
           "peaks": {"bf16_flops_per_s": 1e13}, "config": {},
           "traced": {"stats": {"prefill_tokens": 120, "prefill_calls": 3}}}
    spec = {"params": {"module_pattern": "^jit_prefill_fn$",
                       "work_counter": "prefill_tokens",
                       "calls_counter": "prefill_calls",
                       "cost_function": "_per_token_for_test"}}
    costs._per_token_for_test = lambda cfg: 1e9
    try:
        assert abs(trace_program_mfu.read(spec, run) - 20.0) < 1e-9
        run["traced"]["stats"]["prefill_calls"] = 0  # nothing to read:
        assert trace_program_mfu.read(spec, run) is None  # never 0
    finally:
        del costs._per_token_for_test


def test_recorded_trace():
    rec = json.load(open(DATA))
    lines = {0: {k: [tuple(e) for e in v] for k, v in rec["lines"].items()}}
    s = tr.TraceSummary(lines, rec["window_s"])
    assert abs(s.busy_s() - rec["expect"]["busy_s"]) < 1e-12
    t, n = tr.kernel_time_ns(s.ops(0), s.modules(0), "^jit_step_fn$",
                             "tpu_custom_call")
    assert n == rec["expect"]["step_kernel_events"]
    assert abs(t - rec["expect"]["step_kernel_ns"]) < 1e-6
    assert len(s.breakdown()["device_ops"]) <= 10
