"""What decides ``correct`` has been shown to fail (at a size a test run can
hold; PERF.md has the chip's readings at the cells' own sizes).

The control: the reference in the nearest precision below the
configuration's (``control_precision`` in its file: bfloat16 for these
float32 rehearsal configurations, int8 or fp8 for the cells' bfloat16) is
put in the program's place through the run's own checks, as ``run.py
--control`` does, and the run comes out not correct. The faults: the
rest of a run driven in this process with the timed path broken underneath
— a token altered where it is produced; a step that returns its state
unchanged; half of the batch left out, the mean taken over the rest — and
``correct`` comes out false each time."""

import jax.numpy as jnp
import pytest

from benchmark import harness
from benchmark.drivers import serve_engine, train_template


def _ctx(cell_name, seed, seconds, work_dir, control=None):
    cell = harness.REHEARSAL_CELLS[cell_name]
    return dict(
        cell=cell, seed=seed, seconds=seconds, rehearse=True, tracer=None,
        config=harness.load_json("configs", f"{cell['config']}.json"),
        traffic=harness.load_json("traffic", f"{cell['traffic']}.json"),
        phases=harness.Phases(0.0), monitor=_NoMonitor(),
        work_dir=str(work_dir), peaks=None, control=control)


class _NoMonitor:
    in_window = 0

    def fence(self): pass
    def unfence(self): pass
    def report(self): return {}


def _correct(run):
    return all(c["ok"] for c in run["checks"])


# ------------------------------------------------------------- serving
def test_serving_sound_run_is_correct(tmp_path):
    assert _correct(serve_engine.run(_ctx("tiny-lm.tiny-chat", 21, 1.0,
                                          tmp_path)))


def test_serving_altered_token_is_not_correct(tmp_path, monkeypatch):
    real = serve_engine.build_engine

    def broken(cfg, seed, phases):
        module, core, params, abstract = real(cfg, seed, phases)
        step = core._step_fns[False]

        def altered(*a):
            cache, emitted = step(*a)
            # lane 0's tokens, altered where they are produced
            return cache, emitted.at[:, 0].set(
                (emitted[:, 0] + 1) % cfg["vocab_size"])

        core._step_fns[False] = altered
        return module, core, params, abstract

    monkeypatch.setattr(serve_engine, "build_engine", broken)
    run = serve_engine.run(_ctx("tiny-lm.tiny-chat", 21, 1.0, tmp_path))
    assert not _correct(run)
    bad = {c["name"] for c in run["checks"] if not c["ok"]}
    assert bad == {"served_token_logit_gap"}


@pytest.mark.parametrize("seed", [31, 32, 33])
def test_serving_control_is_not_correct(seed, tmp_path):
    ctx = _ctx("tiny-lm.tiny-chat", seed, 1.0, tmp_path)
    ctx["control"] = ctx["config"]["control_precision"]
    # every request the window finished, ~900 tokens: of 8 requests (~350
    # tokens) the tiny model's control sometimes moves no token at all and
    # reads 0.0002, depending on which requests the host's speed let finish
    # (24 compared, 12 seeds: 0.0038-0.015; the cell compares 1,300-1,900)
    ctx["traffic"]["check_requests"] = 24
    run = serve_engine.run(ctx)
    assert not _correct(run)
    bad = {c["name"]: c for c in run["checks"] if not c["ok"]}
    assert set(bad) == {"served_token_logit_gap"}
    gap = bad["served_token_logit_gap"]
    assert gap["value"] > 3 * gap["limit"]


# ------------------------------------------------------------ training
def _train_checks(tmp_path, monkeypatch, breaker=None):
    import rafiki_tpu.models.vit as vit

    if breaker is not None:
        real = vit.train_epoch

        def faulty(step, state, batches, **kw):
            return real(breaker(step), state, batches, **kw)

        monkeypatch.setattr(vit, "train_epoch", faulty)
    run = train_template.run(_ctx("tiny-vit.tiny-images", 41, 0.5,
                                  tmp_path))
    return run, {c["name"] for c in run["checks"] if not c["ok"]}


def test_training_sound_run_is_correct(tmp_path, monkeypatch):
    run, bad = _train_checks(tmp_path, monkeypatch)
    assert _correct(run), bad


def test_training_state_unchanged_is_not_correct(tmp_path, monkeypatch):
    import rafiki_tpu.models.vit as vit

    # underneath the step the driver watches: the update is worked out
    # and dropped, the parameters come back as they went in
    monkeypatch.setattr(vit.optax, "apply_updates", lambda p, u: p)
    run, bad = _train_checks(tmp_path, monkeypatch)
    got = {c["name"]: c["value"] for c in run["checks"]}
    # a state left unchanged reads 1 by the measure of norms
    assert abs(got["param_change_norm_gap"] - 1.0) < 1e-6
    assert {"step_loss_gap", "param_change_norm_gap"} <= bad


def test_training_half_batch_is_not_correct(tmp_path, monkeypatch):
    def breaker(step):
        def half(st, b):
            m = jnp.asarray(b["m"]).at[b["m"].shape[0] // 2:].set(0.0)
            return step(st, {**b, "m": m})
        return half

    run, bad = _train_checks(tmp_path, monkeypatch, breaker)
    assert not _correct(run) and bad


@pytest.mark.parametrize("seed", [51, 52, 53])
def test_training_control_is_not_correct(seed, tmp_path):
    ctx = _ctx("tiny-vit.tiny-images", seed, 0.5, tmp_path)
    ctx["control"] = ctx["config"]["control_precision"]
    run = train_template.run(ctx)
    assert not _correct(run)
    assert {c["name"] for c in run["checks"] if not c["ok"]} \
        <= set(train_template.COMPARED)


@pytest.mark.parametrize("seed", [54])
def test_training_half_batch_reference_reads_past_a_limit(seed, tmp_path):
    ctx = _ctx("tiny-vit.tiny-images", seed, 0.0, tmp_path)
    lines = []
    orig = harness.emit
    try:
        harness.emit = lambda kind, **f: lines.append((kind, f))
        train_template.calibrate(ctx, [seed], ctx["config"]["control_precision"])
    finally:
        harness.emit = orig
    got = dict(lines)["calibrate"]
    limits = ctx["config"]["limits"]
    assert all(got["program"][k] <= limits[k] for k in limits)
    assert any(got["half_batch"][k] > limits[k] for k in limits)
