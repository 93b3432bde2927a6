"""``--rehearse`` runs of both tiny configurations end to end on the CPU:
the whole command, as a child process; a run off the chip can never say
``"correct": true``, and without ``--rehearse`` it prints no result."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _run(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="ignored")
    return subprocess.run(
        [sys.executable, "benchmark/run.py", *args], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=600)


@pytest.mark.parametrize("cell,trace,metric", [
    ("tiny-lm.tiny-chat", "0", "serve_tokens_per_s"),
    ("tiny-lm.tiny-chat", "1", "decode_batch_occupancy"),
    ("tiny-vit.tiny-images", "0", "train_samples_per_s"),
    ("tiny-vit.tiny-images", "1", "train_mfu"),
])
def test_rehearsal_end_to_end(cell, trace, metric):
    p = _run("--workload", cell, "--seed", "2147483999", "--seconds", "2",
             "--trace", trace, "--rehearse")
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [json.loads(l) for l in p.stdout.splitlines() if l[:1] == "{"]
    last = lines[-1]
    assert list(last)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"] and list(last)[-1] == "checks"
    assert last["correct"] is False and last["device"]["platform"] == "cpu"
    assert last["attempted"] > 0 and last["failed"] == 0
    kinds = {l.get("line") for l in lines[:-1]}
    assert {"setup_phases", "compile_cache", "counters", "window"} <= kinds
    cache = next(l for l in lines if l.get("line") == "compile_cache")
    assert cache["compilations_in_window"] == 0
    if trace == "0":
        assert metric in last["metrics"] and "setup_s" in last["metrics"]
    elif metric != "train_mfu":  # no peaks off the chip: mfu says nothing
        assert metric in last["metrics"]
    sound = {k: v for k, v in last["checks"].items()
             if k != "platform_is_tpu"}
    for name, c in sound.items():
        if isinstance(c["limit"], (int, float)) and not isinstance(
                c["limit"], bool) and isinstance(c["value"], (int, float)):
            assert c["value"] <= c["limit"] or name.endswith("_count"), name
    assert p.stderr.strip().splitlines()[-1].startswith("check ")


def test_no_result_without_a_chip():
    p = _run("--workload", "mistral-7b.chat-decode-c32", "--seed", "1",
             "--seconds", "1", "--trace", "0")
    assert p.returncode == 3 and p.stdout.strip() == ""
