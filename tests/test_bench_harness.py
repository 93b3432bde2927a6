"""Bench harness: the deadline parent kills and reaps an overdue
child."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from _bench_common import run_child  # noqa: E402


def test_overdue_child_is_killed_and_reaped(tmp_path):
    script = tmp_path / "fake_bench.py"
    script.write_text("import time, sys; time.sleep(20)")
    proc = run_child(str(script), str(tmp_path / "o.jsonl"), budget=1.0,
                     env=dict(os.environ))
    assert proc.poll() is not None  # killed and reaped
