"""Stack lifecycle: graceful shutdown, restart adoption, CLI wiring.

VERDICT round-2 items #7/#8: SIGTERM on the admin must stop every child
(kvd data plane included) and leave MetaStore consistent; a restarted
admin must reap stale RUNNING rows; `--slot-size`/`--workers` must reach
the ServicesManager.
"""

import json
import os
import signal
import subprocess
import sys
import time
from pathlib import Path

import pytest

from rafiki_tpu.admin.services_manager import ServicesManager
from rafiki_tpu.parallel.mesh import DeviceSpec
from rafiki_tpu.store.meta_store import MetaStore
from rafiki_tpu.utils.http import json_request


def _pid_alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
        return True
    except (ProcessLookupError, PermissionError):
        return False


def _start_admin(work: Path, extra_cfg: dict) -> subprocess.Popen:
    cfg = {"workdir": str(work), "db_path": str(work / "meta.db"),
           "host": "127.0.0.1", "port": 0,
           "port_file": str(work / "admin.port"), **extra_cfg}
    (work / "admin.json").write_text(json.dumps(cfg))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    proc = subprocess.Popen(
        [sys.executable, "-m", "rafiki_tpu.admin.app", "--config",
         str(work / "admin.json")],
        stdout=open(work / "admin.log", "ab"), stderr=subprocess.STDOUT,
        env=env, start_new_session=True)
    deadline = time.monotonic() + 120
    port_file = work / "admin.port"
    while time.monotonic() < deadline:
        if port_file.exists() and port_file.read_text().strip():
            return proc
        assert proc.poll() is None, (work / "admin.log").read_text()[-2000:]
        time.sleep(0.1)
    proc.kill()
    raise AssertionError("admin did not come up")


@pytest.mark.slow
def test_sigterm_stops_children_and_metastore_consistent(tmp_path):
    proc = _start_admin(tmp_path, {"slot_size": 1})
    port = int((tmp_path / "admin.port").read_text())
    health = json_request("GET", f"http://127.0.0.1:{port}/health",
                          timeout=10)
    assert health["ok"]

    # the data plane (kvd) is a recorded child with a live pid
    meta = MetaStore(str(tmp_path / "meta.db"))
    rows = [r for r in meta.get_services()
            if r["status"] not in ("STOPPED", "ERRORED")]
    assert rows, "expected at least the data-plane service row"
    child_pids = [int(r["pid"]) for r in rows if int(r.get("pid") or 0)]
    assert child_pids and all(_pid_alive(p) for p in child_pids)

    os.kill(proc.pid, signal.SIGTERM)
    assert proc.wait(timeout=30) == 0

    for p in child_pids:
        for _ in range(50):
            if not _pid_alive(p):
                break
            time.sleep(0.1)
        assert not _pid_alive(p), f"orphaned child pid {p}"
    # every service row finalized
    meta2 = MetaStore(str(tmp_path / "meta.db"))
    for r in meta2.get_services():
        assert r["status"] in ("STOPPED", "ERRORED"), r


@pytest.mark.slow
def test_restart_reaps_stale_rows(tmp_path):
    proc = _start_admin(tmp_path, {"slot_size": 1})
    meta = MetaStore(str(tmp_path / "meta.db"))
    # SIGKILL: graceful shutdown never runs, rows stay RUNNING/STARTED
    os.kill(proc.pid, signal.SIGKILL)
    proc.wait(timeout=10)
    stale = [r for r in meta.get_services()
             if r["status"] not in ("STOPPED", "ERRORED")]
    assert stale, "SIGKILL should have left stale rows"

    (tmp_path / "admin.port").unlink()
    proc2 = _start_admin(tmp_path, {"slot_size": 1})
    try:
        meta2 = MetaStore(str(tmp_path / "meta.db"))
        for r in meta2.get_services():
            # stale rows reaped; only the new admin's children are live
            if r["status"] not in ("STOPPED", "ERRORED"):
                assert _pid_alive(int(r["pid"])), r
    finally:
        os.kill(proc2.pid, signal.SIGTERM)
        proc2.wait(timeout=30)


def test_slot_size_reaches_allocator():
    """--slot-size wiring: slot_size=2 over 8 devices -> 4 slots."""
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        meta = MetaStore(str(Path(d) / "meta.db"))
        mgr = ServicesManager(
            meta, d, slot_size=2, platform="cpu",
            devices=[DeviceSpec(id=i) for i in range(8)],
            default_workers=3)
        assert mgr.allocator.free_count() == 4
        assert mgr.default_workers == 3


def test_cli_stack_parser_has_slot_size_and_workers():
    from rafiki_tpu.cli import main as cli_main  # noqa: F401 — import ok
    import argparse

    from rafiki_tpu import cli

    parser = argparse.ArgumentParser()
    sub = parser.add_subparsers(dest="cmd")
    cli._register_service_commands(sub)
    args = parser.parse_args(["stack", "status", "--slot-size", "2",
                              "--workers", "3"])
    assert args.slot_size == 2 and args.workers == 3


def test_unknown_platform_env_warns():
    """A platform nothing can confine a child on is refused outright:
    its children would share every device."""
    from rafiki_tpu.parallel.mesh import (SubMeshAllocator,
                                          submesh_env_vars)

    alloc = SubMeshAllocator([DeviceSpec(id=0), DeviceSpec(id=1)], 1)
    slot = alloc.acquire()
    with pytest.raises(ValueError, match="confinement"):
        submesh_env_vars("gpu", slot)


def test_train_job_rejects_unknown_dataset(tmp_path):
    from rafiki_tpu.admin.admin import Admin

    meta = MetaStore(str(tmp_path / "meta.db"))
    mgr = ServicesManager(meta, str(tmp_path), slot_size=1, platform="cpu",
                          devices=[DeviceSpec(id=0)])
    admin = Admin(meta, mgr)
    user = meta.get_user_by_email("superadmin@rafiki")
    with pytest.raises(ValueError, match="neither a registered dataset"):
        admin.create_train_job(user["id"], "app", "IMAGE_CLASSIFICATION",
                               "nonexistent-id", "also-nonexistent",
                               {"TRIAL_COUNT": 1})
