"""ServicesManager: spawn/track service processes on TPU sub-meshes.

Parity target: the reference's ``ServicesManager`` + ``ContainerManager``
pair (SURVEY.md §2 "Admin"/"Container manager", §3.1/§3.2): the control
plane spawns an advisor plus N train workers per train job, and a predictor
plus N inference workers per inference job. The rebuild replaces "Docker
service with one GPU" by "host process pinned to an ICI-contiguous TPU
sub-mesh" via env vars (``TPU_VISIBLE_CHIPS`` et al., SURVEY.md §7):

- Topology discovery runs in a throwaway probe subprocess so the manager
  never holds the chips itself (``device_probe.py``).
- A :class:`SubMeshAllocator` hands each worker a slot; the slot's env
  vars confine the child's JAX runtime to those chips.
- Service rows land in the MetaStore exactly as the reference records its
  Docker services; ``poll()`` is the failure detector (SURVEY.md §5.3).
- The data plane (param blobs + query queues) is one ``rafiki-kvd``
  process per stack (the Redis container equivalent, SURVEY.md §5.8(b)).

Crash-only control plane (the orchestrator-recovery duty of
arXiv:1804.06087, which Docker Swarm carried for the reference): every
spawn persists its FULL recipe (``spawn_spec``) and the child's kernel
start time into the service row, so the row — not this object's dicts —
is the source of truth. A restarted admin calls :meth:`reconcile` to
re-ADOPT surviving children (identity-checked pid + health probe, slots
re-reserved), crash-and-respawn the dead ones under the durable respawn
budget, and reap orphans whose job was stopped meanwhile. A
single-writer lease row (generation-fenced) keeps a stale or duplicate
admin from spawning a second stack on chips the first still holds.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time
import uuid
from pathlib import Path
from typing import Any, Dict, List, Optional

from ..constants import (ServiceStatus, ServiceType, SubTrainJobStatus,
                         TaskType, TrainJobStatus)
from ..parallel.mesh import DeviceSpec, SubMesh, SubMeshAllocator, \
    submesh_env_vars
from ..store.meta_store import MetaStore
from .autoscaler import AutoscaleConfig, AutoscalePolicy
from .proc import (AdoptedProcess, identity_matches, proc_start_time,
                   terminate_pid)

#: service rows in these states are settled history — never adopted,
#: respawned, or reaped again
_TERMINAL = (ServiceStatus.STOPPED, ServiceStatus.ERRORED,
             ServiceStatus.CRASHED)

#: worker service types eligible for self-healing respawn
_WORKER_TYPES = (ServiceType.TRAIN_WORKER, ServiceType.INFERENCE_WORKER)


class LeaseHeldError(RuntimeError):
    """Another live admin holds the single-writer lease for this
    MetaStore — booting a second control plane would double-spawn the
    stack. Carries the holder/generation for a structured error."""

    def __init__(self, lease: Dict[str, Any]) -> None:
        self.lease = dict(lease)
        age = time.time() - float(lease.get("heartbeat_at") or 0)
        super().__init__(
            f"admin lease held by {lease.get('holder', '?')[:12]} "
            f"(generation {lease.get('generation')}, heartbeat "
            f"{age:.1f}s ago) — a live admin owns this MetaStore; "
            "stop it first or wait for its lease to expire")


class AdminFencedError(RuntimeError):
    """This manager LOST the lease (a newer admin took over): every
    mutating operation is refused so the two control planes cannot
    fight over the same processes and chips."""


class ManagedService:
    """One spawned child process + its MetaStore row + its device slot."""

    def __init__(self, service_id: str, service_type: str,
                 proc: subprocess.Popen, slot: Optional[SubMesh] = None,
                 host: str = "", port: int = 0,
                 adopted: bool = False) -> None:
        self.service_id = service_id
        self.service_type = service_type
        self.proc = proc
        self.slot = slot
        self.host = host
        self.port = port
        #: True when this handle was rebuilt around a surviving pid by
        #: the boot reconciler rather than spawned by this manager
        self.adopted = adopted

    @property
    def url(self) -> str:
        return f"http://{self.host}:{self.port}"

    def alive(self) -> bool:
        return self.proc.poll() is None




def probe_devices(timeout: float = 120.0) -> Dict[str, Any]:
    """Run the device probe subprocess; returns {platform, devices}."""
    out = subprocess.run(
        [sys.executable, "-m", "rafiki_tpu.admin.device_probe"],
        capture_output=True, text=True, timeout=timeout, check=True,
        env=os.environ.copy())
    return json.loads(out.stdout.strip().splitlines()[-1])


class ServicesManager:
    def __init__(self, meta_store: MetaStore, workdir: str,
                 slot_size: int = 1, platform: Optional[str] = None,
                 devices: Optional[List[DeviceSpec]] = None,
                 slot_timeout: float = 30.0,
                 default_workers: int = 1) -> None:
        self.meta = meta_store
        self.slot_timeout = slot_timeout
        #: train workers per job when the budget names no WORKER_COUNT /
        #: GPU_COUNT (the CLI's --workers)
        self.default_workers = max(1, int(default_workers))
        self.workdir = Path(workdir)
        self.workdir.mkdir(parents=True, exist_ok=True)
        if devices is None:
            inv = probe_devices()
            platform = platform or inv["platform"]
            devices = [DeviceSpec.from_probe(d) for d in inv["devices"]]
        self.platform = platform or "cpu"
        self.devices = devices
        self.allocator = SubMeshAllocator(devices, slot_size)
        #: serializes spawn/stop/poll across the admin + monitor threads
        #: (e.g. the monitor must not reap an advisor between its spawn and
        #: its workers' spawn)
        self.op_lock = threading.RLock()
        self.services: Dict[str, ManagedService] = {}
        self.kv_host: str = ""
        self.kv_port: int = 0
        self._kv_proc: Optional[subprocess.Popen] = None
        self._kv_server: Any = None
        #: self-healing: spawn spec per live service so a CRASHED worker
        #: (train or inference) can be respawned while its parent job is
        #: still RUNNING. Lineage = (type, job id): the restart budget is
        #: shared by a job's workers so a crash-looping config converges.
        self._respawn_specs: Dict[str, Dict[str, Any]] = {}
        #: in-memory mirror of the DURABLE respawn_budgets table — the
        #: store is authoritative (increments write through), so the
        #: budget survives an admin crash/restart
        self._respawn_counts: Dict[Any, int] = \
            self._load_respawn_counts()
        #: max replacement spawns per (service type, job) lineage
        self.max_respawns = 3
        #: respawns that found no free slot, retried on every poll —
        #: without this, a single-worker job whose only slot got snatched
        #: between release and re-acquire would lose healing forever
        self._pending_respawns: List[Dict[str, Any]] = []
        #: jobs whose self-healing is exhausted or lost (respawn budget
        #: spent, queued respawn dropped): job id → reason. Surfaced on
        #: the admin /health so a job quietly running under-replicated
        #: (or not at all) is visible, not just a log line.
        self._degraded: Dict[str, str] = {}
        #: completed drain→stop→respawn cycles (rolling_restart)
        self._rolling_restarts = 0
        #: one rolling restart at a time: a concurrent second call (an
        #: operator retrying a timed-out request) would drain the fresh
        #: replacements and spawn duplicates sharing one worker id
        self._rolling_lock = threading.Lock()
        #: single-writer admin lease (generation-fenced). Opt-in:
        #: acquire_lease() arms it; a manager that never acquires (unit
        #: tests, embedded use) is never fenced.
        self.lease_holder = uuid.uuid4().hex
        self.lease_generation = 0
        self.lease_ttl_s = 15.0
        self._lease_held = False
        self.fenced = False
        #: boot-reconciler outcome counters, surfaced on the admin
        #: /metrics (services_adopted / orphans_reaped / ...) and in
        #: the /health recovery block + dashboard banner
        from ..obs.metrics import StatsMap

        self.recovery = StatsMap({
            "services_adopted": 0, "services_crashed": 0,
            "orphans_reaped": 0, "respawns_queued": 0,
            "kv_adopted": 0, "kvd_respawns": 0,
            "kvd_replay_seconds": 0.0, "lease_takeovers": 0,
            "last_recovery_at": 0.0})
        #: kvd persistence: where the WAL + snapshot live (recorded in
        #: the spawn spec so a restarted admin respawns WITH replay)
        self._kv_data_dir: str = ""
        #: cached kvd STATS (scrapes must not open a socket per hit);
        #: guarded by its own lock — never op_lock, a scrape must not
        #: contend with a slow spawn
        self._kvd_stats_cache: Dict[str, Any] = {}
        self._kvd_stats_at = 0.0
        self._kvd_stats_lock = threading.Lock()
        #: consecutive failed kvd boot attempts (one per monitor tick)
        self._kv_boot_attempts = 0
        #: horizontal scale-out state per inference job: routing pool,
        #: spawn template for extra replicas, autoscale policy (when
        #: the budget armed one), warming/draining workers in flight.
        #: Rebuilt lazily from live services + the job budget after an
        #: admin restart (_ensure_scaleout), so adoption keeps scaling.
        self._scaleout: Dict[str, Dict[str, Any]] = {}
        self._last_autoscale_tick = 0.0
        self._pool_hub_cache: Any = None
        self._pool_hub_key: Any = None
        #: autoscaler action counters, surfaced on admin /metrics
        self.scaling = StatsMap({
            "autoscale_ups": 0, "autoscale_downs": 0,
            "autoscale_blocked": 0, "pool_publishes": 0})

    def _load_respawn_counts(self) -> Dict[Any, int]:
        """Durable lineage budgets → the (type, job_id)-keyed mirror."""
        out: Dict[Any, int] = {}
        try:
            for lineage, count in self.meta.get_respawn_counts().items():
                stype, _, job_id = lineage.partition(":")
                out[(stype, job_id)] = int(count)
        except Exception:  # noqa: BLE001 — a pre-migration store must
            # not break boot; budgets then start fresh (old behavior)
            import logging

            logging.getLogger(__name__).warning(
                "could not load durable respawn budgets", exc_info=True)
        return out

    # ---- admin lease (single-writer fencing) ----
    def acquire_lease(self, ttl_s: Optional[float] = None
                      ) -> Dict[str, Any]:
        """Claim the MetaStore's single-writer admin lease, or raise
        :class:`LeaseHeldError` when a live admin already owns it. A
        takeover of an EXPIRED lease bumps the generation (counted as
        ``lease_takeovers``) — the old holder's next renew fails and
        fences it out."""
        if ttl_s is not None:
            self.lease_ttl_s = float(ttl_s)
        got = self.meta.acquire_admin_lease(self.lease_holder,
                                            ttl_s=self.lease_ttl_s)
        if got is None:
            raise LeaseHeldError(self.meta.get_admin_lease() or {})
        self._lease_held = True
        self.fenced = False
        self.lease_generation = int(got["generation"])
        if got.get("took_over"):
            self.recovery.inc("lease_takeovers")
        return got

    def start_lease_heartbeat(self,
                              interval_s: Optional[float] = None) -> None:
        """Start the background lease-renewal thread (idempotent).

        Call IMMEDIATELY after :meth:`acquire_lease` — before
        :meth:`reconcile`: reconciling can legitimately exceed the TTL
        (per-orphan SIGTERM/SIGKILL grace, health probes), and with no
        heartbeat a concurrent boot would "take over" from a live admin
        mid-reconcile. The thread is deliberately independent of the
        admin's monitor loop: it never touches op_lock, so a blocking
        spawn cannot starve it. It exits on release/fence."""
        if getattr(self, "_hb_thread", None) is not None and \
                self._hb_thread.is_alive():
            return
        if not self._lease_held:
            return
        tick = interval_s if interval_s is not None else \
            max(0.2, min(self.lease_ttl_s / 3.0, 5.0))
        self._hb_stop = threading.Event()

        def loop() -> None:
            while not self._hb_stop.wait(tick):
                try:
                    if not self.renew_lease():
                        return  # fenced: nothing left to renew
                except Exception:  # a store hiccup must not kill the
                    # heartbeat — the next tick retries
                    import logging

                    logging.getLogger(__name__).warning(
                        "lease heartbeat failed", exc_info=True)

        self._hb_thread = threading.Thread(target=loop, daemon=True)
        self._hb_thread.start()

    def _stop_lease_heartbeat(self) -> None:
        stop = getattr(self, "_hb_stop", None)
        if stop is not None:
            stop.set()
        th = getattr(self, "_hb_thread", None)
        if th is not None and th.is_alive():
            th.join(timeout=5)
        self._hb_thread = None

    def renew_lease(self) -> bool:
        """Heartbeat the held lease. False (and ``self.fenced``) when a
        newer admin took over — from then on every spawn/stop raises
        and stop_all releases handles WITHOUT killing, because the
        children now belong to the new admin."""
        if not self._lease_held or self.fenced:
            return not self.fenced
        if self.meta.renew_admin_lease(self.lease_holder):
            return True
        import logging

        logging.getLogger(__name__).error(
            "admin lease lost (a newer admin took over) — fencing this "
            "manager: no further spawns/stops")
        self.fenced = True
        return False

    def release_lease(self) -> None:
        """Clean shutdown: expire the lease instantly so the next admin
        boots without waiting out the TTL. Stops the heartbeat FIRST so
        a late renew cannot resurrect the released lease."""
        self._stop_lease_heartbeat()
        if self._lease_held and not self.fenced:
            try:
                self.meta.release_admin_lease(self.lease_holder)
            except Exception:  # noqa: BLE001 — shutdown must not die
                # on a store hiccup; the TTL covers the release anyway
                import logging

                logging.getLogger(__name__).warning(
                    "admin lease release failed (the TTL will expire "
                    "it)", exc_info=True)
        self._lease_held = False

    def _check_fence(self) -> None:
        if self.fenced:
            raise AdminFencedError(
                "admin lease lost — this manager is fenced; a newer "
                "admin owns the stack now")

    def reap_stale_services(self) -> int:
        """Scorched-earth restart cleanup: kill every process a
        previous admin's non-terminal rows still point at and mark the
        rows STOPPED. :meth:`reconcile` (which ADOPTS survivors instead
        of killing them) is the normal boot path; this remains for
        operators who explicitly want a cold start. Kills are gated on
        the hardened pid identity — recorded start time included — so a
        recycled pid is never killed."""
        reaped = 0
        for row in self.meta.get_services():
            if row["status"] in _TERMINAL:
                continue
            if row["id"] in self.services:  # owned by THIS manager
                continue
            pid = int(row.get("pid") or 0)
            if pid > 0:
                terminate_pid(pid, float(row.get("start_time") or 0))
            self.meta.update_service(row["id"],
                                     status=ServiceStatus.STOPPED)
            reaped += 1
        return reaped

    # ---- boot reconciler (crash-only control plane) ----
    def reconcile(self) -> Dict[str, Any]:
        """Rebuild the process table from the MetaStore after an admin
        death. For every non-terminal service row left by the previous
        admin:

        - **adopt** survivors: pid alive + hardened identity (cmdline
          AND recorded kernel start time) + health probe on the
          recorded HTTP/obs port → a :class:`ManagedService` handle is
          rebuilt around the pid, its sub-mesh slot re-reserved, and
          its respawn spec re-registered — streams and trials keep
          running, nothing is restarted;
        - **crash** the dead: rows whose process is gone (or failed the
          identity/probe check) go CRASHED; crashed WORKERS of a
          still-RUNNING job flow into the existing respawn path under
          the durable respawn budget;
        - **reap** orphans: survivors whose job was stopped while the
          admin was down are killed (identity-gated) and marked
          STOPPED.

        The kvd data plane is adopted the same way (PING on the
        recorded port), so param blobs and in-flight queues survive the
        admin dying. Returns the recovery counter snapshot.
        """
        with self.op_lock:
            # op_lock intentionally serializes whole admin operations,
            # terminate/spawn waits included — overlapping reconciles
            # would double-spawn; see "Admin op serialization" in
            # docs/linting.md
            return self._reconcile()  # rafiki: noqa[lock-order-cycle]

    def _reconcile(self) -> Dict[str, Any]:
        import logging

        log = logging.getLogger(__name__)
        self._respawn_counts = self._load_respawn_counts()
        crashed_workers: List[Dict[str, Any]] = []
        for row in self.meta.get_services():
            if row["status"] in _TERMINAL or row["id"] in self.services:
                continue
            stype = row["service_type"]
            if stype == ServiceType.DATA_PLANE:
                self._reconcile_data_plane(row)
                continue
            pid = int(row.get("pid") or 0)
            start_time = float(row.get("start_time") or 0)
            spec = row.get("spawn_spec") or None
            job_id = row.get("train_job_id") or \
                row.get("inference_job_id")
            job = None
            if job_id:
                job = self.meta.get_train_job(job_id) or \
                    self.meta.get_inference_job(job_id)
            job_running = bool(job and job.get("status") == "RUNNING")
            alive = identity_matches(pid, start_time)

            if alive and job_id and not job_running:
                # orphan: its job was stopped/finished while no admin
                # was alive to stop the process
                log.info("reaping orphan %s %s (job %s is %s)",
                         stype, row["id"], job_id,
                         job.get("status") if job else "gone")
                terminate_pid(pid, start_time)
                self.meta.update_service(row["id"],
                                         status=ServiceStatus.STOPPED)
                self.recovery.inc("orphans_reaped")
                continue

            probe = self._probe_service(row, spec) if alive else False
            if alive and probe is not False:
                if self._adopt_service(row, spec, pid, start_time):
                    continue
                # un-adoptable (slot conflict): fall through to crash
                alive = False

            # dead / identity mismatch / failed probe → CRASHED
            if alive or identity_matches(pid, start_time):
                # process exists but is not serving: kill it before
                # respawning a replacement or two claim one slot
                terminate_pid(pid, start_time)
            self.meta.update_service(row["id"],
                                     status=ServiceStatus.CRASHED)
            self.recovery.inc("services_crashed")
            if job_running and spec and stype in _WORKER_TYPES:
                crashed_workers.append({"dead_id": row["id"],
                                        "spec": spec})

        # crashed workers flow into the EXISTING respawn path, under
        # the budget that survived the restart
        for item in crashed_workers:
            try:
                if not self._respawn(item["dead_id"], item["spec"]):
                    self._pending_respawns.append(item)
                    self.recovery.inc("respawns_queued")
            except Exception as e:  # noqa: BLE001 — reconcile must
                # finish; a failed respawn is a degraded job, not a
                # dead control plane
                log.warning("boot respawn of %s failed: %s",
                            item["dead_id"], e)
                mk = item["spec"].get("meta_kwargs") or {}
                self._mark_degraded(
                    item["spec"]["service_type"],
                    mk.get("train_job_id") or mk.get("inference_job_id"),
                    f"boot respawn failed: {e}")
        self.recovery.set("last_recovery_at", time.time())
        return self.recovery_stats()

    def _adopt_service(self, row: Dict[str, Any],
                       spec: Optional[Dict[str, Any]], pid: int,
                       start_time: float) -> bool:
        """Rebuild a ManagedService handle around a surviving pid.
        False when its recorded sub-mesh cannot be re-reserved (the
        caller then treats it as crashed)."""
        import logging

        stype = row["service_type"]
        slot = None
        if spec and spec.get("needs_slot"):
            try:
                devices = json.loads(row.get("devices") or "[]")
            except ValueError:
                devices = []
            slot = self.allocator.reserve(devices)
            if slot is None:
                logging.getLogger(__name__).warning(
                    "cannot adopt %s %s: its recorded sub-mesh %r is "
                    "no longer free", stype, row["id"], devices)
                return False
        svc = ManagedService(
            row["id"], stype, AdoptedProcess(pid, start_time), slot,
            host=row.get("host") or "127.0.0.1",
            port=int(row.get("port") or 0), adopted=True)
        self.services[row["id"]] = svc
        if spec and stype in _WORKER_TYPES:
            self._respawn_specs[row["id"]] = {
                "module": spec["module"], "config": spec["config"],
                "service_type": stype,
                "needs_slot": bool(spec.get("needs_slot")),
                "meta_kwargs": dict(spec.get("meta_kwargs") or {})}
        self.meta.update_service(row["id"],
                                 status=ServiceStatus.RUNNING)
        self.recovery.inc("services_adopted")
        return True

    def _probe_service(self, row: Dict[str, Any],
                       spec: Optional[Dict[str, Any]]
                       ) -> Optional[bool]:
        """Health-probe a candidate's recorded HTTP surface: the row's
        own port (advisor/predictor) or the worker's obs sidecar (port
        discovered from its ``obs_port_file``). ANY HTTP answer —
        including an error status — counts as alive (the process is
        serving; not every service has /health). None = no probe
        channel recorded: identity alone must decide."""
        import urllib.error

        from ..utils.http import json_request

        host = row.get("host") or "127.0.0.1"
        port = int(row.get("port") or 0)
        if port <= 0:
            cfg = (spec or {}).get("config") or {}
            port_file = cfg.get("obs_port_file")
            if port_file and Path(port_file).exists():
                try:
                    port = int(Path(port_file).read_text().strip())
                except (OSError, ValueError):
                    port = 0
        if port <= 0:
            return None
        try:
            json_request("GET", f"http://{host}:{port}/health",
                         timeout=3.0)
            return True
        except urllib.error.HTTPError:
            return True  # it answered — alive, just no /health route
        except (OSError, ValueError):
            return False  # refused/timeout/garbage: not serving

    def _reconcile_data_plane(self, row: Dict[str, Any]) -> None:
        """Adopt a surviving rafiki-kvd (param blobs + queues live in
        its memory — killing it would drop every in-flight stream and
        deployed trial's params). A DEAD kvd whose row records a data
        dir is respawned on the SAME port with WAL replay — "row
        present, process dead" is a recovery case, never a cold
        start."""
        import logging

        from .proc import pid_alive

        pid = int(row.get("pid") or 0)
        start_time = float(row.get("start_time") or 0)
        host, port = row.get("host") or "127.0.0.1", \
            int(row.get("port") or 0)
        spec_cfg = (row.get("spawn_spec") or {}).get("config") or {}
        ok = False
        # identity first (recycled pid must not be PINGed as ours);
        # kvd's cmdline is "rafiki-kvd ..." so cmdline_is_ours holds
        if port > 0 and pid_alive(pid) and identity_matches(
                pid, start_time):
            try:
                from ..native.client import KVClient

                c = KVClient(host, port, connect_timeout=3.0)
                ok = c.ping()
                c.close()
            except (OSError, RuntimeError):
                ok = False  # refused / protocol error: not a live kvd
        if ok:
            self.kv_host, self.kv_port = host, port
            self._kv_data_dir = str(spec_cfg.get("data_dir") or "")
            server = _AdoptedKVServer(host, port,
                                      AdoptedProcess(pid, start_time))
            self._kv_server = server
            self._kv_proc = server._proc
            self._kv_service_id = row["id"]
            self.recovery.inc("kv_adopted")
            logging.getLogger(__name__).info(
                "adopted data plane kvd pid %d on %s:%d", pid, host,
                port)
            return
        if identity_matches(pid, start_time):
            terminate_pid(pid, start_time)
        self.meta.update_service(row["id"],
                                 status=ServiceStatus.CRASHED)
        self.recovery.inc("services_crashed")
        if port > 0 and spec_cfg.get("data_dir"):
            # respawn-with-replay on the recorded address: surviving
            # workers/predictors reconnect to the same host:port and
            # the WAL restores blobs, membership, queued messages
            self.kv_host, self.kv_port = host, port
            self._kv_data_dir = str(spec_cfg["data_dir"])
            self._kv_service_id = row["id"]
            self._kv_proc = _DeadProc()  # respawn path's "died" handle
            self._respawn_data_plane("dead at admin reconcile")

    def recovery_stats(self) -> Dict[str, Any]:
        """Reconciler + lease counters for /metrics, /health, and the
        dashboard recovery banner."""
        out = self.recovery.snapshot()
        out["lease_generation"] = self.lease_generation
        out["fenced"] = bool(self.fenced)
        return out

    # ---- data plane ----
    #: kvd WAL fsync policy (overridable via RAFIKI_KVD_FSYNC):
    #: `everysec` matches the Redis default — at most ~1s of
    #: acknowledged writes lost to a HOST crash; a process crash
    #: (kill -9, OOM) loses nothing under any policy because the
    #: records are already written to the fd
    KVD_FSYNC_DEFAULT = "everysec"

    def start_data_plane(self) -> None:
        """Boot the kvd data plane with WAL + snapshot persistence
        under ``workdir/kvd-data`` (no-op when already running or
        adopted by :meth:`reconcile`). The full boot recipe — data dir,
        fsync policy, host/port — persists in the service row's spawn
        spec, so both this admin's monitor and a RESTARTED admin can
        respawn a dead kvd with replay instead of cold-starting an
        empty one."""
        if self.kv_port:
            return  # already running or adopted by reconcile()
        self._check_fence()
        data_dir = str(self.workdir / "kvd-data")
        fsync = os.environ.get("RAFIKI_KVD_FSYNC",
                               self.KVD_FSYNC_DEFAULT)
        self._boot_data_plane("127.0.0.1", 0, data_dir, fsync)

    def _boot_data_plane(self, host: str, port: int, data_dir: str,
                         fsync: str) -> None:
        """Spawn a kvd (fresh or respawn-with-replay when ``port`` is
        pinned and the data dir already holds a WAL) and record its
        row + spawn spec."""
        from ..native.client import KVServer

        server = KVServer(host=host, port=port, data_dir=data_dir,
                          fsync=fsync)
        self._kv_server = server
        self._kv_proc = server._proc
        self.kv_host, self.kv_port = server.host, server.port
        self._kv_data_dir = data_dir
        row = self.meta.create_service(
            ServiceType.DATA_PLANE, host=server.host, port=server.port,
            pid=server._proc.pid,
            spawn_spec={"module": "rafiki-kvd",
                        "config": {"data_dir": data_dir,
                                   "fsync": fsync,
                                   "host": server.host,
                                   "port": server.port},
                        "service_type": ServiceType.DATA_PLANE,
                        "needs_slot": False, "meta_kwargs": {}},
            start_time=proc_start_time(server._proc.pid))
        self._kv_service_id = row["id"]
        self.meta.update_service(row["id"],
                                 status=ServiceStatus.RUNNING)
        # replay time is the recovery-latency half the bench measures;
        # stats() may briefly race the listener coming up — best-effort
        try:
            st = self._fresh_kvd_stats()
            self.recovery.set("kvd_replay_seconds",
                              float(st.get("replay_seconds") or 0.0))
        except (OSError, RuntimeError) as e:
            import logging

            logging.getLogger(__name__).warning(
                "could not read kvd replay stats: %s", e)

    def _respawn_data_plane(self, reason: str) -> bool:
        """Respawn a dead kvd on its RECORDED host:port + data dir —
        clients reconnect to the same address and the WAL replay
        restores blobs, pool membership, and queued messages. Budgeted
        like worker respawns (persisted lineage ``(DATA_PLANE, kvd)``)
        so a crash-looping data dir converges to a loud degraded state
        instead of a respawn storm. Returns True when a kvd is
        serving again."""
        import logging

        log = logging.getLogger(__name__)
        host, port = self.kv_host, self.kv_port
        data_dir = self._kv_data_dir or str(self.workdir / "kvd-data")
        lineage = (ServiceType.DATA_PLANE, "kvd")
        if self._respawn_counts.get(lineage, 0) >= self.max_respawns:
            log.error(
                "kvd respawn budget exhausted (%s) — the data plane "
                "appears to crash deterministically; stack is degraded "
                "until an operator intervenes", reason)
            self._degraded["data-plane"] = \
                "kvd respawn budget exhausted"
            self._kv_proc = None  # stop supervising the corpse (the
            # degraded flag + kvd_up 0 carry the signal from here)
            return False
        old_id = getattr(self, "_kv_service_id", None)
        if old_id:
            self.meta.update_service(old_id,
                                     status=ServiceStatus.CRASHED)
        log.warning("kvd data plane died (%s): respawning on %s:%d "
                    "with WAL replay from %s", reason, host, port,
                    data_dir)
        fsync = os.environ.get("RAFIKI_KVD_FSYNC",
                               self.KVD_FSYNC_DEFAULT)
        t0 = time.monotonic()
        # ONE boot attempt per monitor tick: poll() holds op_lock, and
        # an in-line wait-for-the-port retry loop here would stall
        # every admin operation for its duration. A failed attempt
        # leaves the dead handle in place so the NEXT poll retries;
        # ~20 ticks of failures (a port that never frees, a corrupt
        # dir the budget check didn't see) go degraded-loud instead.
        try:
            self.kv_host, self.kv_port = "", 0  # let boot re-record
            self._boot_data_plane(host, port, data_dir, fsync)
        except (OSError, RuntimeError) as e:
            self.kv_host, self.kv_port = host, port
            self._kv_boot_attempts += 1
            if self._kv_boot_attempts >= 20:
                self._degraded["data-plane"] = \
                    f"kvd respawn failed: {e}"
                self._kv_proc = None  # see budget branch above
                log.error("kvd respawn failed %d times, giving up: "
                          "%s", self._kv_boot_attempts, e)
            else:
                log.warning("kvd respawn attempt %d failed (%s) — "
                            "retrying on the next monitor tick",
                            self._kv_boot_attempts, e)
            return False
        self._kv_boot_attempts = 0
        try:
            self._respawn_counts[lineage] = \
                self.meta.incr_respawn_count(ServiceType.DATA_PLANE,
                                             "kvd")
        except Exception as e:  # noqa: BLE001 — never lose healing to
            # a store hiccup; fall back to the in-memory count
            log.warning("kvd respawn budget write-through failed: %s",
                        e)
            self._respawn_counts[lineage] = \
                self._respawn_counts.get(lineage, 0) + 1
        self.recovery.inc("kvd_respawns")
        self._degraded.pop("data-plane", None)
        log.warning("kvd respawned in %.2fs (pid %d, replay %.3fs)",
                    time.monotonic() - t0, self._kv_proc.pid,
                    float(self.recovery["kvd_replay_seconds"]))
        return True

    def _check_data_plane(self) -> None:
        """Monitor-tick half of kvd supervision: a data-plane process
        that died (kill -9, OOM) is respawned on its recorded port and
        replays its WAL. Runs under op_lock (poll)."""
        if self._kv_proc is None or self.fenced:
            return
        if self._kv_proc.poll() is None:
            return  # alive
        self._respawn_data_plane(
            f"process exited rc={self._kv_proc.returncode}")

    def _fresh_kvd_stats(self) -> Dict[str, Any]:
        from ..native.client import KVClient

        # op_timeout bounds the read too: a wedged (or compaction-busy)
        # kvd must surface as a caught timeout, not hang every /metrics
        # and /health behind _kvd_stats_lock
        c = KVClient(self.kv_host, self.kv_port, connect_timeout=2.0,
                     op_timeout_s=2.0)
        try:
            return c.stats()
        finally:
            c.close()

    def kvd_stats(self, max_age_s: float = 2.0) -> Dict[str, Any]:
        """Cached kvd STATS (persistence health: wal_bytes,
        snapshot_age_s, last_fsync_age_s, ...) plus ``up``. Guarded by
        its own lock and cached so /metrics scrapes cost at most one
        socket round-trip per ``max_age_s``."""
        with self._kvd_stats_lock:
            now = time.monotonic()
            if now - self._kvd_stats_at < max_age_s:
                return dict(self._kvd_stats_cache)
            if not self.kv_port:
                self._kvd_stats_cache = {"up": 0}
            else:
                try:
                    st = self._fresh_kvd_stats()
                    st["up"] = 1
                    self._kvd_stats_cache = st
                except (OSError, RuntimeError) as e:
                    import logging

                    logging.getLogger(__name__).debug(
                        "kvd stats probe failed: %s", e)
                    self._kvd_stats_cache = {"up": 0}
            self._kvd_stats_at = now
            return dict(self._kvd_stats_cache)

    def kvd_metrics(self) -> Dict[str, Any]:
        """Numeric re-export for the admin /metrics collector:
        ``kvd_up``, ``kvd_wal_bytes``, ``kvd_snapshot_age_s``,
        ``kvd_last_fsync_age_s``, ``kvd_replay_seconds``,
        ``kvd_respawns``."""
        st = self.kvd_stats()
        out = {"kvd_up": int(st.get("up") or 0),
               "kvd_respawns": self.recovery["kvd_respawns"],
               "kvd_replay_seconds":
                   self.recovery["kvd_replay_seconds"]}
        for k in ("wal_bytes", "snapshot_bytes", "snapshot_age_s",
                  "last_fsync_age_s", "compactions",
                  "wal_truncated_bytes"):
            if k in st:
                out[f"kvd_{k}"] = st[k]
        return out

    def data_plane_status(self) -> Dict[str, Any]:
        """The /health ``data_plane`` block: up/down, address, data
        dir, respawn + replay counters, and the persistence stats."""
        st = self.kvd_stats()
        return {"up": bool(st.get("up")),
                "host": self.kv_host, "port": self.kv_port,
                "data_dir": self._kv_data_dir,
                "respawns": self.recovery["kvd_respawns"],
                "replay_seconds":
                    self.recovery["kvd_replay_seconds"],
                "stats": {k: v for k, v in st.items() if k != "up"}}

    @property
    def param_store_uri(self) -> str:
        if self.kv_port:
            return f"kv://{self.kv_host}:{self.kv_port}"
        return f"file://{self.workdir / 'params'}"

    # ---- process plumbing ----
    def _spawn(self, module: str, config: Dict[str, Any],
               service_type: str, slot: Optional[SubMesh] = None,
               wait_port_file: bool = False, timeout: float = 180.0,
               **meta_kwargs: Any) -> ManagedService:
        self._check_fence()
        tag = f"{service_type.lower()}-{uuid.uuid4().hex[:8]}"
        cfg_path = self.workdir / f"{tag}.json"
        port_file = self.workdir / f"{tag}.port"
        if wait_port_file:
            config = {**config, "port_file": str(port_file)}
        cfg_path.write_text(json.dumps(config))

        env = os.environ.copy()
        if slot is not None:
            env.update(submesh_env_vars(self.platform, slot))
        else:
            # control-plane children (advisor/predictor) must never claim
            # accelerator chips — pin them to host CPU
            env["JAX_PLATFORMS"] = "cpu"
        log = open(self.workdir / f"{tag}.log", "ab")
        proc = subprocess.Popen(
            [sys.executable, "-m", module, "--config", str(cfg_path)],
            env=env, stdout=log, stderr=subprocess.STDOUT)
        log.close()

        host, port = "127.0.0.1", 0
        if wait_port_file:
            deadline = time.monotonic() + timeout
            while time.monotonic() < deadline:
                if port_file.exists() and port_file.read_text().strip():
                    port = int(port_file.read_text().strip())
                    break
                if proc.poll() is not None:
                    raise RuntimeError(
                        f"{service_type} died on startup; see "
                        f"{self.workdir / f'{tag}.log'}")
                time.sleep(0.05)
            else:
                proc.kill()
                raise TimeoutError(f"{service_type} did not report a port")

        # the ROW carries everything needed to re-adopt or respawn this
        # service after an admin crash: the full spawn recipe plus the
        # pid's kernel start time (the recycle-proof identity half)
        spawn_spec = {"module": module, "config": dict(config),
                      "service_type": service_type,
                      "needs_slot": slot is not None,
                      "meta_kwargs": dict(meta_kwargs), "tag": tag}
        row = self.meta.create_service(
            service_type, host=host, port=port, pid=proc.pid,
            devices=[d.id for d in (slot.devices if slot else [])],
            spawn_spec=spawn_spec,
            start_time=proc_start_time(proc.pid),
            **meta_kwargs)
        svc = ManagedService(row["id"], service_type, proc, slot, host, port)
        self.services[row["id"]] = svc
        if service_type in _WORKER_TYPES:
            self._respawn_specs[row["id"]] = {
                "module": module, "config": dict(config),
                "service_type": service_type, "needs_slot": slot is not None,
                "meta_kwargs": dict(meta_kwargs)}
        self.meta.update_service(row["id"], status=ServiceStatus.RUNNING)
        return svc

    # ---- train jobs (SURVEY.md §3.1) ----
    def create_train_services(self, train_job_id: str,
                              n_workers: Optional[int] = None
                              ) -> List[ManagedService]:
        with self.op_lock:
            # op_lock serializes admin ops end-to-end, spawn port-waits
            # included (see docs/linting.md "Admin op serialization")
            return self._create_train_services(  # rafiki: noqa[lock-order-cycle]
                train_job_id,
                self.default_workers if n_workers is None else n_workers)

    def _create_train_services(self, train_job_id: str,
                               n_workers: int) -> List[ManagedService]:
        job = self.meta.get_train_job(train_job_id)
        if job is None:
            raise KeyError(f"no train job {train_job_id!r}")
        budget = job["budget"]
        n_workers = int(budget.get("WORKER_COUNT",
                                   budget.get("GPU_COUNT", n_workers)))
        subs = self.meta.get_sub_train_jobs_of_train_job(train_job_id)

        # a knob_overrides key that matches NO model's knob config is a
        # typo: fail before spawning anything rather than silently running
        # the full search on the dimension the user believes is pinned
        # (same validator as tune_model's dev loop — model/knob.py)
        requested = job["train_args"].get("knob_overrides") or {}
        if requested:
            from ..model.base import load_model_class
            from ..model.knob import validate_override_keys

            known: set = set()
            for sub in subs:
                model = self.meta.get_model(sub["model_id"])
                known |= set(load_model_class(
                    model["model_bytes"],
                    model["model_class"]).get_knob_config())
            validate_override_keys(
                known, requested,
                context="knob_overrides for this job's models:")

        spawned: List[ManagedService] = []
        for sub in subs:
            model = self.meta.get_model(sub["model_id"])
            model_file = self.workdir / f"model-{model['id']}.py"
            model_file.write_bytes(model["model_bytes"])

            # one advisor service per sub-train-job (reference: one advisor
            # container per model under tuning)
            from ..model.base import load_model_class
            from ..model.knob import knob_config_to_json

            model_class = load_model_class(model["model_bytes"],
                                           model["model_class"])
            knob_config = model_class.get_knob_config()
            # job-level knob pins: keep only the knobs THIS model has
            # (multi-model jobs — other models' knobs must not leak into
            # its proposals) and substitute FixedKnob into the advisor's
            # search space so no trial budget is spent re-sampling pinned
            # dimensions. The worker still merges the same values as a
            # belt-and-braces.
            overrides = {
                k: v for k, v in (job["train_args"].get("knob_overrides")
                                  or {}).items() if k in knob_config}
            if overrides:
                from ..model.knob import FixedKnob

                knob_config = {
                    name: (FixedKnob(overrides[name])
                           if name in overrides else knob)
                    for name, knob in knob_config.items()}
            advisor = self._spawn(
                "rafiki_tpu.advisor.service",
                {"knob_config": knob_config_to_json(knob_config),
                 "advisor_type": job["train_args"].get("advisor", "auto"),
                 "total_trials": budget.get("TRIAL_COUNT"),
                 "time_budget_s": (float(budget["TIME_HOURS"]) * 3600
                                   if budget.get("TIME_HOURS") else None)},
                ServiceType.ADVISOR, wait_port_file=True,
                train_job_id=train_job_id, sub_train_job_id=sub["id"])
            spawned.append(advisor)

            # per-trial jax.profiler traces, opt-in via train_args
            profile_dir = ""
            if job["train_args"].get("profile"):
                profile_dir = str(self.workdir / "profiles" / sub["id"])
            for w in range(n_workers):
                slot = self.allocator.acquire(timeout=0.0)
                if slot is None:
                    break  # no free sub-mesh; trials queue on fewer workers
                try:
                    worker = self._spawn(
                        "rafiki_tpu.worker.train",
                        {"advisor_url": advisor.url,
                         "model_file": str(model_file),
                         "model_class": model["model_class"],
                         "model_id": model["id"],
                         "train_dataset": job["train_dataset_id"],
                         "val_dataset": job["val_dataset_id"],
                         "param_store_uri": self.param_store_uri,
                         "meta_store_path": self.meta._db_path,
                         "sub_train_job_id": sub["id"],
                         "profile_dir": profile_dir,
                         "knob_overrides": overrides,
                         # gang trial mode: K trials per compiled step
                         # on this worker's sub-mesh (small-zoo
                         # templates)
                         "gang_size": int(job["train_args"].get(
                             "gang_size") or 0),
                         "checkpoint_interval_s": job["train_args"].get(
                             "checkpoint_interval_s", 30.0),
                         "worker_id": f"tw-{sub['id'][:8]}-{w}",
                         # /metrics + /debug/requests sidecar:
                         # ephemeral port, discoverable from this file
                         "obs_port_file": str(
                             self.workdir / f"tw-{sub['id'][:8]}-{w}"
                                            ".obs_port")},
                        ServiceType.TRAIN_WORKER, slot=slot,
                        train_job_id=train_job_id,
                        sub_train_job_id=sub["id"])
                except Exception:
                    # the slot was never handed to a live service:
                    # return it to the pool or it is gone until admin
                    # restart (every sibling spawn site guards this)
                    self.allocator.release(slot)
                    raise
                spawned.append(worker)
            self.meta.update_sub_train_job(
                sub["id"], status=SubTrainJobStatus.RUNNING)
        self.meta.update_train_job(train_job_id,
                                   status=TrainJobStatus.RUNNING)
        return spawned

    def wait_train_job(self, train_job_id: str,
                       timeout: float = 3600.0) -> bool:
        """Block until every train worker of the job exits; stops the
        job's advisors; returns True if it finished in time."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            self.poll()
            # re-list each tick: poll() may have RESPAWNED a crashed
            # worker — a snapshot would declare the job done while the
            # replacement is still training. A queued (slot-starved)
            # respawn also keeps the job busy.
            workers = [s for s in self.services.values()
                       if s.service_type == ServiceType.TRAIN_WORKER]
            if all(not s.alive() for s in workers) and \
                    train_job_id not in self.pending_respawn_job_ids():
                break
            time.sleep(0.2)
        else:
            return False
        for s in list(self.services.values()):
            if s.service_type == ServiceType.ADVISOR:
                self.stop_service(s.service_id)
        for sub in self.meta.get_sub_train_jobs_of_train_job(train_job_id):
            self.meta.update_sub_train_job(sub["id"],
                                           status=SubTrainJobStatus.STOPPED)
        self.meta.update_train_job(train_job_id,
                                   status=TrainJobStatus.STOPPED)
        return True

    # ---- inference jobs (SURVEY.md §3.2) ----
    def create_inference_services(self, inference_job_id: str,
                                  max_workers: int = 2
                                  ) -> List[ManagedService]:
        ijob = self.meta.get_inference_job(inference_job_id)
        if ijob is None:
            raise KeyError(f"no inference job {inference_job_id!r}")
        best = self.meta.get_best_trials_of_train_job(
            ijob["train_job_id"], max_count=max_workers)
        if not best:
            raise RuntimeError("no completed trials to deploy")
        # MULTI_ADAPTER budget flag: deploy the best-N LM trials as ONE
        # worker serving N stacked LoRA adapters (adapter 0 = best
        # trial, i = i-th best; requests route via sampling
        # {"adapter_id": i}) instead of N full replicas — one base
        # model's HBM, one device slot. Requires adapters_only trials;
        # a mismatched base fails the worker boot loudly. Best trials
        # can span MODELS (a train job tunes every registered template
        # for its task), so extras are filtered to the primary trial's
        # model — a foreign trial's dump can't stack onto its base.
        budget = ijob.get("budget") or {}
        multi_adapter = False
        if bool(budget.get("MULTI_ADAPTER")) and len(best) > 1:
            import logging

            log = logging.getLogger(__name__)

            def model_of(trial):
                sub = self.meta.get_sub_train_job(
                    trial["sub_train_job_id"])
                return self.meta.get_model(sub["model_id"])

            primary_model = model_of(best[0])
            if primary_model["task"] != TaskType.LANGUAGE_MODELING:
                log.warning(
                    "MULTI_ADAPTER ignored: task %s is not a language-"
                    "modeling job; deploying plain replicas",
                    primary_model["task"])
            else:
                # stackable = same model AND same shape signature as
                # the primary (shape-relevant knobs are advisor-
                # searched, so same-model trials can still disagree on
                # hidden_dim/rank/...; shipping those to one engine
                # would be a guaranteed crash-looping worker boot)
                sig0 = best[0].get("shape_signature")
                same = [best[0]] + [
                    t for t in best[1:]
                    if model_of(t)["id"] == primary_model["id"]
                    and t.get("shape_signature") == sig0]
                if len(same) > 1:
                    if len(same) < len(best):
                        log.warning(
                            "MULTI_ADAPTER: dropping %d best trial(s) "
                            "with a different model or shape; stacking "
                            "%d trials of model %s",
                            len(best) - len(same), len(same),
                            primary_model["id"])
                    best = same
                    multi_adapter = True
                else:
                    log.warning(
                        "MULTI_ADAPTER ignored: no other best trial "
                        "shares model %s and shape %r; deploying "
                        "plain replicas", primary_model["id"], sig0)
        n_services = 1 if multi_adapter else len(best)

        # autoscale bounds validate at the API surface — a bad bound
        # (MIN > initial, MAX < MIN, bounds without AUTOSCALE) fails
        # the create call, not a monitor tick hours later
        if AutoscaleConfig.from_budget(budget, n_services) is not None \
                and n_services > 1:
            # replicas deploy DISTINCT best trials (an ensemble);
            # autoscaled clones of trial 0 would double-weight it in
            # the unary gather, and a scale-down could evict another
            # trial's only replica
            raise ValueError(
                "AUTOSCALE requires a single-replica deployment "
                f"(this create would spawn {n_services} workers, one "
                "per DISTINCT best trial): create with max_workers=1 "
                "(or MULTI_ADAPTER) and let the autoscaler grow the "
                "pool with clones of the best trial")

        # A replica MUST own a device slot: quietly pinning it to host CPU
        # would serve at CPU speed — a perf cliff, never a default. Acquire
        # every slot BEFORE taking op_lock: release paths (poll /
        # stop_service) need that lock, so blocking on the allocator while
        # holding it could never be satisfied by a concurrent release.
        slots: List[SubMesh] = []
        for i in range(n_services):
            slot = self.allocator.acquire(timeout=self.slot_timeout)
            if slot is None:
                for s in slots:
                    self.allocator.release(s)
                self.meta.update_inference_job(inference_job_id,
                                               status="ERRORED")
                raise RuntimeError(
                    f"no free device slot for inference replica {i} after "
                    f"{self.slot_timeout:.0f}s ({self.allocator.n_slots} "
                    f"slots, {self.allocator.free_count()} free); stop a "
                    "running job or lower the replica count")
            slots.append(slot)

        with self.op_lock:
            try:
                # op_lock serializes admin ops end-to-end, spawn
                # port-waits included (see docs/linting.md "Admin op
                # serialization")
                return self._create_inference_services(  # rafiki: noqa[lock-order-cycle]
                    inference_job_id, best, slots,
                    multi_adapter=multi_adapter)
            except BaseException:
                # slots not yet handed to a spawned service stay ours —
                # give them back (spawned services release via _poll/stop)
                held = {id(s.slot) for s in self.services.values()
                        if s.slot is not None}
                for slot in slots:
                    if id(slot) not in held:
                        try:
                            self.allocator.release(slot)
                        except ValueError:
                            pass  # already released by a service stop
                self.meta.update_inference_job(inference_job_id,
                                               status="ERRORED")
                raise

    def _create_inference_services(self, inference_job_id: str,
                                   best: List[Dict[str, Any]],
                                   slots: List["SubMesh"],
                                   multi_adapter: bool = False
                                   ) -> List[ManagedService]:
        if not self.kv_port:
            self.start_data_plane()

        ijob = self.meta.get_inference_job(inference_job_id) or {}
        budget = ijob.get("budget") or {}
        spawned: List[ManagedService] = []
        worker_ids: List[str] = []
        services = [best[0]] if multi_adapter else best
        # SLO / overload budget keys, validated HERE at the create API
        # (a typo'd class or negative cap fails the call, not a
        # crash-looping worker). SLO_DEFAULT classes unlabeled
        # requests on the predictor AND every worker; SLO_P95_TARGET_S
        # (> 0, seconds of interactive TTFT p95) arms the predictor's
        # brownout ladder; SLO_SHED_BATCH_DEPTH /
        # SLO_SHED_BACKGROUND_DEPTH (>= 0) cap best-effort backlog;
        # SLO_BACKGROUND_MAX_NEW (>= 1) is the ladder's stage-2 clamp
        # and therefore requires the ladder to be armed.
        from ..serving.slo import normalize_slo
        slo_default = ""
        if "SLO_DEFAULT" in budget:
            try:
                slo_default = normalize_slo(budget["SLO_DEFAULT"])
            except ValueError as e:
                raise ValueError(f"SLO_DEFAULT: {e}") from e
        slo_shed_depths: Dict[str, int] = {}
        for key, cls in (("SLO_SHED_BATCH_DEPTH", "batch"),
                         ("SLO_SHED_BACKGROUND_DEPTH", "background")):
            if key in budget:
                d = int(budget[key])
                if d < 0:
                    raise ValueError(f"{key}={d} must be >= 0 "
                                     "(fleet queue-backlog cap)")
                slo_shed_depths[cls] = d
        brownout_target = 0.0
        if budget.get("SLO_P95_TARGET_S"):
            brownout_target = float(budget["SLO_P95_TARGET_S"])
            if brownout_target <= 0:
                raise ValueError(
                    f"SLO_P95_TARGET_S={budget['SLO_P95_TARGET_S']} "
                    "must be > 0 (target interactive TTFT p95, "
                    "seconds)")
        # Disaggregated prefill/decode + host KV tier budget keys,
        # validated HERE at the create API like every serving knob.
        # WORKER_ROLE: one role broadcast to every worker, or a
        # comma-separated role per worker index ("prefill,decode,
        # decode") — any prefill role requires at least one serving
        # (decode/unified) role or nothing would answer queries.
        # HOST_KV_PAGES (>= 1, requires KV_PAGE_SIZE): pinned-host KV
        # page tier per worker — admission budget becomes HBM + host.
        # KV_WAIT_S (>= 0): how long a decode worker holds a request
        # for its KV shipment before re-prefilling locally.
        from ..serving.kv_transfer import normalize_role
        roles: List[str] = []
        if budget.get("WORKER_ROLE"):
            try:
                roles = [normalize_role(r) for r in
                         str(budget["WORKER_ROLE"]).split(",")]
            except ValueError as e:
                raise ValueError(f"WORKER_ROLE: {e}") from e
            if len(roles) == 1:
                roles = roles * len(services)
            if len(roles) != len(services):
                raise ValueError(
                    f"WORKER_ROLE names {len(roles)} roles for "
                    f"{len(services)} workers (one per worker, or a "
                    "single role for all)")
            if any(r == "prefill" for r in roles) and \
                    all(r == "prefill" for r in roles):
                raise ValueError(
                    "WORKER_ROLE: an all-prefill pool serves nothing "
                    "— at least one worker must be decode or unified")
        host_kv_pages = 0
        if budget.get("HOST_KV_PAGES"):
            host_kv_pages = int(budget["HOST_KV_PAGES"])
            if host_kv_pages < 1:
                raise ValueError(
                    f"HOST_KV_PAGES={host_kv_pages} must be >= 1 "
                    "(host-tier page count)")
            if not budget.get("KV_PAGE_SIZE"):
                raise ValueError(
                    "HOST_KV_PAGES requires KV_PAGE_SIZE in the same "
                    "budget (pages are the host tier's transfer unit)")
        kv_wait_s = None
        if "KV_WAIT_S" in budget:
            kv_wait_s = float(budget["KV_WAIT_S"])
            if kv_wait_s < 0:
                raise ValueError(f"KV_WAIT_S={kv_wait_s} must be >= 0")
            if not roles:
                raise ValueError(
                    "KV_WAIT_S requires WORKER_ROLE in the same "
                    "budget (it tunes the disaggregated decode leg)")
        bg_clamp = 0
        if "SLO_BACKGROUND_MAX_NEW" in budget:
            # membership, not truthiness: 0 must FAIL the create call
            # (the documented >= 1 contract), not silently fall back
            # to the predictor's default clamp
            bg_clamp = int(budget["SLO_BACKGROUND_MAX_NEW"])
            if bg_clamp < 1:
                raise ValueError(
                    f"SLO_BACKGROUND_MAX_NEW={bg_clamp} must be >= 1")
            if not brownout_target:
                raise ValueError(
                    "SLO_BACKGROUND_MAX_NEW requires SLO_P95_TARGET_S "
                    "in the same budget (the brownout ladder applies "
                    "the clamp at stage 2)")
        for i, trial in enumerate(services):
            sub = self.meta.get_sub_train_job(trial["sub_train_job_id"])
            model = self.meta.get_model(sub["model_id"])
            model_file = self.workdir / f"model-{model['id']}.py"
            model_file.write_bytes(model["model_bytes"])
            wid = f"iw-{inference_job_id[:8]}-{i}"
            slot = slots[i]
            # generative tasks serve through the continuous-batching
            # decode loop (slot-based KV admission) instead of the
            # classification micro-batcher
            decode_loop = model["task"] == TaskType.LANGUAGE_MODELING
            cfg = {"model_file": str(model_file),
                   "model_class": model["model_class"],
                   "trial_id": trial["id"], "knobs": trial["knobs"],
                   "param_store_uri": self.param_store_uri,
                   "kv_host": self.kv_host, "kv_port": self.kv_port,
                   "worker_id": wid, "decode_loop": decode_loop,
                   # /metrics + /debug/requests sidecar: ephemeral
                   # port, discoverable from this file (and from the
                   # obs_port gauge the worker publishes to /health)
                   "obs_port_file": str(self.workdir
                                        / f"{wid}.obs_port"),
                   # decode-loop dispatch amortization (ops guide): K
                   # fused steps per device program, tunable per job
                   "steps_per_sync": int(budget.get("STEPS_PER_SYNC",
                                                    4))}
            if budget.get("MAX_NEW_TOKENS"):
                cfg["max_new_tokens"] = int(budget["MAX_NEW_TOKENS"])
            if slo_default:
                cfg["default_slo"] = slo_default
            if budget.get("SYSTEM_PREFIX"):
                cfg["system_prefix"] = str(budget["SYSTEM_PREFIX"])
            if budget.get("KV_PAGE_SIZE"):
                # paged (block-table) KV serving: cache HBM and
                # admission scale with the page pool (live tokens),
                # not max_slots x max_len — see docs/operations.md
                # "Paged KV cache". KV_PAGES sizes the pool (0/unset =
                # full coverage, no saving). Misconfigurations fail
                # HERE at the API call, not as a crash-looping worker.
                if not decode_loop:
                    raise ValueError(
                        "KV_PAGE_SIZE requires a language-modeling "
                        "deployment (the decode loop owns the KV "
                        f"cache); task {model['task']} serves through "
                        "the micro-batcher")
                page = int(budget["KV_PAGE_SIZE"])
                trial_max_len = int(
                    (trial.get("knobs") or {}).get("max_len", 0) or 0)
                if page <= 0 or (trial_max_len
                                 and trial_max_len % page):
                    # the engine's own validity rule, enforced at the
                    # deployment surface (a bad page size would
                    # otherwise kill the worker at engine build)
                    raise ValueError(
                        f"KV_PAGE_SIZE={page} must be > 0 and divide "
                        f"the trial's max_len ({trial_max_len})")
                cfg["kv_page_size"] = page
                if budget.get("KV_PAGES"):
                    pages = int(budget["KV_PAGES"])
                    if pages < 2:
                        raise ValueError(
                            f"KV_PAGES={pages} must be >= 2 (page 0 "
                            "is the scratch page; at least one usable "
                            "page) — omit it for the full-coverage "
                            "default")
                    cfg["kv_pages"] = pages
                if "PAGED_KERNEL" in budget:
                    # paged decode dispatch override: the Pallas
                    # block-table kernel vs the page gather. Unset /
                    # blank / "auto" keep the ops-level rule (kernel
                    # on TPU, gather off-TPU); an explicit value
                    # forces one path fleet-wide for this job (A/B,
                    # incident rollback). Parsed by the WORKER'S own
                    # tri-state coercion so the admin surface can
                    # never mean something different from the same
                    # value in a worker config.
                    from ..worker.inference import _tristate

                    pk = _tristate(budget["PAGED_KERNEL"])
                    if pk is not None:
                        cfg["paged_kernel"] = pk
            elif budget.get("KV_PAGES"):
                raise ValueError(
                    "KV_PAGES requires KV_PAGE_SIZE in the same "
                    "budget (pages have no size without it)")
            elif "PAGED_KERNEL" in budget:
                raise ValueError(
                    "PAGED_KERNEL requires KV_PAGE_SIZE in the same "
                    "budget (it selects the PAGED decode path's "
                    "implementation)")
            if host_kv_pages:
                # KV_PAGE_SIZE validation above already guaranteed the
                # decode loop and a paged engine
                cfg["host_kv_pages"] = host_kv_pages
            if roles:
                if not decode_loop:
                    raise ValueError(
                        "WORKER_ROLE requires a language-modeling "
                        "deployment (the decode loop owns the KV "
                        f"shipments); task {model['task']} serves "
                        "through the micro-batcher")
                if roles[i] != "unified":
                    cfg["role"] = roles[i]
            if kv_wait_s is not None:
                cfg["kv_wait_s"] = kv_wait_s
            # the job's pool id keys cross-worker shared state (the
            # prefix-snapshot blob): one replica prefills the shared
            # prefix, every peer imports it
            cfg["pool_id"] = inference_job_id
            if decode_loop and budget.get("SPECULATE_K"):
                # speculative decoding at the DEPLOYMENT surface:
                # SPECULATE_K alone enables prompt-lookup drafting;
                # DRAFT_TRIAL_ID names a (smaller) completed trial as
                # the draft MODEL. The draft must be the same template
                # (the engine's vocab check guards the rest); its own
                # trial knobs shape it. Misconfigurations fail HERE at
                # the API call, not as a crash-looping worker boot.
                spec_k = int(budget["SPECULATE_K"])
                if spec_k < 2:
                    raise ValueError(
                        f"SPECULATE_K={spec_k} must be >= 2 (draft "
                        "window depth; 1 would verify nothing)")
                cfg["speculate_k"] = spec_k
                draft_id = str(budget.get("DRAFT_TRIAL_ID") or "")
                if draft_id:
                    d_trial = self.meta.get_trial(draft_id)
                    if d_trial is None:
                        raise KeyError(
                            f"DRAFT_TRIAL_ID {draft_id!r} names no "
                            "trial")
                    d_sub = self.meta.get_sub_train_job(
                        d_trial["sub_train_job_id"])
                    if d_sub and d_sub["model_id"] != model["id"]:
                        raise ValueError(
                            f"DRAFT_TRIAL_ID {draft_id!r} is a "
                            f"different model ({d_sub['model_id']}) "
                            f"than the deployed {model['id']} — the "
                            "draft must share the target's template/"
                            "tokenizer")
                    cfg["draft_trial_id"] = draft_id
                    cfg["draft_knobs"] = d_trial["knobs"]
            elif budget.get("DRAFT_TRIAL_ID") or budget.get(
                    "SPECULATE_K"):
                if not decode_loop:
                    raise ValueError(
                        "SPECULATE_K/DRAFT_TRIAL_ID require a "
                        "language-modeling deployment (the decode "
                        f"loop); task {model['task']} serves through "
                        "the micro-batcher")
                raise ValueError(
                    "DRAFT_TRIAL_ID requires SPECULATE_K >= 2 (the "
                    "draft window depth) in the same budget")
            if multi_adapter:
                # the other best trials ride as stacked adapters 1..N
                cfg["extra_adapter_trials"] = [t["id"]
                                               for t in best[1:]]
            svc = self._spawn(
                "rafiki_tpu.worker.inference", cfg,
                ServiceType.INFERENCE_WORKER, slot=slot,
                inference_job_id=inference_job_id)
            spawned.append(svc)
            worker_ids.append(wid)

        pred_cfg: Dict[str, Any] = {
            "worker_ids": worker_ids, "kv_host": self.kv_host,
            "kv_port": self.kv_port, "host": "127.0.0.1", "port": 0,
            # live routing-pool membership key: the predictor's
            # router/breaker tables follow autoscale events published
            # under the job id without a predictor rebuild
            "pool_id": inference_job_id,
            # the serving latency/accuracy controller (paper's
            # batching/wait tradeoff): gather deadline tracks the
            # fleet's observed reply latencies instead of always
            # waiting full timeout for stragglers
            "adaptive_gather": bool(budget.get("ADAPTIVE_GATHER"))}
        if slo_default:
            pred_cfg["default_slo"] = slo_default
        if slo_shed_depths:
            pred_cfg["slo_shed_depths"] = slo_shed_depths
        if brownout_target:
            pred_cfg["brownout_target_p95_s"] = brownout_target
        if bg_clamp:
            pred_cfg["brownout_clamp_max_new"] = bg_clamp
        predictor = self._spawn(
            "rafiki_tpu.serving.predictor", pred_cfg,
            ServiceType.PREDICTOR, wait_port_file=True,
            inference_job_id=inference_job_id)
        spawned.append(predictor)
        self.meta.update_inference_job(
            inference_job_id, status="RUNNING",
            predictor_host=f"{predictor.host}:{predictor.port}")
        # arm the scale-out state (routing pool + replica template +
        # autoscale policy when the budget asked for one) and publish
        # the initial membership for the predictor's router
        self._ensure_scaleout(inference_job_id)
        self._publish_pool(inference_job_id)
        return spawned

    # ---- lifecycle / failure detection ----
    def poll(self) -> None:
        """Reap exited children; release their slots; record status."""
        with self.op_lock:
            # op_lock serializes admin ops end-to-end; _poll's respawn
            # path waits on spawn port files by design (see
            # docs/linting.md "Admin op serialization")
            self._poll()  # rafiki: noqa[lock-order-cycle]

    def _poll(self) -> None:
        self._check_data_plane()
        if self._pending_respawns:
            still_pending: List[Dict[str, Any]] = []
            for item in self._pending_respawns:
                try:
                    if not self._respawn(item["dead_id"], item["spec"]):
                        still_pending.append(item)
                except Exception as e:  # noqa: BLE001 — keep polling,
                    import logging      # but never drop healing silently

                    logging.getLogger(__name__).warning(
                        "queued respawn for %s failed and was dropped: "
                        "%s", item["dead_id"], e)
                    mk = item["spec"]["meta_kwargs"]
                    self._mark_degraded(
                        item["spec"]["service_type"],
                        mk.get("train_job_id")
                        or mk.get("inference_job_id"),
                        f"queued respawn failed: {e}")
            self._pending_respawns = still_pending
        for svc in list(self.services.values()):
            if svc.alive():
                continue
            code = svc.proc.returncode
            status = (ServiceStatus.STOPPED if code == 0
                      else ServiceStatus.ERRORED)
            self.meta.update_service(svc.service_id, status=status)
            if svc.slot is not None:
                self.allocator.release(svc.slot)
                svc.slot = None
            spec = self._respawn_specs.pop(svc.service_id, None)
            del self.services[svc.service_id]
            if status == ServiceStatus.ERRORED and spec is not None:
                # self-healing: a CRASHED worker is replaced while its
                # job still runs (rc==0 = normal completion, no respawn).
                # Train-worker replacements then reclaim the dead
                # process's orphaned trial via the resume machinery.
                try:
                    if not self._respawn(svc.service_id, spec):
                        # no free slot this instant (a concurrent spawn
                        # may have snatched the released one): retry on
                        # subsequent polls rather than losing healing
                        self._pending_respawns.append(
                            {"dead_id": svc.service_id, "spec": spec})
                except Exception as e:  # noqa: BLE001 — the monitor loop
                    import logging     # must survive respawn failures

                    logging.getLogger(__name__).warning(
                        "respawn of %s failed: %s", svc.service_id, e)

    def _respawn(self, dead_service_id: str, spec: Dict[str, Any]) -> bool:
        """Spawn a replacement for a crashed worker. Returns True when
        the case is RESOLVED (respawned, or no longer needed); False =
        no free slot right now, caller should queue a retry."""
        meta_kwargs = spec["meta_kwargs"]
        job_id = meta_kwargs.get("train_job_id") or \
            meta_kwargs.get("inference_job_id")
        stype = spec["service_type"]
        if stype == ServiceType.TRAIN_WORKER:
            job = self.meta.get_train_job(job_id) if job_id else None
        else:
            job = self.meta.get_inference_job(job_id) if job_id else None
        if not job or job["status"] != "RUNNING":
            return True  # parent finished/stopped: nothing to heal
        lineage = (stype, job_id)
        if self._respawn_counts.get(lineage, 0) >= self.max_respawns:
            import logging

            logging.getLogger(__name__).warning(
                "respawn budget exhausted for %s job %s (last casualty "
                "%s) — a worker config appears to crash "
                "deterministically", stype, job_id, dead_service_id)
            # the drop is not just a log line: the job surfaces as
            # degraded on /health (and ERRORED in the store when it has
            # no workers left at all)
            self._mark_degraded(stype, job_id,
                                "respawn budget exhausted")
            return True
        slot = None
        if spec["needs_slot"]:
            slot = self.allocator.acquire(timeout=0.0)
            if slot is None:
                return False  # no free chips; caller queues a retry
        try:
            self._spawn(spec["module"], spec["config"], stype, slot=slot,
                        **meta_kwargs)
        except Exception:
            if slot is not None:
                self.allocator.release(slot)
            raise
        # write-through: the budget lives in the MetaStore so an admin
        # crash cannot reset it (a crash-looping worker config would
        # otherwise get a fresh budget per admin restart)
        try:
            self._respawn_counts[lineage] = \
                self.meta.incr_respawn_count(stype, job_id)
        except Exception:  # noqa: BLE001 — never lose healing to a
            # store hiccup; fall back to the in-memory count
            self._respawn_counts[lineage] = \
                self._respawn_counts.get(lineage, 0) + 1
        # healing worked: the job is no longer degraded (a stale flag
        # that survives recovery teaches operators to ignore it)
        self._degraded.pop(job_id, None)
        return True

    def _live_workers_of(self, stype: str, job_id: str
                         ) -> List[ManagedService]:
        """Still-alive workers of ``stype`` belonging to ``job_id``
        (caller holds op_lock or tolerates a snapshot)."""
        key = ("train_job_id" if stype == ServiceType.TRAIN_WORKER
               else "inference_job_id")
        out = []
        for sid, svc in self.services.items():
            if svc.service_type != stype or not svc.alive():
                continue
            spec = self._respawn_specs.get(sid)
            if spec and spec["meta_kwargs"].get(key) == job_id:
                out.append(svc)
        return out

    def _mark_degraded(self, stype: str, job_id: Optional[str],
                       reason: str) -> None:
        """Record a job whose self-healing is gone. With zero workers
        left the job is not degraded but DEAD — its store row flips to
        ERRORED so the dashboard's status column shows it."""
        if not job_id:
            return
        self._degraded[job_id] = reason
        if self._live_workers_of(stype, job_id):
            return  # under-replicated but still serving
        import logging

        try:
            if stype == ServiceType.TRAIN_WORKER:
                self.meta.update_train_job(job_id,
                                           status=TrainJobStatus.ERRORED)
            else:
                self.meta.update_inference_job(job_id, status="ERRORED")
        except Exception as e:  # noqa: BLE001 — a store hiccup must not
            # kill the monitor loop; the /health degraded list already
            # carries the signal
            logging.getLogger(__name__).warning(
                "could not mark job %s ERRORED: %s", job_id, e)

    def degraded_jobs(self) -> Dict[str, str]:
        """Jobs that lost self-healing (job id → reason), for /health.
        Jobs an operator has since STOPPED drop off the list (ERRORED
        ones stay — that verdict is the point of the flag)."""
        with self.op_lock:
            out = dict(self._degraded)
        for jid in list(out):
            job = self.meta.get_train_job(jid) or \
                self.meta.get_inference_job(jid)
            if job is not None and job.get("status") == "STOPPED":
                with self.op_lock:
                    self._degraded.pop(jid, None)
                del out[jid]
        return out

    def respawn_stats(self) -> Dict[str, int]:
        """Self-healing counters for /health (locked: the monitor thread
        mutates these dicts while HTTP threads read)."""
        with self.op_lock:
            return {"respawns_done": sum(self._respawn_counts.values()),
                    "pending_respawns": len(self._pending_respawns),
                    "degraded_jobs": len(self._degraded),
                    "rolling_restarts_done": self._rolling_restarts}

    # ---- graceful drain / rolling restart ----
    def _request_drain(self, config: Dict[str, Any]) -> bool:
        """Ask a worker to drain: POST /drain on its obs sidecar
        (discovered via the obs_port_file the worker wrote at boot),
        falling back to a ``{"control": "drain"}`` message on its query
        queue. Returns False when neither channel is available."""
        import logging

        from ..utils.http import json_request

        log = logging.getLogger(__name__)
        port_file = config.get("obs_port_file")
        if port_file:
            try:
                port = int(Path(port_file).read_text().strip())
                json_request("POST", f"http://127.0.0.1:{port}/drain",
                             {}, timeout=5.0)
                return True
            except Exception as e:  # noqa: BLE001 — the sidecar may be
                # gone with a hung worker; the queue channel still works
                log.warning("drain via obs sidecar failed (%s); "
                            "falling back to queue control message", e)
        wid = config.get("worker_id")
        if wid and self.kv_port:
            from ..serving.queues import KVQueueHub, pack_message

            KVQueueHub(self.kv_host, self.kv_port).push_query(
                wid, pack_message({"control": "drain"}))
            return True
        log.warning("no drain channel for worker config %r",
                    config.get("worker_id"))
        return False

    def rolling_restart(self, inference_job_id: str,
                        drain_timeout: float = 120.0
                        ) -> Dict[str, Any]:
        """Drain → stop → respawn each of a live inference job's
        workers ONE AT A TIME, so a deploy/restart never drops a
        stream: the draining worker finishes its in-flight requests
        (streams included) while the predictor's breaker board routes
        new traffic to its siblings; only then is it replaced. A worker
        that fails to drain within ``drain_timeout`` is terminated —
        the restart must converge even over a hung process. Returns the
        old→new service id pairs."""
        self._check_fence()
        if not self._rolling_lock.acquire(blocking=False):
            raise RuntimeError(
                "a rolling restart is already in progress — wait for "
                "it to finish (retrying a timed-out request would "
                "drain the fresh replacements)")
        try:
            return self._rolling_restart(inference_job_id,
                                         drain_timeout)
        finally:
            self._rolling_lock.release()

    def _rolling_restart(self, inference_job_id: str,
                         drain_timeout: float) -> Dict[str, Any]:
        with self.op_lock:
            targets = []
            for sid, svc in list(self.services.items()):
                if svc.service_type != ServiceType.INFERENCE_WORKER:
                    continue
                spec = self._respawn_specs.get(sid)
                if spec and spec["meta_kwargs"].get(
                        "inference_job_id") == inference_job_id:
                    targets.append((sid, svc, spec))
        if not targets:
            raise KeyError("no live inference workers for job "
                           f"{inference_job_id!r}")
        import logging

        log = logging.getLogger(__name__)
        restarted = []
        for sid, svc, spec in targets:
            with self.op_lock:
                # de-register crash healing for THIS worker only, at
                # its own turn: dying non-zero while draining (or the
                # terminate below) must not make the monitor respawn
                # it in parallel with the replacement spawned here —
                # while workers not yet reached keep their healing if
                # the restart aborts mid-way
                self._respawn_specs.pop(sid, None)
            drain_sent = self._request_drain(spec["config"])
            # wait OUTSIDE op_lock: the monitor thread must stay able
            # to poll (and the draining worker may take a while to
            # finish its streams). A worker that was never asked to
            # drain (no channel) gets a short grace, not the full
            # budget — waiting can't help it finish what it doesn't
            # know to finish.
            try:
                svc.proc.wait(timeout=drain_timeout if drain_sent
                              else min(5.0, drain_timeout))
            except subprocess.TimeoutExpired:
                log.warning(
                    "worker %s did not drain within %.0fs%s; "
                    "terminating", sid, drain_timeout,
                    "" if drain_sent else " (no drain channel)")
                svc.proc.terminate()
                try:
                    svc.proc.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    svc.proc.kill()
                    svc.proc.wait()
            with self.op_lock:
                if sid in self.services:  # the monitor may have reaped
                    # the rc=0 exit already (drain = clean completion)
                    self.meta.update_service(sid,
                                             status=ServiceStatus.STOPPED)
                    if svc.slot is not None:
                        self.allocator.release(svc.slot)
                        svc.slot = None
                    self._respawn_specs.pop(sid, None)
                    del self.services[sid]
                slot = None
                if spec["needs_slot"]:
                    slot = self.allocator.acquire(
                        timeout=self.slot_timeout)
                    if slot is None:
                        raise RuntimeError(
                            "no free device slot to respawn drained "
                            f"worker {sid} — rolling restart aborted "
                            "mid-way")
                try:
                    # rolling restart must hold op_lock across the
                    # spawn wait — releasing it mid-restart would let
                    # a concurrent scale op grab the vacated slot (see
                    # docs/linting.md "Admin op serialization")
                    new = self._spawn(spec["module"], spec["config"],  # rafiki: noqa[lock-order-cycle]
                                      spec["service_type"], slot=slot,
                                      **spec["meta_kwargs"])
                except Exception:
                    if slot is not None:
                        self.allocator.release(slot)
                    raise
                self._rolling_restarts += 1
                # a fresh healthy worker supersedes any degraded flag
                self._degraded.pop(inference_job_id, None)
            restarted.append({"old": sid, "new": new.service_id,
                              "drained": bool(drain_sent)})
        return {"job_id": inference_job_id, "restarted": restarted}

    # ---- horizontal scale-out / autoscaler ----
    #: floor between autoscale evaluations (the monitor ticks faster)
    AUTOSCALE_TICK_EVERY_S = 1.0
    #: a scaled-up worker joins the routing pool when its obs sidecar
    #: reports a port (boot + warmup complete) — or after this long
    #: regardless (the predictor's breakers gate a worker that still
    #: is not serving; membership must not hang on a lost port file)
    WARM_PUBLISH_TIMEOUT_S = 600.0

    def _pool_hub(self):
        """A cached KVQueueHub against the live data plane (worker
        stats reads + pool-membership publishes)."""
        from ..serving.queues import KVQueueHub

        key = (self.kv_host, self.kv_port)
        if self._pool_hub_cache is None or self._pool_hub_key != key:
            self._pool_hub_cache = KVQueueHub(self.kv_host, self.kv_port)
            self._pool_hub_key = key
        return self._pool_hub_cache

    @staticmethod
    def _wid_index(wid: str) -> int:
        """The numeric suffix of ``iw-<job8>-<n>`` worker ids (pool
        ordering + next-index recovery); -1 when unparseable."""
        try:
            return int(wid.rsplit("-", 1)[1])
        except (IndexError, ValueError):
            return -1

    def _ensure_scaleout(self, job_id: str) -> Optional[Dict[str, Any]]:
        """The job's scale-out state, rebuilt from live services + the
        job budget when missing (an adopted stack keeps scaling after
        an admin restart). None when the job has no live inference
        workers to derive a pool/template from."""
        with self.op_lock:
            st = self._scaleout.get(job_id)
            if st is not None:
                return st
            workers: List[Any] = []
            for sid, spec in self._respawn_specs.items():
                if spec["service_type"] != ServiceType.INFERENCE_WORKER:
                    continue
                if spec["meta_kwargs"].get("inference_job_id") != job_id:
                    continue
                wid = str(spec["config"].get("worker_id") or "")
                if wid:
                    workers.append((self._wid_index(wid), wid, spec))
            if not workers:
                return None
            workers.sort(key=lambda t: (t[0], t[1]))
            job = self.meta.get_inference_job(job_id)
            budget = (job or {}).get("budget") or {}
            policy = None
            trial_ids = {s["config"].get("trial_id")
                         for _, _, s in workers}
            try:
                cfg_as = AutoscaleConfig.from_budget(budget,
                                                     len(workers))
                if cfg_as is not None and len(trial_ids) > 1:
                    # an ensemble pool (distinct trials) must never be
                    # auto-scaled: clones would skew the gather and a
                    # shrink could evict a trial's only replica
                    raise ValueError(
                        "pool serves distinct trials (ensemble)")
                if cfg_as is not None:
                    policy = AutoscalePolicy(cfg_as)
            except ValueError as e:
                # validated at create; a rebuilt pool can disagree with
                # the budget bounds after manual scaling — run without
                # the policy rather than refuse to track the pool
                import logging

                logging.getLogger(__name__).warning(
                    "autoscaler for job %s disabled on rebuild: %s",
                    job_id, e)
            # replica template: prefer a SERVING worker's config — a
            # disaggregated job's worker 0 may be prefill-role, and a
            # scale-up cloning it would add capacity that never
            # answers queries (the autoscaler grows on serving
            # pressure). Fallback strips the role: a unified clone
            # serves either way.
            tmpl_cfg = next((dict(spec["config"])
                             for _i, _w, spec in workers
                             if spec["config"].get("role")
                             != "prefill"), None)
            if tmpl_cfg is None:
                tmpl_cfg = dict(workers[0][2]["config"])
                tmpl_cfg.pop("role", None)
            st = {"pool": [w for _, w, _ in workers],
                  "template": tmpl_cfg,
                  "module": workers[0][2]["module"],
                  "next_index": max(i for i, _, _ in workers) + 1,
                  "pool_version": 0.0, "policy": policy,
                  "warming": [], "victim": None,
                  "drain_timeout": 120.0}
            self._scaleout[job_id] = st
            return st

    def _publish_pool(self, job_id: str) -> None:
        """Write the job's routing-pool membership to the hub (the
        predictor's router applies the diff live). Version is a
        strictly increasing stamp so a late re-delivery can't roll the
        pool back."""
        with self.op_lock:
            st = self._scaleout.get(job_id)
            if st is None or not self.kv_port:
                return
            st["pool_version"] = max(time.time(),
                                     st["pool_version"] + 1e-4)
            members = {"workers": list(st["pool"]),
                       "version": st["pool_version"],
                       "published_at": time.time()}
        try:
            self._pool_hub().put_pool_members(job_id, members)
            self.scaling.inc("pool_publishes")
        except Exception:  # noqa: BLE001 — the hub may be mid-restart;
            # the next scale event (or tick) republishes
            import logging

            logging.getLogger(__name__).warning(
                "pool membership publish failed for job %s", job_id,
                exc_info=True)

    def _worker_sid(self, job_id: str, wid: str) -> Optional[str]:
        """service id of the job's worker ``wid`` (caller holds
        op_lock or tolerates a snapshot)."""
        for sid, spec in self._respawn_specs.items():
            if spec["service_type"] != ServiceType.INFERENCE_WORKER:
                continue
            if spec["meta_kwargs"].get("inference_job_id") != job_id:
                continue
            if spec["config"].get("worker_id") == wid:
                return sid
        return None

    def _scale_up_one(self, job_id: str,
                      slot_timeout: float) -> Optional[str]:
        """Spawn one extra replica from the job's template. The new
        worker starts WARMING: it joins the routing pool (and the
        published membership) only once its obs sidecar reports a port
        — a worker mid-compile must not attract streams. Returns the
        new worker id, or None when no device slot was free."""
        with self.op_lock:
            self._check_fence()
            if self._scaleout.get(job_id) is None:
                raise KeyError(f"no scale-out state for job {job_id!r}")
        # acquire the slot OUTSIDE op_lock: every release path (monitor
        # poll, stop_service, a draining victim's reap) needs that
        # lock, so blocking on the allocator while holding it could
        # never be satisfied by a concurrent release — the same
        # invariant create_inference_services documents
        slot = self.allocator.acquire(timeout=slot_timeout)
        if slot is None:
            return None
        with self.op_lock:
            st = self._scaleout.get(job_id)
            if st is None:  # job stopped between the locks
                self.allocator.release(slot)
                return None
            idx = st["next_index"]
            st["next_index"] += 1
            wid = f"iw-{job_id[:8]}-{idx}"
            cfg = dict(st["template"])
            cfg["worker_id"] = wid
            port_file = self.workdir / f"{wid}.obs_port"
            cfg["obs_port_file"] = str(port_file)
            try:
                port_file.unlink()  # a stale file from a previous life
            except OSError:         # must not instantly promote
                pass
            try:
                # scale-up holds op_lock across the spawn wait so the
                # claimed slot cannot be double-assigned (see
                # docs/linting.md "Admin op serialization")
                self._spawn(st["module"], cfg,  # rafiki: noqa[lock-order-cycle]
                            ServiceType.INFERENCE_WORKER, slot=slot,
                            inference_job_id=job_id)
            except Exception:
                self.allocator.release(slot)
                raise
            st["warming"].append({"wid": wid,
                                  "port_file": str(port_file),
                                  "since": time.monotonic()})
            self.scaling.inc("autoscale_ups")
            return wid

    def _promote_warmed(self, job_id: str,
                        st: Dict[str, Any]) -> None:
        """Move warmed-up replicas (obs port reported) into the routing
        pool and publish the new membership."""
        changed = False
        with self.op_lock:
            for item in list(st["warming"]):
                ready = Path(item["port_file"]).exists()
                timed_out = (time.monotonic() - item["since"]
                             > self.WARM_PUBLISH_TIMEOUT_S)
                if not ready and not timed_out:
                    continue
                st["warming"].remove(item)
                if item["wid"] not in st["pool"]:
                    st["pool"].append(item["wid"])
                changed = True
        if changed:
            self._publish_pool(job_id)

    def _begin_scale_down(self, job_id: str, wid: str) -> bool:
        """Start a drain-based scale-down of ``wid``: membership FIRST
        (the predictor stops routing there and fails over its streams
        with forced prefixes), then the graceful-drain request; the
        victim finishes in-flight work and exits 0 (reaped by the
        monitor). Crash-healing for the victim is de-registered so a
        non-zero exit while draining is not respawned."""
        with self.op_lock:
            st = self._scaleout.get(job_id)
            if st is None or st.get("victim"):
                return False
            if wid in st["pool"]:
                st["pool"].remove(wid)
            sid = self._worker_sid(job_id, wid)
            spec = self._respawn_specs.pop(sid, None) if sid else None
            cfg = dict((spec or {}).get("config") or {})
            if sid is not None and sid in self.services:
                st["victim"] = {"sid": sid, "wid": wid, "cfg": cfg,
                                "deadline": time.monotonic()
                                + st["drain_timeout"]}
        self._publish_pool(job_id)
        with self.op_lock:
            st = self._scaleout.get(job_id)
            victim = (st or {}).get("victim")
        if not victim:
            return False  # worker already gone: the pool just shrank
        self._request_drain(victim["cfg"])
        self.scaling.inc("autoscale_downs")
        return True

    def _victim_tick(self, job_id: str, st: Dict[str, Any]) -> None:
        """Advance an in-flight scale-down: a cleanly drained victim is
        reaped by the monitor poll (rc=0 → STOPPED, slot released); one
        that blows its drain deadline is terminated — a stuck scale-
        down must converge, not wedge the autoscaler forever."""
        with self.op_lock:
            v = st.get("victim")
            if not v:
                return
            if v["sid"] not in self.services:
                st["victim"] = None  # drained + reaped: done
                return
            overdue = time.monotonic() > v["deadline"]
        if overdue:
            import logging

            logging.getLogger(__name__).warning(
                "scale-down victim %s did not drain in time; "
                "terminating", v["wid"])
            self.stop_service(v["sid"])
            with self.op_lock:
                st["victim"] = None

    @staticmethod
    def _choose_victim(st: Dict[str, Any],
                       stats: Dict[str, Any]) -> Optional[str]:
        """Scale-down victim: the member with the fewest live KV pages
        (least in-flight state to fail over), ties to the most recently
        added — the pool shrinks newest-first by default.

        Prefill-role workers are never autoscale victims: the
        autoscaler manages SERVING capacity, and a prefill worker's
        near-zero page count would otherwise make it the first pick
        every time — silently destroying a tier the operator
        explicitly provisioned (scale-ups clone the serving
        template, so it would never come back)."""
        pool = []
        for w in st["pool"]:
            s = stats.get(w)
            if not (isinstance(s, dict) and s.get("role") == "prefill"):
                pool.append(w)
        if len(pool) <= 1:
            return None

        def pages(wid: str) -> float:
            s = stats.get(wid)
            if not isinstance(s, dict):
                return float("inf")
            v = s.get("engine_kv_pages_used", s.get("kv_pages_used"))
            return float(v) if isinstance(v, (int, float)) else \
                float("inf")

        return min(pool, key=lambda w: (pages(w), -pool.index(w)))

    def autoscale_tick(self, force: bool = False) -> List[Dict[str, Any]]:
        """One autoscaler evaluation (called from the admin monitor
        loop; self-rate-limited). Grows a job's pool on sustained
        admission stalls, shrinks it through the drain path when idle;
        promotes warmed replicas into the routing pool and converges
        stuck drains. Returns the actions taken (for tests/logs)."""
        actions: List[Dict[str, Any]] = []
        if self.fenced or not self.kv_port:
            return actions
        now = time.monotonic()
        if not force and now - self._last_autoscale_tick < \
                self.AUTOSCALE_TICK_EVERY_S:
            return actions
        self._last_autoscale_tick = now
        with self.op_lock:
            job_ids = set(self._scaleout)
            for spec in self._respawn_specs.values():
                if spec["service_type"] == ServiceType.INFERENCE_WORKER:
                    jid = spec["meta_kwargs"].get("inference_job_id")
                    if jid:
                        job_ids.add(jid)
        for job_id in sorted(job_ids):
            job = self.meta.get_inference_job(job_id)
            if job is None or job.get("status") != "RUNNING":
                with self.op_lock:
                    self._scaleout.pop(job_id, None)
                continue
            st = self._ensure_scaleout(job_id)
            if st is None:
                continue
            self._promote_warmed(job_id, st)
            self._victim_tick(job_id, st)
            with self.op_lock:
                policy = st.get("policy")
                busy = bool(st.get("victim") or st.get("warming")
                            or st.get("manual"))
                pool = list(st["pool"])
            if policy is None or busy:
                # no policy, or a previous action / an operator's
                # manual scale still converging — decisions wait until
                # the pool is quiescent (the policy must never fight
                # an in-flight operation)
                continue
            stats: Dict[str, Any] = {}
            for wid in pool:
                try:
                    stats[wid] = self._pool_hub().get_worker_stats(wid)
                except Exception:  # rafiki: noqa[silent-except] — a
                    stats[wid] = None  # hub hiccup reads as missing
            decision = policy.observe(stats)
            if decision == "up":
                try:
                    wid = self._scale_up_one(job_id, slot_timeout=0.0)
                except Exception as e:  # noqa: BLE001 — a failed spawn
                    # must not kill the monitor loop
                    import logging

                    logging.getLogger(__name__).warning(
                        "autoscale-up spawn for job %s failed: %s",
                        job_id, e)
                    wid = None
                if wid is None:
                    self.scaling.inc("autoscale_blocked")
                    actions.append({"job_id": job_id,
                                    "action": "blocked"})
                else:
                    actions.append({"job_id": job_id, "action": "up",
                                    "worker": wid})
            elif decision == "down":
                victim = self._choose_victim(st, stats)
                if victim and self._begin_scale_down(job_id, victim):
                    actions.append({"job_id": job_id, "action": "down",
                                    "worker": victim})
        return actions

    def scale_inference_job(self, job_id: str, workers: int,
                            drain_timeout: float = 120.0,
                            warm_timeout: float = 180.0
                            ) -> Dict[str, Any]:
        """Manual scale to an exact replica count (the operator's
        override; also stamps the autoscaler cooldown so the policy
        doesn't immediately fight the operator). Ups spawn from the
        job's template and block until the new workers report their
        obs port (joined the routing pool); downs drain newest-first,
        one at a time, and block until each victim exits."""
        self._check_fence()
        workers = int(workers)
        if workers < 1:
            raise ValueError(f"workers={workers} must be >= 1")
        st = self._ensure_scaleout(job_id)
        if st is None:
            raise KeyError(
                f"no live inference workers for job {job_id!r}")
        with self.op_lock:
            if st.get("manual"):
                raise RuntimeError(
                    f"a manual scale of job {job_id} is already in "
                    "progress — wait for it to finish")
            if len(self._pool_trial_ids(job_id, st)) > 1:
                raise RuntimeError(
                    f"job {job_id}'s replicas serve DISTINCT trials "
                    "(an ensemble) — scaling would clone one trial "
                    "and skew/evict the others; redeploy with "
                    "max_workers=1 (or MULTI_ADAPTER) to scale")
            # the busy flag + an up-front cooldown stamp keep the
            # autoscaler's tick out while this (possibly minutes-long,
            # drain-blocking) operation runs — the policy must not
            # undo the operator's target mid-flight
            st["manual"] = True
            policy = st.get("policy")
        if policy is not None:
            policy.note_action()
        try:
            return self._scale_to(job_id, st, workers, drain_timeout,
                                  warm_timeout)
        finally:
            with self.op_lock:
                st["manual"] = False
            if policy is not None:
                policy.note_action()  # cooldown runs from COMPLETION

    def _pool_trial_ids(self, job_id: str,
                        st: Dict[str, Any]) -> set:
        """Distinct ``trial_id`` values across the pool's worker
        configs (caller holds op_lock). More than one means the job is
        a cross-trial ensemble — cloning its template would double-
        weight one trial in the unary gather and a scale-down could
        evict another trial's only replica."""
        out = set()
        for wid in st["pool"]:
            sid = self._worker_sid(job_id, wid)
            spec = self._respawn_specs.get(sid) if sid else None
            out.add((spec or {}).get("config", {}).get("trial_id"))
        return out

    def _scale_to(self, job_id: str, st: Dict[str, Any], workers: int,
                  drain_timeout: float,
                  warm_timeout: float) -> Dict[str, Any]:
        result: Dict[str, Any] = {"job_id": job_id, "scaled_up": [],
                                  "scaled_down": []}
        with self.op_lock:
            current = len(st["pool"]) + len(st["warming"])
        while current < workers:
            wid = self._scale_up_one(job_id,
                                     slot_timeout=self.slot_timeout)
            if wid is None:
                raise RuntimeError(
                    f"no free device slot to scale job {job_id} to "
                    f"{workers} workers ({self.allocator.n_slots} "
                    f"slots, {self.allocator.free_count()} free)")
            result["scaled_up"].append(wid)
            current += 1
        deadline = time.monotonic() + warm_timeout
        while time.monotonic() < deadline:
            self._promote_warmed(job_id, st)
            with self.op_lock:
                if not st["warming"]:
                    break
            time.sleep(0.05)
        with self.op_lock:
            # blown warm deadline: publish anyway — the breakers gate a
            # worker that still is not serving
            for item in list(st["warming"]):
                st["warming"].remove(item)
                if item["wid"] not in st["pool"]:
                    st["pool"].append(item["wid"])
        self._publish_pool(job_id)
        while True:
            with self.op_lock:
                if len(st["pool"]) <= workers:
                    break
                victim = st["pool"][-1]
            self._scale_down_blocking(job_id, victim, drain_timeout)
            result["scaled_down"].append(victim)
        with self.op_lock:
            result["pool"] = list(st["pool"])
        return result

    def _scale_down_blocking(self, job_id: str, wid: str,
                             drain_timeout: float) -> None:
        """Manual-path scale-down: membership first, then drain, then
        wait for the exit (terminate on a blown deadline) — mirrors
        rolling_restart's reap-or-terminate contract."""
        with self.op_lock:
            st = self._scaleout.get(job_id)
            if st is None:
                return
            if wid in st["pool"]:
                st["pool"].remove(wid)
            sid = self._worker_sid(job_id, wid)
            spec = self._respawn_specs.pop(sid, None) if sid else None
            svc = self.services.get(sid) if sid else None
        self._publish_pool(job_id)
        if svc is None:
            return
        drain_sent = self._request_drain(
            dict((spec or {}).get("config") or {}))
        try:
            svc.proc.wait(timeout=drain_timeout if drain_sent
                          else min(5.0, drain_timeout))
        except subprocess.TimeoutExpired:
            import logging

            logging.getLogger(__name__).warning(
                "scale-down victim %s did not drain within %.0fs; "
                "terminating", wid, drain_timeout)
            svc.proc.terminate()
            try:
                svc.proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                svc.proc.kill()
                svc.proc.wait()
        with self.op_lock:
            if sid in self.services:  # the monitor may have reaped the
                # clean rc=0 exit already
                self.meta.update_service(sid,
                                         status=ServiceStatus.STOPPED)
                if svc.slot is not None:
                    self.allocator.release(svc.slot)
                    svc.slot = None
                del self.services[sid]
        self.scaling.inc("autoscale_downs")

    def scaleout_status(self, job_id: str) -> Dict[str, Any]:
        """Pool + autoscaler state for the admin API/dashboard."""
        with self.op_lock:
            st = self._scaleout.get(job_id)
            if st is None:
                return {"enabled": False, "pool": [], "warming": [],
                        "victim": None}
            policy = st.get("policy")
            out = {"enabled": policy is not None,
                   "pool": list(st["pool"]),
                   "warming": [w["wid"] for w in st["warming"]],
                   "victim": (st.get("victim") or {}).get("wid"),
                   "drain_timeout_s": st["drain_timeout"]}
        if policy is not None:
            out.update(policy.status())
        return out

    def pending_respawn_job_ids(self) -> set:
        """Jobs that currently have a queued (slot-starved) worker
        respawn — they must count as busy, or the finalizers declare
        them done and the queued healing is dropped."""
        with self.op_lock:
            out = set()
            for item in self._pending_respawns:
                mk = item["spec"]["meta_kwargs"]
                jid = mk.get("train_job_id") or mk.get("inference_job_id")
                if jid:
                    out.add(jid)
            return out

    def stop_service(self, service_id: str, timeout: float = 10.0) -> None:
        self._check_fence()
        with self.op_lock:
            self._stop_service(service_id, timeout)

    def _stop_service(self, service_id: str, timeout: float) -> None:
        svc = self.services.get(service_id)
        if svc is None:
            return
        if svc.alive():
            svc.proc.terminate()
            try:
                svc.proc.wait(timeout=timeout)
            except subprocess.TimeoutExpired:
                svc.proc.kill()
                svc.proc.wait()
        self.meta.update_service(service_id, status=ServiceStatus.STOPPED)
        if svc.slot is not None:
            self.allocator.release(svc.slot)
            svc.slot = None
        self._respawn_specs.pop(service_id, None)
        del self.services[service_id]

    def _drop_handles(self) -> None:
        """Fenced shutdown: the children (and their MetaStore rows) now
        belong to the admin that took the lease over — killing them
        would tear down the NEW admin's adopted stack. Release only our
        local bookkeeping."""
        for sid, svc in list(self.services.items()):
            if svc.slot is not None:
                try:
                    self.allocator.release(svc.slot)
                except ValueError:
                    pass
                svc.slot = None
            self._respawn_specs.pop(sid, None)
            del self.services[sid]
        self._kv_proc = None
        self.kv_host, self.kv_port = "", 0

    def stop_all(self) -> None:
        if self.fenced:
            self._drop_handles()
            return
        for sid in list(self.services):
            with self.op_lock:
                self._stop_service(sid, timeout=10.0)
        if self._kv_proc is not None and self._kv_server is not None:
            self._kv_server.stop()
            self._kv_proc = None
            self.kv_host, self.kv_port = "", 0
            if getattr(self, "_kv_service_id", None):
                self.meta.update_service(self._kv_service_id,
                                         status=ServiceStatus.STOPPED)
        self.release_lease()


class _DeadProc:
    """Popen-shaped placeholder for a kvd the reconciler found DEAD
    (row present, process gone): gives the respawn path a non-None,
    already-exited handle so data-plane supervision state stays
    uniform."""

    pid = 0
    returncode = -1

    def poll(self) -> int:
        return self.returncode


class _AdoptedKVServer:
    """KVServer-shaped handle over a rafiki-kvd the reconciler adopted
    (same ``host``/``port``/``_proc``/``stop()`` surface as
    :class:`rafiki_tpu.native.client.KVServer`)."""

    def __init__(self, host: str, port: int,
                 proc: AdoptedProcess) -> None:
        self.host, self.port = host, port
        self._proc = proc

    def stop(self) -> None:
        from ..native.client import KVClient

        try:
            KVClient(self.host, self.port).shutdown()
        except OSError:
            pass
        try:
            self._proc.wait(timeout=5)
        except subprocess.TimeoutExpired:
            self._proc.kill()
