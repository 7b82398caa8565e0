"""Node manager: worker pool + local dispatch for one (possibly simulated) node.

Capability parity with the reference's raylet
(reference: src/ray/raylet/node_manager.h:133 NodeManager;
worker_pool.h:280 WorkerPool with prestart and reuse;
local_lease_manager.cc:121 local dispatch). Each Node owns a unix-socket
listener, a pool of worker subprocesses, and the node's shared-memory
object store arena. The cluster test harness
(ray_tpu/core/cluster_utils.py) runs several Nodes in one head process
to simulate a multi-host TPU pod on a dev box — the same pattern as the
reference's Cluster (reference: python/ray/cluster_utils.py:135).
"""

from __future__ import annotations

import logging
import os
import socket
import subprocess
import sys
import tempfile
import threading
import time
from collections import deque
from typing import Any, Deque, Dict, List, Optional, Tuple

from ray_tpu.core import serialization
from ray_tpu.core import task_phase as _task_phase
from ray_tpu.core.config import get_config
from ray_tpu.core.ids import ActorID, NodeID, ObjectID, TaskID, WorkerID
from ray_tpu.core.object_store import SharedMemoryStore
from ray_tpu.core.protocol import MessageConnection
from ray_tpu.core.task_spec import TaskSpec
from ray_tpu.devtools import threadguard

logger = logging.getLogger(__name__)

# how long stop() waits for a worker that was told to go to be gone
_REAP_TIMEOUT_S = 60.0

# Worker states
STARTING = "STARTING"
IDLE = "IDLE"
BUSY = "BUSY"
ACTOR = "ACTOR"
DEAD = "DEAD"


def compile_cache_dir(env: Dict[str, str], checkout: str) -> str:
    """Where a chip-owning worker keeps JAX's persistent compile cache
    (jax reads JAX_COMPILATION_CACHE_DIR itself; nothing else in the
    tree configures a cache): the directory the variable already names,
    else ``<checkout>/.jax_cache``. The path is part of the cache key,
    so it is fixed: no temp name, pid or time."""
    return (env.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(checkout, ".jax_cache"))


class WorkerHandle:
    def __init__(self, worker_id: WorkerID, proc: subprocess.Popen,
                 profile: str = "cpu"):
        self.worker_id = worker_id
        self.proc = proc
        self.profile = profile  # "cpu" | "tpu:<k>" — see _spawn_worker
        self.chips: List[int] = []  # TPU chips this worker owns
        self.conn: Optional[MessageConnection] = None
        self.state = STARTING
        self.actor_id: Optional[ActorID] = None
        self.running: Dict[TaskID, TaskSpec] = {}
        self.registered = threading.Event()
        # objects this worker holds borrowed refs to (pinned at owner)
        self.held_refs: set = set()
        # outstanding blocking requests (get/wait/stream-next) — a
        # blocked worker doesn't count toward the pool cap, or nested
        # submission would deadlock (reference: workers blocked in
        # ray.get release their CPU resource)
        self.blocked_requests = 0
        self.node: Optional["Node"] = None

    def send(self, msg: dict) -> bool:
        conn = self.conn
        if conn is None or self.state == DEAD:
            return False
        try:
            conn.send(msg)
            return True
        except OSError:
            return False


class Node:
    proto_minor = 0  # in-process nodes share the head's schema

    def __init__(self, runtime, node_id: NodeID, resources: Dict[str, float],
                 labels: Optional[Dict[str, str]] = None,
                 object_store_memory: Optional[int] = None,
                 session_dir: Optional[str] = None):
        cfg = get_config()
        self.runtime = runtime
        self.node_id = node_id
        self.resources = dict(resources)
        self.labels = dict(labels or {})
        self.session_dir = session_dir or tempfile.mkdtemp(prefix="rtpu_")
        self.socket_path = os.path.join(
            self.session_dir, f"node_{node_id.hex()[:8]}.sock")
        self.store_name = f"rtpu_{node_id.hex()[:16]}"
        self.store = SharedMemoryStore(
            self.store_name,
            size=object_store_memory or cfg.object_store_memory,
            create=True)
        self._lock = threading.RLock()
        self._workers: Dict[WorkerID, WorkerHandle] = {}
        # workers whose connection closed before their process was
        # gone (a killed chip owner takes seconds): stop() waits for
        # these too
        self._dying: List[WorkerHandle] = []
        # Separate pools per worker profile: "cpu" workers start with the
        # accelerator runtime masked out (fast startup, no chip
        # contention); "tpu:<k>" workers own k specific chips from the
        # node's chip pool, exported via TPU_VISIBLE_CHIPS + bounds env
        # vars ("tpu:0" = fractional request, shares all chips). This is
        # the reference's per-worker accelerator-visibility plumbing
        # (reference: _private/accelerators/tpu.py:283 TPU_VISIBLE_CHIPS)
        # applied at process-pool level.
        from collections import defaultdict
        self._idle: Dict[str, Deque[WorkerHandle]] = defaultdict(deque)
        self._dispatch_queue: Dict[str, Deque[TaskSpec]] = defaultdict(deque)
        # runtime_env_hash → normalized env dict, registered on first
        # dispatch of a spec carrying that env (ray_tpu/runtime_env/)
        self._runtime_envs: Dict[str, dict] = {}
        self._free_chips: List[int] = list(
            range(int(self.resources.get("TPU", 0))))
        self._total_chips = len(self._free_chips)
        # per-profile pool counters (avoid scanning _workers per dispatch)
        self._n_starting: Dict[str, int] = {}
        self._n_live: Dict[str, int] = {}
        self._n_blocked: Dict[str, int] = {}
        self._stopped = threading.Event()
        self._listener = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        self._listener.bind(self.socket_path)
        self._listener.listen(128)
        # Worker connections ride the process-wide selector IO loop:
        # thread-per-worker reader loops anti-scale under the GIL (the
        # reference's raylet is similarly a single asio event loop,
        # src/ray/common/asio/), and one shared loop also covers the
        # head/client/object-transfer sockets (io_loop.py).
        from ray_tpu.core.io_loop import get_io_loop
        self._io = get_io_loop()
        self._listener_handle = self._io.register_listener(
            self._listener, self._on_worker_accept,
            label=f"node-{node_id.hex()[:6]}")
        self.prestart_workers(get_config().min_idle_workers)

    # --- worker pool ---------------------------------------------------
    def _allocate_chips(self, count: int) -> Optional[List[int]]:
        """Take `count` chips from the pool (under self._lock); None if
        the pool is short (the caller reclaims idle TPU workers)."""
        if count <= 0:
            return []
        if len(self._free_chips) < count:
            return None
        taken, self._free_chips = (self._free_chips[:count],
                                   self._free_chips[count:])
        return taken

    def _spawn_worker(self, profile: str = "cpu") -> Optional[WorkerHandle]:
        worker_id = WorkerID.from_random()
        pkg_parent = os.path.dirname(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        env = dict(os.environ)
        env["PYTHONPATH"] = pkg_parent + os.pathsep + env.get("PYTHONPATH", "")
        chips: List[int] = []
        image_uri = None
        hw_profile, _, renv_part = profile.partition("|")
        if renv_part:
            renv = self._runtime_envs.get(renv_part[3:])  # strip "re:"
            if renv is not None:
                import json
                env["RTPU_RUNTIME_ENV"] = json.dumps(renv)
                image_uri = renv.get("image_uri")
        if hw_profile == "cpu":
            # Mask the accelerator: no TPU runtime import (which costs
            # seconds per process and can contend for chips), and any jax
            # the user code imports runs on CPU.
            env["JAX_PLATFORMS"] = "cpu"
            env["TPU_VISIBLE_CHIPS"] = ""
        else:
            env["JAX_COMPILATION_CACHE_DIR"] = compile_cache_dir(
                env, pkg_parent)
            # "tpu:<k>": the worker owns k chips, exported to the TPU
            # runtime via TPU_VISIBLE_CHIPS + bounds vars (reference:
            # tpu.py:283-323). k=0 (fractional TPU request) shares the
            # full host.
            need = (int(hw_profile.split(":", 1)[1])
                    if ":" in hw_profile else 0)
            with self._lock:
                allocated = self._allocate_chips(need)
                victim = None
                if allocated is None:
                    # Reclaim chips hoarded by idle TPU workers (prefer
                    # actual chip holders — killing a chipless tpu:0
                    # worker frees nothing); retry happens when the
                    # death returns chips to the pool.
                    for p, idle in self._idle.items():
                        if (p.startswith("tpu") and idle
                                and idle[0].chips):
                            victim = idle.popleft()
                            break
                    if victim is None:
                        for p, idle in self._idle.items():
                            if p.startswith("tpu") and idle:
                                victim = idle.popleft()
                                break
            if allocated is None:
                if victim is not None:
                    self.kill_worker(victim.worker_id)
                return None
            chips = allocated
            if chips:
                from ray_tpu.accelerators.tpu import TpuAcceleratorManager
                for key, value in TpuAcceleratorManager.visible_chip_env(
                        chips, self._total_chips).items():
                    if value is None:
                        env.pop(key, None)
                    else:
                        env[key] = value
        # Workers write stdout+stderr to a per-worker session log file
        # (reference: workers log under the session dir; log_monitor.py
        # tails and streams to the driver). The dashboard serves these
        # via /api/logs; PYTHONUNBUFFERED so lines appear as printed.
        env["PYTHONUNBUFFERED"] = "1"
        log_dir = os.path.join(self.session_dir, "logs")
        os.makedirs(log_dir, exist_ok=True)
        log_path = os.path.join(log_dir,
                                f"worker-{worker_id.hex()[:8]}.log")
        env["RTPU_WORKER_LOG"] = log_path  # worker self-rotates at cap
        cmd = [sys.executable, "-m", "ray_tpu.core.worker",
               "--socket", self.socket_path,
               "--node-id", self.node_id.hex(),
               "--worker-id", worker_id.hex(),
               "--store-name", self.store_name]
        if chips:
            # the worker waits for these, should a dying process of an
            # earlier runtime still hold them (tpu.wait_for_chips)
            cmd += ["--chips", ",".join(str(c) for c in chips)]
        if image_uri:
            # Containerized worker (reference: _private/runtime_env/
            # image_uri.py:24 — podman-run with host net/IPC so the
            # unix socket + shm arena pass through; session/cache/src
            # dirs mounted).
            from ray_tpu.runtime_env.container import (
                container_worker_command)
            from ray_tpu.runtime_env.packaging import cache_root
            sock_dir = os.path.dirname(self.socket_path)
            mounts = [f"{self.session_dir}:{self.session_dir}",
                      f"{cache_root()}:{cache_root()}",
                      f"{pkg_parent}:{pkg_parent}:ro"]
            if os.path.commonpath(
                    [sock_dir, self.session_dir]) != self.session_dir:
                mounts.append(f"{sock_dir}:{sock_dir}")
            if chips or hw_profile.startswith("tpu"):
                # TPU device nodes must be mapped explicitly — host
                # net/IPC do not expose /dev (reference: image_uri
                # worker flags for accelerator access).
                import glob as _glob
                devices = _glob.glob("/dev/accel*")
                if os.path.exists("/dev/vfio"):
                    devices.append("/dev/vfio")
            else:
                devices = []
            try:
                cmd = container_worker_command(image_uri, cmd, env,
                                               mounts=mounts,
                                               devices=devices)
            except RuntimeError as exc:
                # No container runtime on this node: launch plain and
                # let the worker surface RuntimeEnvSetupError to the
                # requesting task (same path as pip failures) instead
                # of stranding the spec in the dispatch queue.
                env["RTPU_PIP_ERROR"] = repr(exc)
        # Deliberate GL009 exception: worker spawn is reachable from
        # loop-thread dispatch paths (_pump / _on_worker_death), but
        # deferring it would break the synchronous _n_starting
        # accounting that gates spawn decisions (two queued REGISTERs
        # would both spawn). Popen is one bounded fork+exec; the
        # threadguard stall watchdog flags it if it ever degrades.
        with open(log_path, "ab") as log_file:
            proc = subprocess.Popen(  # graftlint: disable=GL009
                cmd,
                env=env,
                stdout=log_file,
                stderr=subprocess.STDOUT,
            )
        handle = WorkerHandle(worker_id, proc, profile)
        handle.chips = chips
        handle.node = self
        with self._lock:
            self._workers[worker_id] = handle
            self._n_starting[profile] = self._n_starting.get(profile, 0) + 1
            self._n_live[profile] = self._n_live.get(profile, 0) + 1
        self._emit_worker_event("WORKER_STARTED", "DEBUG", worker_id,
                                profile)
        return handle

    def _emit_worker_event(self, kind: str, severity: str, worker_id,
                           message: str, caused_by=None):
        """Worker lifecycle event, driver-side only: on a remote node
        daemon ``self.runtime`` is the HeadProxy (no GCS) — worker
        crashes are forwarded as WORKER_CRASHED_FWD and narrated by the
        head's on_worker_crashed fallback instead."""
        gcs = getattr(self.runtime, "gcs", None)
        if gcs is None:
            return None
        return gcs.add_cluster_event(kind, severity,
                                     node_id=self.node_id,
                                     worker_id=worker_id,
                                     caused_by=caused_by,
                                     message=message)

    def prestart_workers(self, count: int, profile: str = "cpu") -> None:
        """Warm the pool (reference: worker_pool.h prestart)."""
        for _ in range(count):
            self._spawn_worker(profile)

    def _profile_for(self, spec: TaskSpec) -> str:
        amount = 0.0
        for key, value in spec.resources.items():
            if value > 0 and (key == "TPU" or key.startswith("TPU_group")):
                amount = max(amount, value)
        if amount <= 0:
            base = "cpu"
        elif amount < 1:
            base = "tpu:0"  # fractional request: shares the full host
        else:
            import math
            base = f"tpu:{int(math.ceil(amount))}"
        if spec.runtime_env_hash:
            # Workers with a runtime env form their own sub-pool: a
            # default worker must never execute inside someone else's
            # env, nor vice versa (reference: dedicated workers per
            # runtime_env in worker_pool.cc).
            with self._lock:
                self._runtime_envs.setdefault(
                    spec.runtime_env_hash, spec.runtime_env)
            return f"{base}|re:{spec.runtime_env_hash}"
        return base

    @threadguard.loop_only
    def _on_worker_accept(self, sock, _addr) -> None:
        """Runs on the IO loop thread for each worker that dials the
        node's unix socket. ``holder`` threads the WorkerHandle from
        the REGISTER message into later frames and the close hook."""
        holder = [None]

        def on_msg(conn, msg):
            try:
                holder[0] = self._handle_worker_msg(conn, holder[0], msg)
            except Exception:  # noqa: BLE001 — keep the connection alive
                import traceback
                traceback.print_exc()

        def on_close(conn):
            # Post-stop EOFs are expected (workers exiting on SHUTDOWN);
            # don't drive the death path during teardown.
            if holder[0] is not None and not self._stopped.is_set():
                self._on_worker_death(holder[0])

        self._io.register_message_conn(sock, on_msg, on_close,
                                       label="node-worker")

    def _handle_worker_msg(self, conn: MessageConnection,
                           handle: Optional[WorkerHandle],
                           msg: dict) -> Optional[WorkerHandle]:
            kind = msg["kind"]
            if kind == "REGISTER":
                from ray_tpu.core.protocol import PROTOCOL_VERSION
                peer_version = msg.get("proto_version", 0)
                if peer_version != PROTOCOL_VERSION:
                    # version skew (e.g. a stale worker binary): reject
                    # cleanly instead of failing on message shapes later
                    conn.send({"kind": "SHUTDOWN",
                               "reason": f"protocol version mismatch: "
                                         f"head={PROTOCOL_VERSION} "
                                         f"worker={peer_version}"})
                    return handle
                worker_id = WorkerID(msg["worker_id"])
                with self._lock:
                    handle = self._workers.get(worker_id)
                    if handle is None:  # externally started worker
                        handle = WorkerHandle(worker_id, None)
                        handle.node = self
                        self._workers[worker_id] = handle
                        self._n_live[handle.profile] = \
                            self._n_live.get(handle.profile, 0) + 1
                    else:
                        self._n_starting[handle.profile] = max(
                            0, self._n_starting.get(handle.profile, 0) - 1)
                    handle.conn = conn
                    handle.state = IDLE
                    self._idle[handle.profile].append(handle)
                handle.registered.set()
                self._pump()
            elif handle is None:
                # unregistered (or version-rejected) connection: ignore
                # everything but REGISTER — handlers dereference handle
                return handle
            elif kind == "TASK_DONE":
                self._on_task_done(handle, msg)
            elif kind == "TASK_DONE_BATCH":
                self._on_task_batch_done(handle, msg)
            elif kind == "RETURN_SPECS":
                # the worker is blocking: it hands queued specs back for
                # re-dispatch elsewhere
                self._on_specs_returned(handle, msg)
            elif kind == "BLOCKED":
                # the worker reports it is blocking on an object: take
                # it out of the pool-cap accounting so queued work can
                # still spawn replacements (nested submit+get)
                if handle is not None:
                    self._mark_blocked(handle)
            elif kind == "UNBLOCKED":
                if handle is not None:
                    self._mark_unblocked(handle)
            elif kind == "GET_OBJECT":
                self.runtime.handle_get_object(self, handle, msg)
            elif kind == "CHECK_READY":
                self.runtime.handle_check_ready(handle, msg)
            elif kind == "STREAM_NEXT":
                self.runtime.handle_stream_next(handle, msg)
            elif kind == "SUBMIT":
                spec = serialization.loads(msg["spec"])
                self.runtime.submit_spec(spec)
            elif kind == "PUT_META":
                self.runtime.on_worker_put(self, msg)
            elif kind == "STREAM_ITEM":
                self.runtime.on_stream_item(self, msg)
            elif kind == "SUBSCRIBE":
                self.runtime.handle_subscribe(self, handle, msg)
            elif kind == "SPILL_REQUEST":
                self.runtime.handle_spill_request(self, handle, msg)
            elif kind == "GCS_REQUEST":
                self.runtime.handle_gcs_request(handle, msg)
            elif kind == "KILL_ACTOR":
                self.runtime.kill_actor(ActorID(msg["actor_id"]),
                                        no_restart=msg.get("no_restart", True))
            elif kind == "REF_ADD":
                oid = ObjectID(msg["object_id"])
                if handle is not None:
                    handle.held_refs.add(oid)
                self.runtime.reference_counter.add_local_reference(oid)
            elif kind == "REF_DROP":
                oid = ObjectID(msg["object_id"])
                if handle is not None:
                    handle.held_refs.discard(oid)
                self.runtime.deferred_remove_reference(oid)
            elif kind == "CANCEL":
                self.runtime.cancel(ObjectID(msg["object_id"]),
                                    force=msg.get("force", False))
            return handle

    # --- dispatch ------------------------------------------------------
    def _mark_blocked(self, worker: WorkerHandle) -> None:
        spawn = False
        with self._lock:
            if worker.state == ACTOR:
                # actor workers already left the pool count at creation;
                # counting their blocks would drive the cap negative
                return
            worker.blocked_requests += 1
            if worker.blocked_requests == 1:
                self._n_blocked[worker.profile] = \
                    self._n_blocked.get(worker.profile, 0) + 1
                # escape hatch: queued work may now be spawnable
                profile = worker.profile
                spawn = (bool(self._dispatch_queue.get(profile))
                         and self._n_starting.get(profile, 0) == 0
                         and self._effective_live(profile)
                         < self._worker_cap(profile))
        if spawn:
            self._spawn_worker(worker.profile)

    def _mark_unblocked(self, worker: WorkerHandle) -> None:
        with self._lock:
            if worker.blocked_requests > 0:
                worker.blocked_requests -= 1
                if worker.blocked_requests == 0:
                    self._n_blocked[worker.profile] = max(
                        0, self._n_blocked.get(worker.profile, 0) - 1)

    def _effective_live(self, profile: str) -> int:
        """Pool workers counting toward the cap: live minus blocked."""
        return (self._n_live.get(profile, 0)
                - self._n_blocked.get(profile, 0))

    def _worker_cap(self, profile: str) -> int:
        """Max live workers per profile (reference: worker_pool.h
        maximum_startup_concurrency + num_cpus-bounded pool). Without
        this, a deep dispatch queue would fork one process per task.
        TPU pools are bounded by chips, not CPUs — a 1-CPU host with 2
        chips must still run 2 single-chip workers concurrently."""
        cfg = get_config()
        if cfg.max_workers_per_node > 0:
            return cfg.max_workers_per_node
        if profile.startswith("tpu:"):
            k = int(profile.partition("|")[0].split(":", 1)[1])
            if k > 0 and self._total_chips:
                return max(1, self._total_chips // k)
        return max(1, int(self.resources.get("CPU", 1)))

    def dispatch(self, spec: TaskSpec) -> None:
        """Run a (non-actor-method) task on this node. Resources already
        acquired by the cluster scheduler."""
        profile = self._profile_for(spec)
        with self._lock:
            idle = self._idle[profile]
            worker = idle.popleft() if idle else None
            if worker is not None:
                self._send_task(worker, spec)
                return
            # Pipeline: hand a busy-but-shallow worker a second spec so
            # it never idles a round trip (reference: owner-side lease
            # reuse); deeper backlogs park in the profile queue, from
            # which completions refill workers in batches. The scan is
            # restricted to the empty-queue case (light load) so a deep
            # backlog never pays O(workers) per dispatch, and skips
            # actor creations both as payload (they must own a worker)
            # and as hosts (a creating worker is off-limits).
            if (not spec.is_actor_creation
                    and not self._dispatch_queue[profile]
                    and self._effective_live(profile)
                    >= self._worker_cap(profile)):
                for candidate in self._workers.values():
                    if (candidate.profile == profile
                            and candidate.state == BUSY
                            and len(candidate.running) < 2
                            and candidate.blocked_requests == 0
                            and not any(s.is_actor_creation
                                        for s in
                                        candidate.running.values())):
                        self._send_task(candidate, spec)
                        return
            self._dispatch_queue[profile].append(spec)
            n_starting = self._n_starting.get(profile, 0)
            if (n_starting < len(self._dispatch_queue[profile])
                    and self._effective_live(profile)
                    < self._worker_cap(profile)):
                self._spawn_worker(profile)

    def dispatch_to_actor(self, worker_id: WorkerID, spec: TaskSpec) -> bool:
        """Send an actor method task to the actor's dedicated worker; the
        worker's thread pool queues it FIFO (ordering guarantee)."""
        with self._lock:
            worker = self._workers.get(worker_id)
            if worker is None or worker.state in (DEAD,):
                return False
            worker.running[spec.task_id] = spec
            return worker.send({"kind": "EXECUTE_ACTOR_TASK",
                                "spec": serialization.dumps_fast(spec)})

    def _send_task(self, worker: WorkerHandle, spec: TaskSpec) -> None:
        worker.state = BUSY
        worker.running[spec.task_id] = spec
        kind = "CREATE_ACTOR" if spec.is_actor_creation else "EXECUTE"
        if _task_phase._TRACKED:  # sampled-chain brackets (task_phase.py)
            _task_phase.mark(spec.task_id, "lease-dispatch")
            payload = serialization.dumps_fast(spec)
            _task_phase.mark(spec.task_id, "frame-encode")
            ok = worker.send({"kind": kind, "spec": payload})
            _task_phase.mark(spec.task_id, "wire-write")
        else:
            ok = worker.send({"kind": kind,
                              "spec": serialization.dumps_fast(spec)})
        if not ok:
            # This spec never reached the worker: requeue without
            # consuming a retry, then run the FULL death path so other
            # in-flight (pipelined) specs on this worker are retried too
            # — setting DEAD here would make the later EOF handler
            # early-return and strand them.
            self._dispatch_queue[worker.profile].appendleft(spec)
            del worker.running[spec.task_id]
            self._on_worker_death(worker)
            # The IO thread may not have noticed this death yet, so
            # make sure a replacement exists to drain the queue.
            self._spawn_worker(worker.profile)

    def _pump(self) -> None:
        """Match queued specs with idle workers; spawn for starved TPU
        queues (a finished worker may now be an idle chip holder the
        spawn path can reclaim)."""
        with self._lock:
            profiles = list(self._dispatch_queue.keys())
        for profile in profiles:
            while True:
                with self._lock:
                    queue = self._dispatch_queue[profile]
                    idle = self._idle[profile]
                    if not queue or not idle:
                        break
                    spec = queue.popleft()
                    worker = idle.popleft()
                    self._send_task(worker, spec)
        for profile in profiles:
            with self._lock:
                starved = (
                    self._dispatch_queue[profile]
                    and not self._idle[profile]
                    and self._n_starting.get(profile, 0) == 0
                    and (profile.startswith("tpu")  # chip reclaim path
                         or self._effective_live(profile)
                         < self._worker_cap(profile)))
            if starved:
                self._spawn_worker(profile)

    def _on_task_done(self, worker: WorkerHandle, msg: dict) -> None:
        task_id = TaskID(msg["task_id"])
        batch = None
        spawn_profile = None
        with self._lock:
            spec = worker.running.pop(task_id, None)
            if spec is None:
                return
            if spec.is_actor_creation and msg.get("error") is None:
                worker.state = ACTOR
                worker.actor_id = spec.actor_id
                # Actor workers leave the task pool: the pool cap must
                # not count them or long-lived actors starve task
                # dispatch (serve runs dozens of actors per node).
                self._n_live[worker.profile] = max(
                    0, self._n_live.get(worker.profile, 0) - 1)
                if worker.blocked_requests > 0:
                    # it blocked during __init__: clear the pool-side
                    # mark too, since actor blocks are no longer counted
                    worker.blocked_requests = 0
                    self._n_blocked[worker.profile] = max(
                        0, self._n_blocked.get(worker.profile, 0) - 1)
                # This worker's departure may leave queued specs with no
                # pool worker to drain them.
                if (self._dispatch_queue.get(worker.profile)
                        and self._n_starting.get(worker.profile, 0) == 0
                        and self._n_live.get(worker.profile, 0)
                        < self._worker_cap(worker.profile)):
                    spawn_profile = worker.profile
            elif worker.state == BUSY:
                # Fast path: keep the worker's pipeline topped up
                # straight from its own profile's queue — a full _pump()
                # scan per completion is the throughput bottleneck.
                batch = self._refill_locked(worker)
        if spawn_profile is not None:
            self._spawn_worker(spawn_profile)
        if batch:
            self._send_batch(worker, batch)
        self.runtime.on_task_done(self, worker, spec, msg)

    def _refill_locked(self, worker: WorkerHandle) -> Optional[List[TaskSpec]]:
        """Top up a busy worker's pipeline from its profile queue
        (called under self._lock). Returns the batch to send, or None.
        Batching amortizes the head's per-message cost — the single
        IO thread is the task-throughput ceiling."""
        queue = self._dispatch_queue.get(worker.profile)
        if worker.blocked_requests > 0:
            # the worker would only bounce refills while blocked
            return None
        if queue and len(worker.running) < 32:
            take = min(len(queue), 32 - len(worker.running), 16)
            batch: List[TaskSpec] = []
            while len(batch) < take and queue:
                head = queue[0]
                if head.is_actor_creation:
                    # An actor creation must own a fresh worker: send it
                    # alone once this worker has fully drained.
                    if not worker.running and not batch:
                        batch.append(queue.popleft())
                    break
                if not self._batchable(head):
                    if not batch:
                        batch.append(queue.popleft())  # dispatch singly
                    break
                batch.append(queue.popleft())
            if batch:
                for spec in batch:
                    worker.running[spec.task_id] = spec
                return batch
        if not worker.running:
            worker.state = IDLE
            self._idle[worker.profile].append(worker)
        return None

    @staticmethod
    def _batchable(spec: TaskSpec) -> bool:
        """Batch-mates execute sequentially in one worker slot, so a
        spec whose inline args embed unresolved ObjectRefs (no
        dependency edge — the head never waited for them) could block
        on a batch-mate's output: head-of-line deadlock. Dispatch those
        singly; direct object_id deps are safe (resolved before
        dispatch). Streaming tasks stay single for reply ordering."""
        if spec.num_returns == -1:
            return False
        for arg in list(spec.args) + list(spec.kwargs.values()):
            if (arg.value_bytes is not None
                    and getattr(arg, "_keepalive", None)):
                return False
        return True

    def _send_batch(self, worker: WorkerHandle,
                    batch: List[TaskSpec]) -> None:
        if len(batch) == 1:
            with self._lock:
                if worker.running.pop(batch[0].task_id, None) is None:
                    return  # worker died; the crash path retried it
                self._send_task(worker, batch[0])
            return
        if _task_phase._TRACKED:  # sampled-chain brackets (task_phase.py)
            for spec in batch:
                _task_phase.mark(spec.task_id, "lease-dispatch")
            payload = serialization.dumps_fast(batch)
            for spec in batch:
                _task_phase.mark(spec.task_id, "frame-encode")
            ok = worker.send({"kind": "EXECUTE_BATCH", "specs": payload})
            for spec in batch:
                _task_phase.mark(spec.task_id, "wire-write")
        else:
            ok = worker.send({"kind": "EXECUTE_BATCH",
                              "specs": serialization.dumps_fast(batch)})
        if not ok:
            with self._lock:
                for spec in batch:
                    if worker.running.pop(spec.task_id, None) is not None:
                        self._dispatch_queue[worker.profile].appendleft(spec)
            # full death path: retries any remaining in-flight specs
            self._on_worker_death(worker)
            self._spawn_worker(worker.profile)

    def _on_task_batch_done(self, worker: WorkerHandle, msg: dict) -> None:
        done = []
        batch = None
        with self._lock:
            for item in msg["items"]:
                spec = worker.running.pop(TaskID(item["task_id"]), None)
                if spec is not None:
                    done.append((spec, item))
            if worker.state == BUSY:
                batch = self._refill_locked(worker)
        if batch:
            self._send_batch(worker, batch)
        for spec, item in done:
            self.runtime.on_task_done(self, worker, spec, item)

    def _on_specs_returned(self, worker: WorkerHandle, msg: dict) -> None:
        with self._lock:
            for tid_bytes in msg["task_ids"]:
                spec = worker.running.pop(TaskID(tid_bytes), None)
                if spec is not None:
                    self._dispatch_queue[worker.profile].appendleft(spec)
        self._pump()

    def _on_worker_death(self, worker: WorkerHandle) -> None:
        with self._lock:
            if worker.state == DEAD:
                return
            was_actor = worker.state == ACTOR
            if worker.state == STARTING:
                self._n_starting[worker.profile] = max(
                    0, self._n_starting.get(worker.profile, 0) - 1)
            if not was_actor:  # actor workers already left the pool count
                self._n_live[worker.profile] = max(
                    0, self._n_live.get(worker.profile, 0) - 1)
            if worker.blocked_requests > 0:
                worker.blocked_requests = 0
                self._n_blocked[worker.profile] = max(
                    0, self._n_blocked.get(worker.profile, 0) - 1)
            worker.state = DEAD
            running = list(worker.running.values())
            worker.running.clear()
            held = list(worker.held_refs)
            worker.held_refs.clear()
            try:
                self._idle[worker.profile].remove(worker)
            except ValueError:
                pass
            self._workers.pop(worker.worker_id, None)
            self._dying = [w for w in self._dying if w.proc.poll() is None]
            if worker.proc is not None and worker.proc.poll() is None:
                self._dying.append(worker)
            # Return this worker's chips; TPU specs may be queued
            # waiting for exactly these.
            if worker.chips:
                self._free_chips.extend(worker.chips)
                worker.chips = []
            starved = [
                p for p, q in self._dispatch_queue.items()
                if q and p.startswith("tpu") and not self._idle[p]
                and self._n_starting.get(p, 0) == 0
            ]
        for oid in held:  # release this worker's borrowed pins
            self.runtime.reference_counter.remove_local_reference(oid)
        if self._stopped.is_set():
            return
        # Root event for this worker's incident; the seq rides the
        # handle so on_worker_crashed chains retries/actor deaths to
        # it. Idle reclaims (nothing running, no actor) are DEBUG —
        # they root no recovery work.
        severity = "ERROR" if (running or was_actor) else "DEBUG"
        worker._exit_event_seq = self._emit_worker_event(
            "WORKER_EXIT", severity, worker.worker_id,
            f"{len(running)} tasks in flight" if running else "",
            caused_by=getattr(worker, "_chaos_cause_seq", None))
        for profile in starved:
            self._spawn_worker(profile)
        self.runtime.on_worker_crashed(self, worker, running,
                                       worker.actor_id if was_actor else None)

    def cancel_queued(self, task_id: TaskID) -> Optional[TaskSpec]:
        """Remove a not-yet-running spec from this node's dispatch
        queues (burst-granted specs park here); None if the spec
        already reached a worker."""
        with self._lock:
            for queue in self._dispatch_queue.values():
                for spec in queue:
                    if spec.task_id == task_id:
                        queue.remove(spec)
                        return spec
        return None

    def idle_worker_count(self) -> int:
        with self._lock:
            return sum(len(q) for q in self._idle.values())

    def kill_worker(self, worker_id: WorkerID) -> None:
        """Does not wait for the process (callers are on the IO loop):
        its chips go back to the pool when its connection closes, the
        worker that gets them next waits for the device nodes
        (accelerators/tpu.py::wait_for_chips), and stop() waits for
        the process if it is still dying then."""
        with self._lock:
            worker = self._workers.get(worker_id)
        if worker is not None:
            worker.send({"kind": "KILL"})
            if worker.proc is not None:
                try:
                    worker.proc.kill()
                except ProcessLookupError:
                    pass

    def live_actors(self) -> List[Tuple[bytes, bytes]]:
        """(actor_id, worker_id) for every live actor worker — reported
        in NODE_REGISTER so a restarted head re-binds surviving
        detached/named actors (reference: gcs_init_data.cc replaying
        actor ownership on GCS restart)."""
        with self._lock:
            return [(w.actor_id.binary(), w.worker_id.binary())
                    for w in self._workers.values()
                    if w.state == ACTOR and w.actor_id is not None]

    # --- shutdown ------------------------------------------------------
    def stop(self) -> None:
        self._stopped.set()
        with self._lock:
            workers = list(self._workers.values()) + self._dying
        for worker in workers:
            worker.send({"kind": "SHUTDOWN"})
        sent = time.time()
        deadline = sent + 2.0
        for worker in workers:
            if worker.proc is None:
                continue
            remaining = max(0.05, deadline - time.time())
            try:
                worker.proc.wait(timeout=remaining)
            except subprocess.TimeoutExpired:
                worker.proc.kill()
        # A worker that owned chips is gone only when the kernel has
        # taken them back (seconds after it stopped answering: 14 for
        # the four chips of a v5e host), killed or not. Whoever starts
        # next on this machine must find them free, so stop() returns
        # after that.
        reap_by = time.time() + _REAP_TIMEOUT_S
        for worker in workers:
            proc = worker.proc
            if proc is None or proc.poll() is not None:
                continue
            try:
                proc.wait(timeout=max(0.05, reap_by - time.time()))
            except subprocess.TimeoutExpired:
                logger.error(
                    "worker %s (pid %d, %s) still there %.0f s after "
                    "SHUTDOWN", worker.worker_id.hex()[:8], proc.pid,
                    worker.profile, time.time() - sent)
                continue
            took = time.time() - sent
            if took > 5.0:
                logger.warning(
                    "worker %s (%s) was gone %.1f s after SHUTDOWN",
                    worker.worker_id.hex()[:8], worker.profile, took)
        self._listener_handle.close(wait=True)
        for worker in workers:
            if worker.conn is not None:
                worker.conn.close()
        try:
            os.unlink(self.socket_path)
        except OSError:
            pass
        self.store.close()
