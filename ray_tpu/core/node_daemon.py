"""Node daemon: runs one Node (worker pool + shm store) on another host.

Capability parity with the reference's per-node raylet process
(reference: src/ray/raylet/main.cc:180 — a raylet per node registering
with the GCS over the network, heartbeating, and executing leased work).
``python -m ray_tpu.core.node_daemon --address HEAD_HOST:PORT`` (or the
``ray-tpu start`` CLI) connects to the head's HeadServer
(ray_tpu/core/remote_node.py), registers the node's resources, and then
serves dispatches. The local ``Node`` is exactly the in-process Node the
head uses — only its ``runtime`` is a ``HeadProxy`` that forwards every
runtime call over the TCP control connection instead of calling the
DriverRuntime directly.

Object data does not transit the control connection: each daemon runs an
ObjectServer (object_transfer.py) and pulls objects it needs directly
from the holder node in bounded chunks.
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import threading
from typing import Optional

from ray_tpu.core import serialization
from ray_tpu.core.config import get_config, reset_config
from ray_tpu.core.ids import ActorID, NodeID, ObjectID, TaskID, WorkerID
from ray_tpu.core.object_transfer import ObjectServer
from ray_tpu.core.protocol import (
    MessageConnection,
    connect_tcp,
    parse_address,
)
from ray_tpu.exceptions import ObjectLostError


class _RefForwarder:
    """Forwards borrowed-ref transitions to the head's ReferenceCounter."""

    def __init__(self, proxy: "HeadProxy"):
        self._proxy = proxy

    def add_local_reference(self, object_id: ObjectID) -> None:
        self._proxy.send({"kind": "REF_ADD",
                          "object_id": object_id.binary()})

    def remove_local_reference(self, object_id: ObjectID) -> None:
        self._proxy.send({"kind": "REF_DROP",
                          "object_id": object_id.binary(), "defer": False})


class HeadProxy:
    """The runtime interface a Node invokes, forwarded to the head."""

    is_driver = False

    def __init__(self, conn: MessageConnection):
        self.conn = conn
        self.dead = threading.Event()
        self.reference_counter = _RefForwarder(self)

    def send(self, msg: dict) -> bool:
        if self.dead.is_set():
            return False
        try:
            self.conn.send(msg)
            return True
        except OSError:
            self.dead.set()
            return False

    # --- runtime interface used by Node --------------------------------
    def submit_spec(self, spec) -> None:
        self.send({"kind": "SUBMIT", "spec": serialization.dumps_fast(spec)})

    def on_worker_put(self, node, msg: dict) -> None:
        self.send({"kind": "PUT_META", "object_id": msg["object_id"],
                   "contained": list(msg.get("contained", ()))})

    def on_stream_item(self, node, msg: dict) -> None:
        self.send({"kind": "STREAM_ITEM", "task_id": msg["task_id"],
                   "object_id": msg["object_id"], "index": msg["index"],
                   "item_kind": msg["item_kind"], "data": msg["data"],
                   "contained": list(msg.get("contained", ()))})

    def handle_stream_next(self, handle, msg: dict) -> None:
        self.send({"kind": "STREAM_NEXT",
                   "worker_id": handle.worker_id.binary(),
                   "task_id": msg["task_id"], "index": msg["index"],
                   "req_id": msg.get("req_id")})

    def handle_get_object(self, node, handle, msg: dict) -> None:
        self.send({"kind": "GET_OBJECT",
                   "worker_id": handle.worker_id.binary(),
                   "object_id": msg["object_id"],
                   "req_id": msg.get("req_id")})

    def handle_check_ready(self, handle, msg: dict) -> None:
        self.send({"kind": "CHECK_READY",
                   "worker_id": handle.worker_id.binary(),
                   "object_ids": msg["object_ids"],
                   "req_id": msg.get("req_id")})

    def handle_subscribe(self, node, handle, msg: dict) -> None:
        self.send({"kind": "SUBSCRIBE",
                   "worker_id": handle.worker_id.binary(),
                   "channel": msg["channel"]})

    def handle_spill_request(self, node, handle, msg: dict) -> None:
        self.send({"kind": "SPILL_REQUEST",
                   "worker_id": handle.worker_id.binary(),
                   "bytes": msg.get("bytes", 0),
                   "req_id": msg.get("req_id")})

    def handle_gcs_request(self, handle, msg: dict) -> None:
        self.send({"kind": "GCS_REQUEST",
                   "worker_id": handle.worker_id.binary(),
                   "method": msg["method"], "args": msg["args"],
                   "req_id": msg.get("req_id")})

    def kill_actor(self, actor_id: ActorID, no_restart: bool = True) -> None:
        self.send({"kind": "KILL_ACTOR", "actor_id": actor_id.binary(),
                   "no_restart": no_restart})

    def cancel(self, object_id: ObjectID, force: bool = False) -> None:
        self.send({"kind": "CANCEL", "object_id": object_id.binary(),
                   "force": force})

    def deferred_remove_reference(self, object_id: ObjectID) -> None:
        self.send({"kind": "REF_DROP", "object_id": object_id.binary(),
                   "defer": True})

    def on_task_done(self, node, worker, spec, msg: dict) -> None:
        self.send({"kind": "TASK_DONE_FWD",
                   "worker_id": worker.worker_id.binary(),
                   "spec": serialization.dumps_fast(spec), "msg": msg})

    def on_worker_crashed(self, node, worker, running, actor_id) -> None:
        self.send({"kind": "WORKER_CRASHED_FWD",
                   "worker_id": worker.worker_id.binary(),
                   "running": [serialization.dumps_fast(s) for s in running],
                   "actor_id": actor_id.binary() if actor_id else None})


class NodeDaemon:
    def __init__(self, head_address: str,
                 resources: Optional[dict] = None,
                 labels: Optional[dict] = None,
                 object_store_memory: Optional[int] = None,
                 session_dir: Optional[str] = None,
                 advertise_host: Optional[str] = None):
        from ray_tpu.core.node import Node  # late: spawns worker procs

        self.head_address = head_address
        self.node_id = NodeID.from_random()
        self._stop_requested = False
        if resources is None:
            resources = {}
        resources = dict(resources)
        if "CPU" not in resources:
            import multiprocessing
            resources["CPU"] = float(multiprocessing.cpu_count())
        labels = dict(labels or {})
        from ray_tpu.accelerators.tpu import TpuAcceleratorManager
        TpuAcceleratorManager.augment_node(resources, labels)
        self.resources = resources
        self.node_labels = dict(labels)
        self._advertise = advertise_host or get_config().head_host
        # must be set BEFORE the Node prestarts workers: they inherit
        # it for cross-host endpoints they advertise (e.g.
        # compiled-graph TCP channel listeners)
        os.environ["RTPU_NODE_ADVERTISE_HOST"] = self._advertise

        self.conn = self._dial()
        self.proxy = HeadProxy(self.conn)
        self.node = Node(self.proxy, self.node_id, resources, labels,
                         object_store_memory=object_store_memory,
                         session_dir=session_dir)
        self.object_server = ObjectServer(self._resolve_store,
                                          host=self._advertise)
        self._adopt(self.conn, self._register_on(self.conn))

    def _dial(self) -> MessageConnection:
        """Dial the head and send the AUTH preamble (registration is a
        separate step — its NODE_REGISTER carries the object-server
        port, which only exists after the ObjectServer starts)."""
        host, port = parse_address(self.head_address)
        conn = MessageConnection(connect_tcp(host, port, timeout=30.0))
        token = get_config().auth_token
        if token:
            # plaintext auth frame BEFORE any pickled message (the head
            # refuses to unpickle from unauthenticated peers)
            from ray_tpu.core.protocol import send_frame
            send_frame(conn.sock, b"AUTH" + token.encode("utf-8"))
        return conn

    def _register_on(self, conn: MessageConnection,
                     timeout_s: float = 30.0) -> dict:
        """NODE_REGISTER/REGISTERED exchange on ``conn`` — bounded, and
        touching NO daemon state (the live connection stays untouched
        until the new one is fully registered)."""
        from ray_tpu.core.protocol import PROTOCOL_MINOR, PROTOCOL_VERSION
        conn.sock.settimeout(timeout_s)
        try:
            conn.send({
                "kind": "NODE_REGISTER",
                "proto_version": PROTOCOL_VERSION,
                "proto_minor": PROTOCOL_MINOR,
                "node_id": self.node_id.binary(),
                "resources": self.resources,
                "labels": dict(self.node_labels),
                "object_addr": [self._advertise,
                                self.object_server.address[1]],
                "address": f"{socket.gethostname()}:{os.getpid()}",
                # live actor workers, so a restarted head re-binds
                # surviving detached/named actors (head FT slice 2)
                "actors": self.node.live_actors(),
            })
            reply = conn.recv()
        finally:
            try:
                conn.sock.settimeout(None)
            except OSError:
                pass
        if reply is None or reply.get("kind") != "REGISTERED":
            reason = (reply or {}).get("reason", "connection closed")
            raise RuntimeError(f"head rejected node registration: {reason}")
        return reply

    def _adopt(self, conn: MessageConnection, reply: dict) -> None:
        """Switch the daemon onto a REGISTERED connection. Ordering
        matters: proxy.dead stays SET until the swap is complete, so
        worker completions can't write frames ahead of registration
        and poison the handshake."""
        self.conn = conn
        self.proxy.conn = conn
        # Negotiated head features (additive minors; protocol.py policy)
        self.head_proto_minor = reply.get("proto_minor", 0)
        self.head_capabilities = frozenset(reply.get("capabilities", ()))
        self.proxy.dead.clear()
        self._heartbeat_thread = threading.Thread(
            target=self._heartbeat_loop, args=(conn,),
            name="heartbeat", daemon=True)
        self._heartbeat_thread.start()

    def _try_reconnect(self) -> bool:
        """Head link lost: retry within node_reconnect_s, re-registering
        under the SAME node id so a restarted head (journal-replayed
        control plane) adopts this node (reference: raylets reconnecting
        to a restarted GCS, gcs_init_data.cc). Work dispatched before
        the outage is lost — the new head never owned it — and any late
        completions are dropped by the head as unknown tasks. The dead
        flag stays set for the whole attempt, so nothing else writes to
        the half-established connection."""
        from ray_tpu.util.backoff import Backoff

        window = get_config().node_reconnect_s
        if window <= 0 or self._stop_requested:
            return False
        # Jittered (util/backoff.py): after a head restart EVERY daemon
        # in the fleet redials at once, and identical timers would slam
        # the fresh listener in synchronized waves.
        backoff = Backoff(initial_s=0.5, max_s=3.0, deadline_s=window)
        old = self.conn
        while not self._stop_requested:
            if backoff.expired():
                return False
            remaining = backoff.remaining() or 0.0
            try:
                conn = self._dial()
            except OSError:
                if not backoff.wait():
                    return False
                continue
            try:
                reply = self._register_on(conn,
                                          timeout_s=min(15.0, remaining))
            except (RuntimeError, OSError):
                conn.close()  # every failed attempt frees its socket
                if not backoff.wait():
                    return False
                continue
            self._adopt(conn, reply)
            try:
                old.close()
            except OSError:
                pass
            return True
        return False

    def _resolve_store(self, oid: ObjectID):
        if self.node.store.contains(oid):
            return self.node.store
        path = os.path.join(self._spill_dir(), oid.hex())
        if os.path.exists(path):
            return ("file", path)  # spilled: serve straight off disk
        return None

    def _heartbeat_loop(self, conn) -> None:
        cfg = get_config()
        while not self.proxy.dead.wait(cfg.heartbeat_interval_s):
            if self.proxy.conn is not conn:
                return  # superseded: a reconnect started a fresh thread
            self.proxy.send({"kind": "HEARTBEAT",
                             "idle": self.node.idle_worker_count(),
                             "store_used": self.node.store.used_bytes()})

    # --- main loop ------------------------------------------------------
    def serve_forever(self) -> None:
        try:
            while True:
                msg = self.conn.recv()
                if msg is None:
                    # head link lost: survive a head restart when the
                    # reconnect window allows (node_reconnect_s)
                    self.proxy.dead.set()
                    if self._try_reconnect():
                        continue
                    break
                try:
                    if not self._handle(msg):
                        self._stop_requested = True
                        break
                except Exception:  # noqa: BLE001 — keep serving
                    import traceback
                    traceback.print_exc()
        finally:
            self.proxy.dead.set()
            self.shutdown()

    def _handle(self, msg: dict) -> bool:
        kind = msg["kind"]
        if kind == "DISPATCH":
            self.node.dispatch(serialization.loads(msg["spec"]))
        elif kind == "DISPATCH_ACTOR":
            spec = serialization.loads(msg["spec"])
            if not self.node.dispatch_to_actor(WorkerID(msg["worker_id"]),
                                               spec):
                self.proxy.send({"kind": "ACTOR_DISPATCH_FAILED",
                                 "spec": serialization.dumps_fast(spec)})
        elif kind == "TO_WORKER":
            self._route_to_worker(WorkerID(msg["worker_id"]), msg["payload"])
        elif kind == "KILL_WORKER":
            self.node.kill_worker(WorkerID(msg["worker_id"]))
        elif kind == "PRESTART":
            self.node.prestart_workers(msg.get("count", 1),
                                       msg.get("profile", "cpu"))
        elif kind == "DELETE_OBJECT":
            oid = ObjectID(msg["object_id"])
            self.node.store.delete(oid)
            spill_path = os.path.join(self._spill_dir(), oid.hex())
            if os.path.exists(spill_path):
                try:
                    os.unlink(spill_path)
                except OSError:
                    pass
        elif kind == "SPILL_OBJECTS":
            self._spill_objects(msg)
        elif kind == "CANCEL_TASK":
            self._cancel_task(TaskID(msg["task_id"]),
                              force=msg.get("force", True))
        elif kind == "STOP":
            return False
        elif kind == "UNSUPPORTED":
            pass  # answer to OUR probe; never re-answered (echo loop)
        else:
            # Additive evolution (protocol.py policy): answer probes for
            # kinds this daemon predates so a newer head can fall back.
            if msg.get("req_id") is not None:
                self.proxy.send({"kind": "UNSUPPORTED",
                                 "req_id": msg["req_id"],
                                 "unsupported_kind": kind})
        return True

    def _route_to_worker(self, worker_id: WorkerID, payload: dict) -> None:
        if payload.get("status") == "pull":
            # The head pointed us at the holder node; pull the object
            # into the local arena (chunked, node-to-node), then tell the
            # worker it is local (reference: PullManager-driven transfer,
            # pull_manager.h:50).
            threading.Thread(
                target=self._pull_and_reply,
                args=(worker_id, payload), daemon=True).start()
            return
        self._send_to_worker(worker_id, payload)

    def _pull_and_reply(self, worker_id: WorkerID, payload: dict) -> None:
        oid = ObjectID(payload["object_id"])
        addr = tuple(payload["addr"])
        out = {"kind": "OBJECT_VALUE", "req_id": payload.get("req_id")}
        from ray_tpu.core.object_transfer import (
            PRIORITY_TASK_ARG, get_pull_manager)
        if get_pull_manager().pull(addr, oid, self.node.store,
                                   priority=PRIORITY_TASK_ARG):
            self.proxy.send({"kind": "REPLICA", "object_id": oid.binary()})
            out["status"] = "shm_local"
        else:
            out["status"] = "error"
            out["error"] = serialization.dumps(ObjectLostError(oid))
        self._send_to_worker(worker_id, out)

    def _send_to_worker(self, worker_id: WorkerID, payload: dict) -> None:
        with self.node._lock:
            worker = self.node._workers.get(worker_id)
        if worker is not None:
            worker.send(payload)

    def _spill_dir(self) -> str:
        path = os.path.join(self.node.session_dir, "spill")
        os.makedirs(path, exist_ok=True)
        return path

    def _spill_objects(self, msg: dict) -> None:
        """Spill candidates from the local arena until `bytes` are freed
        (reference: LocalObjectManager::SpillObjects). Reports results
        so the head records locations and unblocks the worker."""
        from ray_tpu.core.object_store import spill_objects
        needed = int(msg.get("bytes", 0)) or 1
        results = spill_objects(
            self.node.store, self._spill_dir(),
            [ObjectID(b) for b in msg.get("object_ids", ())], needed)
        self.proxy.send({"kind": "SPILLED",
                         "results": [(oid.binary(), path, size)
                                     for oid, path, size in results],
                         "freed": sum(size for _, _, size in results),
                         "reply_worker": msg.get("reply_worker"),
                         "req_id": msg.get("req_id")})

    def _cancel_task(self, task_id: TaskID, force: bool = True) -> None:
        # node-queued (not yet running): drop + report so the head can
        # fail the ref immediately (queued-task cancel semantics)
        spec = self.node.cancel_queued(task_id)
        if spec is not None:
            self.proxy.send({"kind": "TASK_CANCELLED_FWD",
                             "spec": serialization.dumps_fast(spec)})
            return
        if not force:
            return
        with self.node._lock:
            target = None
            for worker in self.node._workers.values():
                if task_id in worker.running:
                    target = worker.worker_id
                    break
        if target is not None:
            self.node.kill_worker(target)

    def shutdown(self) -> None:
        self.object_server.stop()
        self.node.stop()
        try:
            self.conn.close()
        except OSError:
            pass


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(
        description="ray_tpu node daemon (joins a head over TCP)")
    parser.add_argument("--address", required=True,
                        help="head address, host:port")
    parser.add_argument("--resources", default="{}",
                        help="JSON resource dict, e.g. '{\"CPU\": 4}'")
    parser.add_argument("--labels", default="{}",
                        help="JSON node labels")
    parser.add_argument("--object-store-memory", type=int, default=None)
    parser.add_argument("--system-config", default=None,
                        help="JSON system config matching the head's")
    parser.add_argument("--session-dir", default=None)
    args = parser.parse_args(argv)
    if args.system_config:
        reset_config(json.loads(args.system_config))
    daemon = NodeDaemon(
        args.address,
        resources=json.loads(args.resources) or None,
        labels=json.loads(args.labels) or None,
        object_store_memory=args.object_store_memory,
        session_dir=args.session_dir)
    from ray_tpu.util import flight_recorder
    flight_recorder.start_stall_watch("node")
    daemon.serve_forever()


if __name__ == "__main__":
    main()
