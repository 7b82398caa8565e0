"""Worker process: executes tasks and hosts actors.

Capability parity with the reference's worker side
(reference: python/ray/_private/workers/default_worker.py main loop →
CoreWorkerProcess::RunTaskExecutionLoop, core_worker_process.cc:119;
task execution via TaskReceiver, task_execution/task_receiver.h:44, with
concurrency groups running on a thread pool,
task_execution/concurrency_group_manager.h).

One process per worker; connects to its node manager over a unix socket;
executes plain tasks FIFO on a single thread (ordering guarantee) and
actor tasks on a pool of ``max_concurrency`` threads. Inside task code
the global runtime is a WorkerRuntime, so ``remote``/``get``/``put``
compose (nested tasks, actor handles in args).
"""

from __future__ import annotations

import argparse
import os
import queue
import socket
import sys
import threading
import traceback
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Optional, Tuple

from ray_tpu.core import serialization
from ray_tpu.core.ids import ActorID, NodeID, ObjectID, TaskID, WorkerID
from ray_tpu.core.object_store import SharedMemoryStore
from ray_tpu.core.object_ref import ObjectRef
from ray_tpu.core.protocol import MessageConnection
from ray_tpu.core.task_manager import ReferenceCounter
from ray_tpu.core.task_spec import Arg, TaskSpec
from ray_tpu.devtools import refsan
from ray_tpu.exceptions import GetTimeoutError, ObjectLostError, TaskError
from ray_tpu.util import flight_recorder as _flight


class _ContextValue:
    """threading.local-compatible ``.value`` holder backed by a
    ContextVar — isolated per thread AND per asyncio task."""

    def __init__(self, name: str):
        import contextvars
        object.__setattr__(self, "_var",
                           contextvars.ContextVar(name, default=None))

    @property
    def value(self):
        return self._var.get()

    @value.setter
    def value(self, v):
        self._var.set(v)


class WorkerRuntime:
    """The runtime visible to user code executing inside this worker."""

    def __init__(self, conn: MessageConnection, store: SharedMemoryStore,
                 node_id: NodeID, worker_id: WorkerID):
        self.conn = conn
        self.store = store
        self.node_id = node_id
        self.worker_id = worker_id
        # Borrowed-ref reporting: the first local ref to an object pins
        # it at the owner (REF_ADD); the last drop releases it
        # (REF_DROP). reference: reference_counter.h:43 borrowing.
        self.reference_counter = ReferenceCounter()
        self.reference_counter.refsan_role = "borrower"
        self.reference_counter.set_on_first(
            lambda oid: self._send_borrow("REF_ADD", oid))
        self.reference_counter.set_deleter(
            lambda oid: self._send_borrow("REF_DROP", oid))
        self.is_driver = False
        # set by worker_main: flushes queued specs back to the node
        # before this worker blocks on an object
        self.on_block = None
        self._pubsub_callbacks: Dict[str, list] = {}
        self._req_lock = threading.Lock()
        self._req_counter = 0
        self._replies: Dict[int, Tuple[threading.Event, list]] = {}
        self._fn_cache: Dict[str, Any] = {}
        self._put_counter = 0
        # contextvars, not threading.local: async-actor coroutines
        # interleave on ONE event-loop thread, and each asyncio Task
        # runs in its own context copy — a thread-local would be
        # clobbered across awaits (wrong task ids / merged spans)
        self._current_task_id = _ContextValue("current_task_id")
        # per-task user profile spans (ray_tpu.util.tracing.profile),
        # shipped with the TASK_DONE reply into the GCS event store
        self._profile_spans = _ContextValue("profile_spans")
        self.actor_instance = None
        self.actor_id: Optional[ActorID] = None
        # normalized runtime env this worker runs inside (child tasks
        # submitted from here inherit it; see runtime_env/__init__.py)
        self.current_runtime_env: Optional[dict] = None
        # set when runtime_env setup failed: every task handed to this
        # worker fails fast with this error instead of executing
        self.setup_error: Optional[Exception] = None

    def _send_borrow(self, op: str, oid) -> None:
        """Report a borrow transition to the owner; mirrored into the
        refsan ledger so the driver-side fold can pair each wire send
        with the owner's add/drop."""
        led = refsan.LEDGER
        if led is not None:
            led.record(refsan.KIND_BORROW_SEND, oid.hex(), {"op": op})
        self.conn.send({"kind": op, "object_id": oid.binary()})

    # --- request/reply with the node manager ---------------------------
    def _next_req(self) -> Tuple[int, threading.Event, list]:
        with self._req_lock:
            self._req_counter += 1
            rid = self._req_counter
            ev = threading.Event()
            slot: list = [None]
            self._replies[rid] = (ev, slot)
        return rid, ev, slot

    def request(self, msg: dict, timeout: Optional[float] = None) -> Any:
        rid, ev, slot = self._next_req()
        msg["req_id"] = rid
        self.conn.send(msg)
        if not ev.wait(timeout):
            with self._req_lock:
                self._replies.pop(rid, None)
            raise GetTimeoutError(f"request {msg.get('kind')} timed out")
        with self._req_lock:
            self._replies.pop(rid, None)
        return slot[0]

    def deliver_reply(self, msg: dict) -> None:
        rid = msg.get("req_id")
        with self._req_lock:
            entry = self._replies.get(rid)
        if entry is not None:
            ev, slot = entry
            slot[0] = msg
            ev.set()

    # --- object plane ---------------------------------------------------
    def put(self, value: Any) -> ObjectRef:
        with serialization.collect_contained_refs() as contained:
            data, buffers = serialization.serialize(value)
        return self.put_serialized(
            data, buffers, contained=[o.binary() for o in contained])

    def request_spill(self, nbytes: int) -> None:
        """Ask the owner to spill objects from this node's arena to disk
        (reference: raylet-triggered spilling under create pressure,
        local_object_manager.h:43)."""
        self.request({"kind": "SPILL_REQUEST", "bytes": nbytes},
                     timeout=60.0)

    def _store_with_spill(self, write_fn, nbytes: int):
        """Run a store write; on a full arena, spill and retry. Several
        rounds: a spilled victim's space frees only after in-flight
        readers (e.g. an object-server stream) release their pins."""
        import time as _time

        from ray_tpu.exceptions import ObjectStoreFullError
        attempts = 5
        for attempt in range(attempts):
            try:
                return write_fn()
            except ObjectStoreFullError:
                if attempt == attempts - 1:
                    raise
                self.request_spill(nbytes)
                _time.sleep(0.05 * (attempt + 1))

    def put_serialized(self, data: bytes, buffers, contained=()) -> ObjectRef:
        # Random IDs: a retried task attempt must not collide with the
        # puts of its previous attempt (the ID travels in the returned
        # ref + PUT_META, so determinism buys nothing).
        oid = ObjectID.from_random()
        sizes = [b.nbytes for b in buffers]
        nbytes = serialization.packed_size(data, sizes)
        rec = _flight.RECORDER
        t0_ns = rec.clock() if rec is not None else 0
        self._store_with_spill(
            lambda: self.store.put_parts(oid, data, buffers, sizes),
            nbytes)
        if rec is not None:
            rec.record("object", "put", t0_ns, rec.clock() - t0_ns,
                       {"oid": oid.hex()[:12], "bytes": nbytes})
        self.conn.send({"kind": "PUT_META", "object_id": oid.binary(),
                        "contained": list(contained)})
        return ObjectRef(oid)

    def put_result(self, oid: ObjectID, value: Any) -> Tuple[str, Any, list]:
        """Store a task return; small values go inline in the reply.
        Returns (kind, payload, contained_ref_binaries)."""
        with serialization.collect_contained_refs() as contained:
            data, buffers = serialization.serialize(value)
        contained_bin = [o.binary() for o in contained]
        from ray_tpu.core.config import get_config
        if not buffers and len(data) < get_config().max_inline_object_size:
            return ("inline", serialization.pack_parts(data, buffers),
                    contained_bin)
        sizes = [b.nbytes for b in buffers]
        packed_len = serialization.packed_size(data, sizes)

        def write():
            dest = self.store.create(oid, packed_len)
            try:
                serialization.pack_into(dest, data, buffers, sizes)
            finally:
                del dest
            self.store.seal(oid)

        self._store_with_spill(write, packed_len)
        return ("shm", None, contained_bin)

    def get(self, refs, timeout: Optional[float] = None):
        single = isinstance(refs, ObjectRef)
        if single:
            refs = [refs]
        out = []
        for ref in refs:
            out.append(self._get_one(ref.id, timeout))
        return out[0] if single else out

    def _get_one(self, oid: ObjectID, timeout: Optional[float]):
        found, value = self.store.get_value(oid, timeout_s=0.0)
        if found:
            return value
        # About to block: hand queued (pipelined) specs back to the node
        # so they can run elsewhere — one of them might be what this
        # get() is waiting for (head-of-line deadlock otherwise). Specs
        # arriving while blocked bounce straight back (enter/exit).
        if self.on_block is not None:
            self.on_block(True)
        rec = _flight.RECORDER
        t0_ns = rec.clock() if rec is not None else 0
        try:
            reply = self.request(
                {"kind": "GET_OBJECT", "object_id": oid.binary()},
                timeout=timeout if timeout is not None else None,
            )
        finally:
            if rec is not None:
                rec.record("object", "get_wait", t0_ns,
                           rec.clock() - t0_ns,
                           {"oid": oid.hex()[:12]})
            if self.on_block is not None:
                self.on_block(False)
        status = reply["status"]
        if status == "inline":
            return serialization.unpack(reply["data"])
        if status == "shm_local":
            found, value = self.store.get_value(oid, timeout_s=5.0)
            if found:
                return value
            raise ObjectLostError(oid)
        if status == "spilled_local":
            # payload was spilled to a file on this host (reference:
            # reading back from external storage)
            try:
                with open(reply["path"], "rb") as f:
                    return serialization.unpack(f.read())
            except OSError:
                raise ObjectLostError(oid)
        if status == "error":
            raise serialization.loads(reply["error"])
        raise ObjectLostError(oid)

    def wait(self, refs: List[ObjectRef], num_returns: int = 1,
             timeout: Optional[float] = None, fetch_local: bool = True):
        if num_returns > len(refs):
            raise ValueError(
                f"num_returns ({num_returns}) exceeds the number of refs "
                f"({len(refs)})")
        if self.on_block is not None:
            self.on_block(True)
            try:
                return self._wait_inner(refs, num_returns, timeout)
            finally:
                self.on_block(False)
        return self._wait_inner(refs, num_returns, timeout)

    def _wait_inner(self, refs: List[ObjectRef], num_returns: int,
                    timeout: Optional[float]):
        import time as _time
        deadline = None if timeout is None else _time.monotonic() + timeout
        pending = list(refs)
        ready: List[ObjectRef] = []
        while True:
            ids = [r.id.binary() for r in pending]
            reply = self.request({"kind": "CHECK_READY", "object_ids": ids},
                                 timeout=30.0)
            ready_set = set(reply["ready"])
            newly = [r for r in pending if r.id.binary() in ready_set]
            pending = [r for r in pending if r.id.binary() not in ready_set]
            ready.extend(newly)
            if len(ready) >= num_returns or not pending:
                break
            if deadline is not None and _time.monotonic() >= deadline:
                break
            _time.sleep(0.005)
        done = ready[:num_returns]
        rest = ready[num_returns:] + pending
        return done, rest

    # --- task/actor submission (nested) ---------------------------------
    def submit_spec(self, spec: TaskSpec) -> None:
        self.conn.send({"kind": "SUBMIT", "spec": serialization.dumps_fast(spec)})

    def create_actor(self, spec: TaskSpec, name: Optional[str] = None) -> None:
        self.submit_spec(spec)

    def kill_actor(self, actor_id: ActorID, no_restart: bool = True) -> None:
        self.conn.send({"kind": "KILL_ACTOR", "actor_id": actor_id.binary(),
                        "no_restart": no_restart})

    def cancel_task(self, object_id: ObjectID, force: bool = False) -> None:
        self.conn.send({"kind": "CANCEL", "object_id": object_id.binary(),
                        "force": force})

    def stream_next(self, task_id: TaskID, index: int,
                    timeout: Optional[float]):
        """Consume item ``index`` of a streaming task owned by the head
        (reference: ObjectRefGenerator protocol, _raylet.pyx:299)."""
        if self.on_block is not None:
            self.on_block(True)
        try:
            reply = self.request({"kind": "STREAM_NEXT",
                                  "task_id": task_id.binary(),
                                  "index": index},
                                 timeout=timeout)
        finally:
            if self.on_block is not None:
                self.on_block(False)
        status = reply["status"]
        if status == "item":
            return "item", ObjectID(reply["object_id"])
        if status == "done":
            return "done", None
        return "error", serialization.loads(reply["error"])

    # --- pubsub ----------------------------------------------------------
    def subscribe_channel(self, channel: str, callback) -> None:
        """Subscribe to a GCS pubsub channel from inside a worker
        (reference: subscriber.h:215 — workers couldn't subscribe in
        round 1). Callbacks run on the worker's socket-reader thread;
        keep them fast."""
        with self._req_lock:
            first = channel not in self._pubsub_callbacks
            self._pubsub_callbacks.setdefault(channel, []).append(callback)
        if first:
            self.conn.send({"kind": "SUBSCRIBE", "channel": channel})

    def publish_channel(self, channel: str, message: Any) -> None:
        self.gcs_call("publish", channel, serialization.dumps(message))

    def _on_pubsub(self, msg: dict) -> None:
        with self._req_lock:
            callbacks = list(self._pubsub_callbacks.get(msg["channel"], ()))
        payload = serialization.loads(msg["data"])
        for cb in callbacks:
            try:
                cb(payload)
            except Exception:  # noqa: BLE001 — user callback
                import traceback
                traceback.print_exc()

    # --- control plane --------------------------------------------------
    def gcs_call(self, method: str, *args, timeout: float = 30.0) -> Any:
        reply = self.request({"kind": "GCS_REQUEST", "method": method,
                              "args": serialization.dumps(args)},
                             timeout=timeout)
        if reply.get("error"):
            raise serialization.loads(reply["error"])
        return serialization.loads(reply["result"])

    def get_function(self, function_id: str):
        fn = self._fn_cache.get(function_id)
        if fn is None:
            blob = self.gcs_call("get_function", function_id)
            if blob is None:
                raise RuntimeError(f"function {function_id} not found in GCS")
            fn = serialization.loads(blob)
            # benign race: concurrent misses both fetch; last write
            # wins and both values are identical deserializations.
            # Taking _req_lock here would serialize GCS fetches.
            self._fn_cache[function_id] = fn  # graftlint: disable=GL001
        return fn

    def put_function(self, function_id: str, blob: bytes) -> None:
        self.gcs_call("put_function", function_id, blob)

    def next_task_id(self) -> TaskID:
        return TaskID.from_random()

    def node_labels(self) -> Dict[str, str]:
        return self.gcs_call("node_labels", self.node_id.binary())

    def as_future(self, ref: ObjectRef):
        from concurrent.futures import Future
        fut: Future = Future()
        def run():
            try:
                fut.set_result(self.get(ref))
            except Exception as e:
                fut.set_exception(e)
        threading.Thread(target=run, daemon=True).start()
        return fut


def _resolve_arg(rt: WorkerRuntime, arg: Arg) -> Any:
    if arg.value_bytes is not None:
        return serialization.unpack(arg.value_bytes)
    return rt._get_one(arg.object_id, timeout=None)


def _resolve_args(rt: WorkerRuntime, spec: TaskSpec):
    args = [_resolve_arg(rt, a) for a in spec.args]
    kwargs = {k: _resolve_arg(rt, a) for k, a in spec.kwargs.items()}
    return args, kwargs


def _stream_item(rt: WorkerRuntime, spec: TaskSpec, index: int,
                 value: Any) -> None:
    """Store one yielded value and report it to the owner incrementally
    (reference: streaming-generator intermediate returns,
    generator_waiter.cc)."""
    oid = ObjectID.from_random()
    kind, data, contained = rt.put_result(oid, value)
    rt.conn.send({"kind": "STREAM_ITEM", "task_id": spec.task_id.binary(),
                  "object_id": oid.binary(), "index": index,
                  "item_kind": kind, "data": data, "contained": contained})


def _stream_out(rt: WorkerRuntime, spec: TaskSpec, result: Any) -> int:
    """Drain a (a)sync generator, reporting each yield. Returns count."""
    import inspect

    if inspect.isasyncgen(result):
        import asyncio

        async def drain():
            count = 0
            async for value in result:
                _stream_item(rt, spec, count, value)
                count += 1
            return count

        return asyncio.run(drain())
    count = 0
    for value in result:
        _stream_item(rt, spec, count, value)
        count += 1
    return count


def _call_target(rt: WorkerRuntime, spec: TaskSpec, args, kwargs) -> Any:
    if spec.actor_id is not None and not spec.is_actor_creation:
        if spec.method_name == "__ray_call__":
            # run an arbitrary function against the actor instance
            # (reference: ActorHandle.__ray_call__ convention used by
            # compiled graphs to install execution loops)
            fn = args[0]
            return fn(rt.actor_instance, *args[1:], **kwargs)
        method = getattr(rt.actor_instance, spec.method_name)
        return method(*args, **kwargs)
    fn = rt.get_function(spec.function_id)
    return fn(*args, **kwargs)


def _pack_reply(rt: WorkerRuntime, spec: TaskSpec, reply: dict,
                result_values: List[Any]) -> dict:
    results = []
    for oid, value in zip(spec.return_ids(), result_values):
        kind, data, contained = rt.put_result(oid, value)
        results.append((oid.binary(), kind, data, contained))
    reply["results"] = results
    reply["error"] = None
    return reply


def _pack_stream_reply(reply: dict, count: int) -> dict:
    reply["stream_len"] = count
    reply["results"] = []
    reply["error"] = None
    return reply


def _pack_error(spec: TaskSpec, reply: dict) -> dict:
    tb = traceback.format_exc()
    # Ship the original exception as .cause when it pickles — callers
    # can catch-and-unwrap domain errors (util.queue Full/Empty, user
    # exception types) instead of string-matching the traceback
    # (reference: RayTaskError.cause, exceptions.py).
    import sys
    exc = sys.exc_info()[1]
    try:
        err = TaskError(spec.name or spec.function_id, tb, exc)
        blob = serialization.dumps(err)
    except Exception:
        err = TaskError(spec.name or spec.function_id, tb, None)
        blob = serialization.dumps(err)
    reply["results"] = []
    reply["error"] = blob
    reply["error_str"] = tb
    return reply


def _enter_trace(spec: TaskSpec):
    """Re-establish the submitter's trace context for this task's
    execution: the task itself is a span (id derived from the task id),
    so nested ``.remote()`` calls and ``tracing.span()`` blocks inside
    user code attach to the same trace. Returns the reset token."""
    from ray_tpu.util import tracing
    if spec.trace_id is None:
        return tracing.set_trace_context(None)
    return tracing.set_trace_context(tracing.TraceContext(
        spec.trace_id, tracing.task_span_id(spec.task_id)))


def _exit_trace(token) -> None:
    from ray_tpu.util import tracing
    tracing.reset_trace_context(token)


def _execute(rt: WorkerRuntime, spec: TaskSpec) -> dict:
    """Run one task/actor-task; returns the TASK_DONE message."""
    rt._current_task_id.value = spec.task_id
    trace_token = _enter_trace(spec)
    reply: dict = {"kind": "TASK_DONE", "task_id": spec.task_id.binary(),
                   "spec_is_actor_creation": spec.is_actor_creation}
    if rt.setup_error is not None:
        reply["results"] = []
        reply["error"] = serialization.dumps(rt.setup_error)
        reply["error_str"] = str(rt.setup_error)
        return reply
    import time as _time
    rt._profile_spans.value = []
    reply["t_start"] = _time.time()
    try:
        args, kwargs = _resolve_args(rt, spec)
        if spec.is_actor_creation:
            cls = rt.get_function(spec.function_id)
            rt.actor_instance = cls(*args, **kwargs)
            rt.actor_id = spec.actor_id
            result_values = [None]
        else:
            result = _call_target(rt, spec, args, kwargs)
            if spec.num_returns == -1:
                return _pack_stream_reply(
                    reply, _stream_out(rt, spec, result))
            result_values = _split_returns(result, spec.num_returns)
        return _pack_reply(rt, spec, reply, result_values)
    except Exception:  # noqa: BLE001 — user code may raise anything
        return _pack_error(spec, reply)
    finally:
        reply["t_end"] = _time.time()
        spans = rt._profile_spans.value
        if spans:
            reply["profile"] = spans
        rt._current_task_id.value = None
        _exit_trace(trace_token)


async def _execute_async(rt: WorkerRuntime, spec: TaskSpec) -> dict:
    """Async-actor execution: awaits coroutine methods and drains async
    generators on the actor's event loop, so ``max_concurrency``
    requests interleave at await points (reference: asyncio actors,
    task_execution/concurrency_group_manager.h + fiber.h)."""
    import asyncio
    import inspect

    rt._current_task_id.value = spec.task_id
    trace_token = _enter_trace(spec)
    reply: dict = {"kind": "TASK_DONE", "task_id": spec.task_id.binary(),
                   "spec_is_actor_creation": False}
    import time as _time
    rt._profile_spans.value = []
    reply["t_start"] = _time.time()
    loop = asyncio.get_running_loop()
    try:
        # Argument resolution may block on object fetches; keep the loop
        # free for other coroutines.
        args, kwargs = await loop.run_in_executor(
            None, _resolve_args, rt, spec)
        result = _call_target(rt, spec, args, kwargs)
        if inspect.iscoroutine(result):
            result = await result
        if spec.num_returns == -1:
            if inspect.isasyncgen(result):
                count = 0
                async for value in result:
                    _stream_item(rt, spec, count, value)
                    count += 1
            else:
                count = await loop.run_in_executor(
                    None, _stream_out, rt, spec, result)
            return _pack_stream_reply(reply, count)
        return _pack_reply(rt, spec, reply,
                           _split_returns(result, spec.num_returns))
    except Exception:  # noqa: BLE001 — user code may raise anything
        return _pack_error(spec, reply)
    finally:
        reply["t_end"] = _time.time()
        spans = rt._profile_spans.value
        if spans:
            reply["profile"] = spans
        rt._current_task_id.value = None
        _exit_trace(trace_token)


def _split_returns(result: Any, num_returns: int) -> List[Any]:
    if num_returns == 1:
        return [result]
    result = list(result)
    if len(result) != num_returns:
        raise ValueError(
            f"task declared num_returns={num_returns} but returned "
            f"{len(result)} values")
    return result


def worker_main(socket_path: str, node_id_hex: str, worker_id_hex: str,
                store_name: str, chips: Optional[List[int]] = None) -> None:
    import faulthandler
    import signal
    faulthandler.register(signal.SIGUSR1)  # kill -USR1 <pid> dumps stacks
    sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    sock.connect(socket_path)
    conn = MessageConnection(sock)
    store = SharedMemoryStore(store_name)
    node_id = NodeID.from_hex(node_id_hex)
    worker_id = WorkerID.from_hex(worker_id_hex)
    rt = WorkerRuntime(conn, store, node_id, worker_id)
    if chips:
        # before anything can start the TPU runtime: a process that
        # held these chips may still be on its way out. If they never
        # come free, every task handed to this worker fails saying so.
        from ray_tpu.accelerators.tpu import TpuAcceleratorManager
        try:
            TpuAcceleratorManager.wait_for_chips(chips)
        except TimeoutError as exc:
            print(f"ray_tpu: {exc}", file=sys.stderr, flush=True)
            rt.setup_error = exc

    from ray_tpu.core import runtime as runtime_mod
    runtime_mod.set_runtime(rt)

    # Flight recorder: enable + start the journal flusher when the
    # driver turned it on (flag rides the inherited environment).
    from ray_tpu.util import flight_recorder
    flight_recorder.init_worker(rt, worker_id)
    # The process's stall watch, always on: a worker that becomes a
    # serve replica or a train worker has one by construction.
    flight_recorder.start_stall_watch("worker")
    # Lifetime sanitizer: same inherit-the-env contract — the ledger and
    # its push flusher start only when the driver exported RAY_TPU_REFSAN.
    refsan.init_worker(rt, worker_id)
    # Collective-program sanitizer: fingerprint ledger + pusher start
    # only when the driver exported RAY_TPU_COLLSAN.
    from ray_tpu.devtools import collsan
    collsan.init_worker(rt, worker_id)
    # Sampling profiler: sampler + profile pusher start only when the
    # driver ran with RAY_TPU_PROFILER (env rides into this process).
    from ray_tpu.devtools import profiler
    profiler.init_worker(rt, worker_id)

    from ray_tpu.core.protocol import PROTOCOL_VERSION
    conn.send({"kind": "REGISTER", "worker_id": worker_id.binary(),
               "pid": os.getpid(), "proto_version": PROTOCOL_VERSION})

    # Apply this worker's runtime env (env_vars / working_dir /
    # py_modules) before any task can run; messages arriving during the
    # blocking KV fetches are deferred into the main loop (ray_tpu/
    # runtime_env/worker_setup.py). pip envs were handled pre-connect.
    deferred_msgs: List[dict] = []
    pip_error = os.environ.get("RTPU_PIP_ERROR")
    if pip_error:
        from ray_tpu.exceptions import RuntimeEnvSetupError
        rt.setup_error = RuntimeEnvSetupError(
            f"runtime_env setup failed: {pip_error}")
    renv_json = os.environ.get("RTPU_RUNTIME_ENV")
    if renv_json and rt.setup_error is None:
        import json as _json
        from ray_tpu.runtime_env import worker_setup
        try:
            worker_setup.apply_runtime_env(renv_json, conn, deferred_msgs)
            rt.current_runtime_env = _json.loads(renv_json)
        except Exception as setup_exc:  # noqa: BLE001
            # A broken env (bad URI, failed extract) must fail the tasks
            # that require it — not crash-loop the worker pool. The
            # worker stays alive and replies RuntimeEnvSetupError to
            # every spec it is handed (_execute short-circuit).
            from ray_tpu.exceptions import RuntimeEnvSetupError
            traceback.print_exc()
            rt.setup_error = RuntimeEnvSetupError(
                f"runtime_env setup failed: {setup_exc!r}")

    exec_pool = ThreadPoolExecutor(max_workers=1)
    pool_lock = threading.Lock()
    # Plain tasks run off a local pending queue on one runner thread;
    # when the current task blocks on an object, queued specs are handed
    # BACK to the node (RETURN_SPECS) so they can run elsewhere — a
    # pipelined batch-mate might be exactly what the task waits for.
    from collections import deque as _deque
    pending: "_deque" = _deque()  # (spec, collector | None)
    pending_cv = threading.Condition()

    class BatchCollector:
        """Aggregates one EXECUTE_BATCH's replies into TASK_DONE_BATCH
        (specs given back reduce the expected count)."""

        def __init__(self, expected: int):
            self.expected = expected
            self.items: list = []

        def add(self, item: dict) -> None:
            with pending_cv:
                self.items.append(item)
                done = len(self.items) >= self.expected
                items = list(self.items) if done else None
            if done:
                conn.send({"kind": "TASK_DONE_BATCH", "items": items})

        def returned(self, count: int) -> None:
            # called under pending_cv
            self.expected -= count
            if self.items and len(self.items) >= self.expected:
                items = list(self.items)
                conn.send({"kind": "TASK_DONE_BATCH", "items": items})

    blocked_depth = [0]

    def on_block(entering: bool) -> None:
        # Explicit blocked/unblocked reports keep the node's pool-cap
        # accounting exact even when a get() times out locally (the
        # node can't infer the unblock from a reply it never sent).
        with pending_cv:
            blocked_depth[0] += 1 if entering else -1
            if not entering:
                notify = blocked_depth[0] == 0
                ids = []
            else:
                notify = blocked_depth[0] == 1
                taken = list(pending)
                pending.clear()
                ids = []
                for spec, collector in taken:
                    ids.append(spec.task_id.binary())
                    if collector is not None:
                        collector.returned(1)
        if entering and ids:
            conn.send({"kind": "RETURN_SPECS", "task_ids": ids})
        if notify:
            conn.send({"kind": "BLOCKED" if entering else "UNBLOCKED"})

    rt.on_block = on_block

    def log_rotation_loop() -> None:
        """Bound this worker's log file: a chatty long-lived worker must
        not fill the disk (reference: rotated worker logs in the session
        dir). At the cap, keep one .1 backup and dup2 a fresh file over
        stdout/stderr — O_APPEND writers continue seamlessly."""
        from ray_tpu.core.config import get_config
        log_path = os.environ.get("RTPU_WORKER_LOG")
        cap = get_config().worker_log_max_bytes
        if not log_path or cap <= 0:
            return
        import time as _time
        while True:
            _time.sleep(30.0)
            try:
                if os.path.getsize(log_path) <= cap:
                    continue
                os.replace(log_path, log_path + ".1")
                fd = os.open(log_path,
                             os.O_CREAT | os.O_WRONLY | os.O_APPEND, 0o644)
                os.dup2(fd, 1)
                os.dup2(fd, 2)
                os.close(fd)
            except OSError:
                pass

    threading.Thread(target=log_rotation_loop, name="log-rotate",
                     daemon=True).start()

    def runner_loop() -> None:
        while True:
            with pending_cv:
                while not pending:
                    pending_cv.wait()
                spec, collector = pending.popleft()
            reply = _execute(rt, spec)
            if collector is None:
                conn.send(reply)
            else:
                collector.add(reply)
            if rt.setup_error is not None:
                # A setup-failed worker must not rejoin the idle pool —
                # a transient cause (GCS blip) would otherwise poison
                # this env's sub-pool forever. Fail what we were handed,
                # then die so the node respawns a clean worker.
                with pending_cv:
                    drained = not pending
                if drained:
                    os._exit(1)

    threading.Thread(target=runner_loop, name="task-runner",
                     daemon=True).start()

    def enqueue(spec: TaskSpec, collector=None) -> None:
        with pending_cv:
            if blocked_depth[0] > 0:
                # runner is blocked on an object: bounce the spec back
                # immediately rather than parking it behind the block
                if collector is not None:
                    collector.returned(1)
                bounce = spec.task_id.binary()
            else:
                pending.append((spec, collector))
                pending_cv.notify()
                return
        conn.send({"kind": "RETURN_SPECS", "task_ids": [bounce]})
    # Async-actor support (reference: asyncio actors — the reference runs
    # coroutine methods on a dedicated event loop so max_concurrency
    # requests interleave at awaits rather than occupying threads).
    actor_state = {"loop": None, "sem": None, "max_concurrency": 1}

    def run_task(spec: TaskSpec):
        reply = _execute(rt, spec)
        conn.send(reply)
        if rt.setup_error is not None:
            os._exit(1)  # see runner_loop: don't poison the pool

    def ensure_actor_loop():
        import asyncio
        if actor_state["loop"] is None:
            loop = asyncio.new_event_loop()
            threading.Thread(target=loop.run_forever,
                             name="actor-loop", daemon=True).start()
            actor_state["loop"] = loop
            actor_state["sem"] = asyncio.Semaphore(
                actor_state["max_concurrency"])
        return actor_state["loop"]

    def run_async_task(spec: TaskSpec):
        import asyncio

        async def run():
            async with actor_state["sem"]:
                reply = await _execute_async(rt, spec)
                conn.send(reply)

        asyncio.run_coroutine_threadsafe(run(), ensure_actor_loop())

    def is_async_actor() -> bool:
        """An actor with ANY coroutine/async-gen method runs ALL its
        methods on the event loop (reference semantics: sync methods of
        asyncio actors execute on the loop, serialized with the rest) —
        per-method routing would let a sync and an async method of a
        max_concurrency=1 actor run concurrently."""
        cached = actor_state.get("is_async")
        if cached is not None:
            return cached
        import inspect
        instance = rt.actor_instance
        if instance is None:
            return False
        # getattr_static: never trigger @property getters or other
        # descriptors — a raising getter must not kill the worker.
        result = False
        for name in dir(type(instance)):
            if name.startswith("__"):
                continue
            attr = inspect.getattr_static(type(instance), name, None)
            if (inspect.iscoroutinefunction(attr)
                    or inspect.isasyncgenfunction(attr)):
                result = True
                break
        actor_state["is_async"] = result
        return result

    def handle_msg(msg: dict) -> bool:
        nonlocal exec_pool
        kind = msg["kind"]
        if kind == "EXECUTE_BATCH":
            # Batched dispatch: execute sequentially off the pending
            # queue, reply once — the head's single IO thread amortizes
            # its per-message cost across the batch.
            specs: List[TaskSpec] = serialization.loads(msg["specs"])
            collector = BatchCollector(len(specs))
            for s in specs:
                enqueue(s, collector)
        elif kind == "EXECUTE":
            enqueue(serialization.loads(msg["spec"]))
        elif kind in ("CREATE_ACTOR", "EXECUTE_ACTOR_TASK"):
            spec: TaskSpec = serialization.loads(msg["spec"])
            if spec.is_actor_creation and spec.max_concurrency > 1:
                with pool_lock:
                    exec_pool = ThreadPoolExecutor(max_workers=spec.max_concurrency)
            if spec.is_actor_creation:
                actor_state["max_concurrency"] = max(1, spec.max_concurrency)
            if kind == "EXECUTE_ACTOR_TASK" and is_async_actor():
                run_async_task(spec)
            else:
                exec_pool.submit(run_task, spec)
        elif kind in ("OBJECT_VALUE", "GCS_REPLY", "READY_REPLY",
                      "STREAM_REPLY", "SPILL_REPLY"):
            rt.deliver_reply(msg)
        elif kind == "PUBSUB_MSG":
            rt._on_pubsub(msg)
        elif kind == "SHUTDOWN":
            return False
        elif kind == "KILL":
            os._exit(1)
        return True

    for msg in deferred_msgs:
        if not handle_msg(msg):
            os._exit(0)
    while True:
        msg = conn.recv()
        if msg is None or not handle_msg(msg):
            break
    os._exit(0)


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--socket", required=True)
    parser.add_argument("--node-id", required=True)
    parser.add_argument("--worker-id", required=True)
    parser.add_argument("--store-name", required=True)
    # the chips this worker owns (node.py::_spawn_worker)
    parser.add_argument("--chips", default="")
    args = parser.parse_args()
    # pip/conda runtime envs must take effect before this process
    # touches its node connection: build (or reuse) the cached
    # venv/conda env and re-exec into its interpreter (exec closes the
    # not-yet-opened socket safely; RTPU_PIP_READY breaks the loop on
    # the second pass).
    renv_json = os.environ.get("RTPU_RUNTIME_ENV")
    if renv_json and not os.environ.get("RTPU_PIP_READY"):
        import json as _json
        renv = _json.loads(renv_json) or {}
        pip_spec = renv.get("pip")
        conda_spec = renv.get("conda")
        python = None
        try:
            if pip_spec:
                from ray_tpu.runtime_env.pip_env import ensure_pip_env
                python = ensure_pip_env(pip_spec)
            elif conda_spec:
                from ray_tpu.runtime_env.conda_env import ensure_conda_env
                python = ensure_conda_env(conda_spec)
        except Exception as exc:  # noqa: BLE001
            # Still connect and register: the failure must travel to
            # the requesting task as RuntimeEnvSetupError, not
            # strand the spec in the node's dispatch queue.
            os.environ["RTPU_PIP_ERROR"] = repr(exc)
        else:
            if python is not None:
                os.environ["RTPU_PIP_READY"] = "1"
                os.execve(
                    python,
                    [python, "-m", "ray_tpu.core.worker"] + sys.argv[1:],
                    dict(os.environ))
    worker_main(args.socket, args.node_id, args.worker_id, args.store_name,
                chips=[int(c) for c in args.chips.split(",") if c])


if __name__ == "__main__":
    main()
