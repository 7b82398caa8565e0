"""One selector IO loop per process for every control-plane socket.

Replaces the thread-per-connection reader design (client reader,
head accept + per-peer readers, object-server accept + per-pull
threads, per-Node selector threads) with a single epoll loop — the
analog of the reference's dedicated asio IO service threads
(client_connection.cc framing + boost::asio event loops).

Frame bytes are handled by one of two codecs, chosen per connection:

- ``_NativeCodec``: the C codec in native/src/wire.cc reached over
  ctypes. All recv/writev syscalls and frame memcpy run with the GIL
  released; outbound frames are coalesced into ~256KB blocks and
  flushed with one writev.
- ``_PyCodec``: pure-Python fallback (protocol.FrameReader +
  ``socket.sendmsg`` vectored flush) selected automatically when g++ /
  the native library is unavailable, or when ``RAY_TPU_NATIVE_WIRE=0``.

Backpressure: each connection has a bounded outbound queue
(``io_loop_high_water_bytes``); producer threads that outrun the
socket block on a drain event until the loop flushes the queue below
the low-water mark. The loop thread itself never blocks — bulk
transfers go through ``send_stream`` which pulls chunks only while the
queue has room.

Teardown discipline: all selector mutations and fd closes happen on
the loop thread (closing a registered fd from another thread can
deliver events for a recycled descriptor). ``on_close`` fires exactly
once per connection — for EOF, fatal errors, and explicit close().
"""

from __future__ import annotations

import ctypes
import logging
import os
import selectors
import socket
import struct
import threading
import time
from collections import deque
from typing import Callable, Iterator, Optional

import heapq

from ray_tpu.core import protocol, serialization
from ray_tpu.core.config import get_config
from ray_tpu.devtools import locktrace, threadguard
from ray_tpu.native import _lib
from ray_tpu.util import flight_recorder as _flight
from ray_tpu.util.metrics import Gauge, Histogram

logger = logging.getLogger(__name__)

_LEN = struct.Struct("<I")
_RECV_CHUNK = 262144
_SENDMSG_IOV = 32

REGISTERED_FDS = Gauge(
    "ray_tpu_core_io_loop_registered_fds",
    "Sockets (connections + listeners) registered with the IO loop")
DISPATCH_SECONDS = Histogram(
    "ray_tpu_core_io_loop_dispatch_latency_seconds",
    "Frame-batch handler latency on the IO loop thread (sampled 1/64)",
    boundaries=[0.00005, 0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005,
                0.01, 0.025, 0.05, 0.1])
QUEUE_DEPTH = Gauge(
    "ray_tpu_core_io_loop_outbound_queue_depth",
    "Peak outbound bytes queued across all loop connections (sampled ~1s)")
PROCESS_THREADS = Gauge(
    "ray_tpu_process_thread_count",
    "Live threads in this process (sampled ~1s by the IO loop)")

# Test hook: force the codec choice regardless of env/toolchain
# (None = automatic). The native choice still degrades to the
# fallback when the library can't be built.
_native_forced: Optional[bool] = None


def use_native_wire() -> bool:
    """True when new connections should use the C codec."""
    if _native_forced is not None:
        return bool(_native_forced) and _lib.try_load() is not None
    env = os.environ.get("RAY_TPU_NATIVE_WIRE", "1").strip().lower()
    if env in ("0", "false", "no", "off"):
        return False
    return _lib.try_load() is not None


# Chaos seam: when set, every new connection's codec is passed through
# this wrapper (devtools/chaos.py installs a fault-injecting shim that
# delays or drops inbound frames deterministically). Test-only — None
# in production, and the indirection costs one None-check per
# connection setup, never per frame.
_codec_wrapper = None


def _make_codec(native: Optional[bool] = None):
    if native is None:
        native = use_native_wire()
    if native:
        lib = _lib.try_load()
        if lib is not None:
            codec = _NativeCodec(lib)
        else:
            codec = _PyCodec()
    else:
        codec = _PyCodec()
    wrapper = _codec_wrapper
    if wrapper is not None:
        codec = wrapper(codec)
    return codec


class _NativeCodec:
    """Per-connection frame state in C (wire.cc). The decoder is only
    touched by the loop thread; the writer is internally mutexed so
    any thread may enqueue/flush. Handles are freed by GC (__del__),
    never eagerly: a racing sender thread may still hold a reference
    mid-call when the loop tears the connection down."""

    native = True

    def __init__(self, lib):
        self._lib = lib
        self._dec = lib.wire_decoder_new()
        self._wr = lib.wire_writer_new()

    def read(self, sock):
        lib = self._lib
        status = lib.wire_decoder_read_fd(self._dec, sock.fileno())
        frames = []
        ptr = ctypes.c_void_p()
        while True:
            n = lib.wire_decoder_next(self._dec, ctypes.byref(ptr))
            if n < 0:
                if n == _lib.WIRE_PROTO:
                    status = _lib.WIRE_PROTO
                break
            frames.append(ctypes.string_at(ptr, n))
        return frames, min(int(status), 0)

    def enqueue(self, payload: bytes) -> int:
        queued = self._lib.wire_writer_enqueue(self._wr, payload,
                                               len(payload))
        if queued < 0:
            raise OSError(f"frame too large ({len(payload)} bytes)")
        return int(queued)

    def flush(self, sock) -> int:
        try:
            fd = sock.fileno()
        except OSError:
            return _lib.WIRE_ERR
        if fd < 0:
            return _lib.WIRE_ERR
        return int(self._lib.wire_writer_flush_fd(self._wr, fd))

    def queued(self) -> int:
        return int(self._lib.wire_writer_queued(self._wr))

    def feed(self, data: bytes) -> None:
        self._lib.wire_decoder_feed(self._dec, bytes(data), len(data))

    def leftover(self) -> bytes:
        ptr = ctypes.c_void_p()
        n = self._lib.wire_decoder_leftover(self._dec, ctypes.byref(ptr))
        return ctypes.string_at(ptr, n) if n > 0 else b""

    def __del__(self):
        lib = getattr(self, "_lib", None)
        if lib is None:
            return
        if getattr(self, "_dec", None):
            lib.wire_decoder_free(self._dec)
        if getattr(self, "_wr", None):
            lib.wire_writer_free(self._wr)


class _PyCodec:
    """Pure-Python codec: FrameReader for inbound parsing and a deque
    of framed buffers flushed with ``socket.sendmsg`` (vectored write,
    the writev analog). Same interface and thread-safety contract as
    _NativeCodec."""

    native = False

    def __init__(self):
        self._reader = protocol.FrameReader()
        self._lock = locktrace.traced_lock("core.io_loop.pycodec")
        self._bufs: deque = deque()
        self._head = 0  # bytes of bufs[0] already sent
        self._queued = 0
        self._prefed: list = []  # frames injected via feed()

    def read(self, sock):
        reader = self._reader
        frames = []
        if self._prefed:
            with self._lock:
                frames, self._prefed = self._prefed, []
        status = 0
        while True:
            try:
                data = sock.recv(_RECV_CHUNK)
            except (BlockingIOError, InterruptedError):
                break
            except OSError:
                status = _lib.WIRE_ERR
                break
            if not data:
                status = _lib.WIRE_EOF
                break
            frames.extend(reader.feed(data))
            if len(data) < _RECV_CHUNK:
                break
        return frames, status

    def enqueue(self, payload: bytes) -> int:
        buf = _LEN.pack(len(payload)) + payload
        with self._lock:
            self._bufs.append(buf)
            self._queued += len(buf)
            return self._queued

    def flush(self, sock) -> int:
        with self._lock:
            while self._bufs:
                iov = [memoryview(self._bufs[0])[self._head:]]
                for i in range(1, min(len(self._bufs), _SENDMSG_IOV)):
                    iov.append(self._bufs[i])
                try:
                    n = sock.sendmsg(iov)
                except (BlockingIOError, InterruptedError):
                    return self._queued
                except OSError:
                    return _lib.WIRE_ERR
                self._queued -= n
                while n > 0:
                    remain = len(self._bufs[0]) - self._head
                    if n >= remain:
                        n -= remain
                        self._head = 0
                        self._bufs.popleft()
                    else:
                        self._head += n
                        n = 0
            return 0

    def queued(self) -> int:
        with self._lock:
            return self._queued

    def feed(self, data: bytes) -> None:
        # Only runs before the connection is live (handshake leftover
        # bytes) — decoded frames are buffered for the next read().
        with self._lock:
            self._prefed.extend(self._reader.feed(bytes(data)))

    def leftover(self) -> bytes:
        return self._reader.leftover()


@threadguard.loop_owned("_streams", "_mask", "_registered")
class LoopConnection:
    """A framed connection serviced by the shared IO loop. Drop-in for
    protocol.MessageConnection on the send side (``send``/``close``/
    ``.sock``); inbound frames are pushed to the registered handler on
    the loop thread instead of being pulled by a reader thread."""

    def __init__(self, loop: "IOLoop", sock: socket.socket,
                 on_frames, on_close, *, label: str, high_water: int,
                 low_water: int, send_timeout: float,
                 native: Optional[bool] = None):
        self._loop = loop
        self.sock = sock
        self.label = label
        self._on_frames = on_frames
        self._on_close = on_close
        self._codec = _make_codec(native)
        self._high_water = high_water
        self._low_water = low_water
        self._send_timeout = send_timeout
        self._streams: deque = deque()
        self._drain = threading.Event()
        self._drain.set()
        self._torn = False
        self._closing = False
        self._registered = False
        self._mask = selectors.EVENT_READ
        self._flush_scheduled = False

    @property
    def native(self) -> bool:
        return self._codec.native

    @property
    def closed(self) -> bool:
        return self._torn or self._closing

    def send(self, msg: dict) -> None:
        protocol._maybe_chaos(msg.get("kind"))
        self.send_frame(serialization.dumps_fast(msg))

    def send_frame(self, payload: bytes) -> None:
        if self._torn or self._closing:
            raise OSError(f"connection closed ({self.label})")
        on_loop = self._loop.on_loop_thread()
        # Backpressure: producer threads (never the loop itself) wait
        # for the loop to drain the queue below the low-water mark.
        if not on_loop and self._codec.queued() >= self._high_water:
            self._wait_drain()
        self._codec.enqueue(bytes(payload))
        remaining = self._codec.flush(self.sock)
        if remaining < 0:
            self._loop._exec_on_loop(self._loop._teardown_conn, self)
            raise OSError(f"connection lost during send ({self.label})")
        if remaining > 0:
            if remaining >= self._high_water:
                self._drain.clear()
                # re-check: the loop may have flushed between our
                # flush and the clear — don't strand waiters
                if self._codec.queued() <= self._low_water:
                    self._drain.set()
            self._request_flush(on_loop)

    def send_stream(self, chunks: Iterator[bytes],
                    on_done: Optional[Callable] = None) -> None:
        """Queue a bulk byte-chunk stream (each chunk becomes one
        frame). The LOOP pulls chunks only while the outbound queue is
        below the low-water mark, so an arbitrarily large stream never
        blocks the loop or balloons memory. ``on_done(None)`` fires on
        completion, ``on_done(exc)`` on failure/teardown."""
        if self._torn or self._closing:
            raise OSError(f"connection closed ({self.label})")

        def _arm():
            if self._torn:
                IOLoop._stream_done(on_done,
                                    ConnectionError("connection closed"))
                return
            self._streams.append((chunks, on_done))
            self._loop._flush_conn(self)

        self._loop._exec_on_loop(_arm)

    def close(self) -> None:
        if self._torn or self._closing:
            return
        self._closing = True
        # Opportunistic final flush so a just-queued goodbye frame
        # (SHUTDOWN, CLIENT_DISCONNECT) reaches the peer before the
        # loop closes the socket.
        try:
            self._codec.flush(self.sock)
        except OSError:
            pass
        self._loop._exec_on_loop(self._loop._teardown_conn, self)

    def fileno(self) -> int:
        return self.sock.fileno()

    def queued_bytes(self) -> int:
        return self._codec.queued()

    def _wait_drain(self) -> None:
        deadline = time.monotonic() + self._send_timeout
        while not self._torn and self._codec.queued() >= self._high_water:
            self._drain.clear()
            if self._torn or self._codec.queued() < self._high_water:
                self._drain.set()
                break
            self._request_flush(False)
            waited = self._drain.wait(
                min(1.0, max(0.0, deadline - time.monotonic())))
            if not waited and time.monotonic() >= deadline:
                raise OSError(
                    f"send backpressure timeout ({self.label}, "
                    f"{self._codec.queued()} bytes queued)")
        if self._torn:
            raise OSError(f"connection closed ({self.label})")

    def _request_flush(self, on_loop: bool) -> None:
        if on_loop:
            self._loop._flush_conn(self)
        elif not self._flush_scheduled:
            self._flush_scheduled = True
            self._loop.call_soon(self._loop._flush_conn, self)


class LoopListener:
    """A listening socket serviced by the loop: accepts on the loop
    thread and hands new sockets to ``on_accept(sock, addr)``."""

    def __init__(self, loop: "IOLoop", sock: socket.socket, on_accept,
                 label: str):
        self._loop = loop
        self.sock = sock
        self.label = label
        self._on_accept = on_accept
        self._torn = False
        self._closed_evt = threading.Event()

    def close(self, wait: bool = True) -> None:
        self._loop._exec_on_loop(self._loop._teardown_listener, self)
        if wait and not self._loop.on_loop_thread():
            self._closed_evt.wait(2.0)


@threadguard.loop_owned("_conns", "_listeners", "_peak_queued",
                         "_dispatch_n", "_last_housekeep")
class IOLoop:
    """The per-process selector loop. Use ``get_io_loop()`` for the
    shared singleton; tests may build private instances and stop()
    them. All selector mutations happen on the loop thread (via
    ``call_soon``); handler callbacks run on the loop thread and must
    not block."""

    def __init__(self, name: str = "rtpu-io-loop",
                 report_metrics: bool = False):
        self._selector = selectors.DefaultSelector()
        self._callbacks: deque = deque()
        self._timers = _Timers()
        self._conns: set = set()
        self._listeners: set = set()
        self._stopped = threading.Event()
        self._report_metrics = report_metrics
        self._dispatch_n = 0
        self._peak_queued = 0
        self._last_housekeep = 0.0
        waker_r, waker_w = socket.socketpair()
        waker_r.setblocking(False)
        waker_w.setblocking(False)
        self._waker_r, self._waker_w = waker_r, waker_w
        self._selector.register(waker_r, selectors.EVENT_READ,
                                ("waker", None))
        self._thread = threading.Thread(target=self._run, name=name,
                                        daemon=True)
        # Opt-in runtime enforcement (RAY_TPU_THREADGUARD=1): this
        # thread's busy window becomes a probe of the process's stall
        # watch, which reports its stack when one dispatch pass exceeds
        # RAY_TPU_THREADGUARD_STALL_S.
        self._guard = (threadguard.LoopStallWatchdog(self._thread)
                       if threadguard.enabled() else None)
        self._thread.start()

    # ------------------------------------------------------------- API

    def on_loop_thread(self) -> bool:
        return threading.current_thread() is self._thread

    def register(self, sock: socket.socket, on_frames,
                 on_close=None, *, label: str = "",
                 native: Optional[bool] = None,
                 high_water: Optional[int] = None,
                 low_water: Optional[int] = None) -> LoopConnection:
        """Adopt a connected socket; ``on_frames(conn, frames)`` runs
        on the loop thread for each batch of complete frames."""
        cfg = get_config()
        sock.setblocking(False)
        conn = LoopConnection(
            self, sock, on_frames, on_close, label=label, native=native,
            high_water=high_water or cfg.io_loop_high_water_bytes,
            low_water=low_water or cfg.io_loop_low_water_bytes,
            send_timeout=cfg.io_loop_send_timeout_s)
        self._exec_on_loop(self._do_register, conn)
        return conn

    def register_message_conn(self, sock: socket.socket, on_msg,
                              on_close=None, **kw) -> LoopConnection:
        """register() plus per-frame deserialization: ``on_msg(conn,
        msg_dict)``. One bad frame/handler is logged and skipped, not
        fatal to the connection."""

        def _on_frames(conn, frames):
            for frame in frames:
                try:
                    msg = serialization.loads(frame)
                except Exception:
                    logger.exception("io_loop: undecodable frame (%s)",
                                     conn.label)
                    continue
                try:
                    on_msg(conn, msg)
                except Exception:
                    logger.exception("io_loop: message handler error (%s)",
                                     conn.label)

        return self.register(sock, _on_frames, on_close, **kw)

    def register_listener(self, sock: socket.socket, on_accept,
                          label: str = "") -> LoopListener:
        sock.setblocking(False)
        lst = LoopListener(self, sock, on_accept, label)
        self._exec_on_loop(self._do_register_listener, lst)
        return lst

    def call_soon(self, fn, *args) -> None:
        """Run ``fn(*args)`` on the loop thread ASAP (thread-safe)."""
        self._callbacks.append((fn, args))
        if not self.on_loop_thread():
            self.wake()

    def call_later(self, delay: float, fn, *args) -> None:
        self._timers.add(time.monotonic() + delay, fn, args)
        if not self.on_loop_thread():
            self.wake()

    def wake(self) -> None:
        try:
            self._waker_w.send(b"\x00")
        except (BlockingIOError, InterruptedError):
            pass  # waker pipe already full -> loop is already waking
        except OSError:
            pass

    @threadguard.loop_only
    def detach(self, conn: LoopConnection) -> socket.socket:
        """Loop-thread only: unregister without closing the socket
        (protocol handoff, e.g. CAPI sessions). The caller owns the
        socket afterwards; on_close does NOT fire."""
        assert self.on_loop_thread()
        conn._torn = True
        conn._on_close = None
        if conn._registered:
            try:
                self._selector.unregister(conn.sock)
            except (KeyError, ValueError, OSError):
                pass
            conn._registered = False
        self._conns.discard(conn)
        self._update_fd_gauge()
        conn._drain.set()
        return conn.sock

    def barrier(self, timeout: float = 5.0) -> bool:
        """Block until the loop has processed everything queued before
        this call (test/diagnostic helper)."""
        if self.on_loop_thread():
            return True
        evt = threading.Event()
        self.call_soon(evt.set)
        return evt.wait(timeout)

    def stop(self) -> None:
        """Stop the loop and tear down every registered socket. Only
        for privately constructed loops (tests); the process singleton
        lives for the life of the process."""
        self._stopped.set()
        self.wake()
        if not self.on_loop_thread():
            self._thread.join(5.0)

    # ------------------------------------------------ loop internals

    def _exec_on_loop(self, fn, *args) -> None:
        if self.on_loop_thread():
            fn(*args)
        else:
            self.call_soon(fn, *args)

    @threadguard.loop_only
    def _do_register(self, conn: LoopConnection) -> None:
        if conn._torn or conn._closing:
            self._teardown_conn(conn)
            return
        try:
            self._selector.register(conn.sock, selectors.EVENT_READ,
                                    ("conn", conn))
        except (KeyError, ValueError, OSError):
            self._teardown_conn(conn)
            return
        conn._registered = True
        self._conns.add(conn)
        self._update_fd_gauge()
        if conn._codec.queued() or conn._streams:
            self._flush_conn(conn)

    @threadguard.loop_only
    def _do_register_listener(self, lst: LoopListener) -> None:
        if lst._torn:
            return
        try:
            self._selector.register(lst.sock, selectors.EVENT_READ,
                                    ("listener", lst))
        except (KeyError, ValueError, OSError):
            self._teardown_listener(lst)
            return
        self._listeners.add(lst)
        self._update_fd_gauge()

    @threadguard.loop_only
    def _run(self) -> None:
        guard = self._guard
        if guard:
            guard.enter()
        while not self._stopped.is_set():
            self._run_callbacks()
            timeout = 0.5
            deadline = self._timers.next_deadline()
            if deadline is not None:
                timeout = min(timeout,
                              max(0.0, deadline - time.monotonic()))
            if self._callbacks:
                timeout = 0.0
            if guard:
                guard.exit_busy()
            try:
                events = self._selector.select(timeout)
            except OSError:
                continue
            finally:
                if guard:
                    guard.enter()
            for key, mask in events:
                kind, obj = key.data
                try:
                    if kind == "waker":
                        self._drain_waker()
                    elif kind == "listener":
                        self._service_accept(obj)
                    else:
                        self._service_conn(obj, mask)
                except Exception:
                    logger.exception("io_loop: %s handler error", kind)
            now = time.monotonic()
            for fn, args in self._timers.pop_due(now):
                try:
                    fn(*args)
                except Exception:
                    logger.exception("io_loop: timer error")
            self._housekeep(now)
        self._finalize()
        if guard:
            guard.stop()

    def _run_callbacks(self) -> None:
        # Bounded drain: callbacks scheduled while running wait for
        # the next pass so socket events can't be starved.
        for _ in range(len(self._callbacks)):
            try:
                fn, args = self._callbacks.popleft()
            except IndexError:
                break
            try:
                fn(*args)
            except Exception:
                logger.exception("io_loop: callback error")

    def _drain_waker(self) -> None:
        try:
            # non-blocking socketpair: recv returns EAGAIN, never waits
            while self._waker_r.recv(4096):  # graftlint: disable=GL009
                pass
        except (BlockingIOError, InterruptedError):
            pass
        except OSError:
            pass

    @threadguard.loop_only
    def _service_accept(self, lst: LoopListener) -> None:
        while True:
            try:
                # listener is non-blocking: accept never waits
                sock, addr = lst.sock.accept()  # graftlint: disable=GL009
            except (BlockingIOError, InterruptedError):
                return
            except OSError:
                self._teardown_listener(lst)
                return
            try:
                lst._on_accept(sock, addr)
            except Exception:
                logger.exception("io_loop: accept handler error (%s)",
                                 lst.label)
                try:
                    sock.close()
                except OSError:
                    pass

    @threadguard.loop_only
    def _service_conn(self, conn: LoopConnection, mask: int) -> None:
        if conn._torn:
            return
        if mask & selectors.EVENT_WRITE:
            self._flush_conn(conn)
            if conn._torn:
                return
        if mask & selectors.EVENT_READ:
            frames, status = conn._codec.read(conn.sock)
            if frames:
                self._dispatch(conn, frames)
            if status < 0:
                self._teardown_conn(conn)

    @threadguard.loop_only
    def _dispatch(self, conn: LoopConnection, frames) -> None:
        self._dispatch_n += 1
        timed = self._report_metrics and (self._dispatch_n & 63) == 0
        rec = _flight.RECORDER  # lock-free journal; no RPC (GL013)
        t0 = time.perf_counter() if timed else 0.0
        t0_ns = rec.clock() if rec is not None else 0
        try:
            conn._on_frames(conn, frames)
        except Exception:
            logger.exception("io_loop: frame handler error (%s)",
                             conn.label)
        if rec is not None:
            rec.record("io", "dispatch", t0_ns, rec.clock() - t0_ns,
                       {"conn": conn.label, "frames": len(frames)})
        if timed:
            # observe_local: a forwarding _record from the loop thread
            # would block on a reply only this thread can dispatch.
            DISPATCH_SECONDS.observe_local(time.perf_counter() - t0)

    @threadguard.loop_only
    def _flush_conn(self, conn: LoopConnection) -> None:
        if conn._torn:
            return
        conn._flush_scheduled = False
        remaining = conn._codec.flush(conn.sock)
        if remaining < 0:
            self._teardown_conn(conn)
            return
        # Pull stream chunks while there's room: the stream never
        # outruns the socket by more than ~low_water bytes.
        rec = _flight.RECORDER  # lock-free journal; no RPC (GL013)
        # not a retry loop: each except-continue pops the finished
        # stream first, so every re-entry makes progress
        while conn._streams and remaining < conn._low_water:  # graftlint: disable=GL019
            gen, on_done = conn._streams[0]
            t0_ns = rec.clock() if rec is not None else 0
            try:
                chunk = next(gen)
            except StopIteration:
                conn._streams.popleft()
                self._stream_done(on_done, None)
                continue
            except Exception as exc:
                conn._streams.popleft()
                self._stream_done(on_done, exc)
                continue
            if rec is not None:
                rec.record("io", "stream_chunk", t0_ns,
                           rec.clock() - t0_ns,
                           {"conn": conn.label, "bytes": len(chunk)})
            try:
                conn._codec.enqueue(bytes(chunk))
            except OSError as exc:
                conn._streams.popleft()
                self._stream_done(on_done, exc)
                self._teardown_conn(conn)
                return
            remaining = conn._codec.flush(conn.sock)
            if remaining < 0:
                self._teardown_conn(conn)
                return
        if remaining > self._peak_queued:
            self._peak_queued = remaining
        if remaining <= conn._low_water:
            conn._drain.set()
        self._set_write_interest(conn,
                                 remaining > 0 or bool(conn._streams))

    @threadguard.loop_only
    def _set_write_interest(self, conn: LoopConnection,
                            want: bool) -> None:
        if not conn._registered or conn._torn:
            return
        mask = selectors.EVENT_READ | (selectors.EVENT_WRITE if want
                                       else 0)
        if mask == conn._mask:
            return
        try:
            self._selector.modify(conn.sock, mask, ("conn", conn))
            conn._mask = mask
        except (KeyError, ValueError, OSError):
            pass

    @threadguard.loop_only
    def _teardown_conn(self, conn: LoopConnection) -> None:
        if conn._torn:
            return
        conn._torn = True
        if conn._registered:
            try:
                self._selector.unregister(conn.sock)
            except (KeyError, ValueError, OSError):
                pass
            conn._registered = False
        self._conns.discard(conn)
        self._update_fd_gauge()
        try:
            conn.sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        try:
            conn.sock.close()
        except OSError:
            pass
        conn._drain.set()  # unblock backpressured senders -> they raise
        streams, conn._streams = list(conn._streams), deque()
        for gen, on_done in streams:
            try:
                gen.close()
            except Exception:
                logger.debug("io_loop: stream close error", exc_info=True)
            self._stream_done(
                on_done, ConnectionError(f"connection closed "
                                         f"({conn.label})"))
        if conn._on_close is not None:
            cb, conn._on_close = conn._on_close, None
            try:
                cb(conn)
            except Exception:
                logger.exception("io_loop: on_close error (%s)",
                                 conn.label)

    @threadguard.loop_only
    def _teardown_listener(self, lst: LoopListener) -> None:
        if lst._torn:
            lst._closed_evt.set()
            return
        lst._torn = True
        try:
            self._selector.unregister(lst.sock)
        except (KeyError, ValueError, OSError):
            pass
        self._listeners.discard(lst)
        self._update_fd_gauge()
        try:
            lst.sock.close()
        except OSError:
            pass
        lst._closed_evt.set()

    @staticmethod
    def _stream_done(on_done, exc) -> None:
        if on_done is None:
            return
        try:
            on_done(exc)
        except Exception:
            logger.exception("io_loop: stream completion callback error")

    def _update_fd_gauge(self) -> None:
        if self._report_metrics:
            REGISTERED_FDS.set_local(
                float(len(self._conns) + len(self._listeners)))

    def _housekeep(self, now: float) -> None:
        if now - self._last_housekeep < 1.0:
            return
        self._last_housekeep = now
        if not self._report_metrics:
            return
        total = 0
        for conn in self._conns:
            total += conn._codec.queued()
        QUEUE_DEPTH.set_local(float(max(total, self._peak_queued)))
        self._peak_queued = 0
        PROCESS_THREADS.set_local(float(threading.active_count()))

    @threadguard.loop_only
    def _finalize(self) -> None:
        for conn in list(self._conns):
            self._teardown_conn(conn)
        for lst in list(self._listeners):
            self._teardown_listener(lst)
        try:
            self._selector.close()
        except OSError:
            pass
        for s in (self._waker_r, self._waker_w):
            try:
                s.close()
            except OSError:
                pass


class _Timers:
    """Monotonic-deadline timer heap, mutated from any thread."""

    def __init__(self):
        self._lock = locktrace.traced_lock("core.io_loop.timers")
        self._heap: list = []
        self._seq = 0

    def add(self, when: float, fn, args) -> None:
        with self._lock:
            self._seq += 1
            heapq.heappush(self._heap, (when, self._seq, fn, args))

    def next_deadline(self) -> Optional[float]:
        with self._lock:
            return self._heap[0][0] if self._heap else None

    def pop_due(self, now: float):
        due = []
        with self._lock:
            while self._heap and self._heap[0][0] <= now:
                _, _, fn, args = heapq.heappop(self._heap)
                due.append((fn, args))
        return due


_singleton: Optional[IOLoop] = None
_singleton_lock = threading.Lock()


def get_io_loop() -> IOLoop:
    """The process-wide IO loop (started on first use, restarted if
    its thread ever died). This is the ONE socket-servicing thread the
    whole control plane shares."""
    global _singleton
    loop = _singleton
    if loop is not None and loop._thread.is_alive():
        return loop
    with _singleton_lock:
        if _singleton is None or not _singleton._thread.is_alive():
            _singleton = IOLoop(report_metrics=True)
        return _singleton
