"""Open-loop load generator for streamed completions: one process,
one thread, non-blocking sockets.

It runs as a child of the runner (``python3 benchmark/loadgen.py``,
the window's spec as JSON on stdin, one JSON object on stdout) so that
it shares no interpreter lock with the HTTP proxy, which lives in the
runner's process. It imports nothing but the standard library.

Each request is sent when its ``due`` time comes, whatever the state
of the others. Every streamed chunk is stamped as it is read. Times
are seconds from the window's start.
"""

from __future__ import annotations

import errno
import json
import selectors
import socket
import sys
import time
from typing import Any, Dict, List

# a chunk that carries a token, as /v1/completions streams it; the
# closing chunk has "finish_reason": "stop"
_TOKEN_MARK = b'"finish_reason": null'
_DONE_MARK = b"data: [DONE]"


class _Conn:
    def __init__(self, index: int, sock: socket.socket, out: bytes):
        self.index = index
        self.sock = sock
        self.out = out
        self.buf = b""
        self.head_done = False


def _request_bytes(host: str, port: int, path: str,
                   body: Dict[str, Any]) -> bytes:
    data = json.dumps(body).encode()
    head = (f"POST {path} HTTP/1.1\r\nHost: {host}:{port}\r\n"
            "Content-Type: application/json\r\n"
            f"Content-Length: {len(data)}\r\nConnection: close\r\n\r\n")
    return head.encode() + data


def run_window(spec: Dict[str, Any]) -> Dict[str, Any]:
    """``spec``: host, port, path, window_s, drain (wait for what is
    in flight when the window closes, at most drain_timeout_s), and
    requests [{due, body}] sorted by due."""
    host, port, path = spec["host"], spec["port"], spec["path"]
    window_s = float(spec["window_s"])
    requests = spec["requests"]
    records: List[Dict[str, Any]] = [
        {"due": r["due"], "sent": None, "status": None, "token_times": [],
         "finished": False, "error": None} for r in requests]
    sel = selectors.DefaultSelector()
    open_conns: Dict[int, _Conn] = {}
    t0 = time.monotonic() + 0.05
    next_i = 0
    hard_stop = window_s + (float(spec.get("drain_timeout_s", 60.0))
                            if spec.get("drain") else 0.0)

    def close(conn: _Conn, error: str = None) -> None:
        rec = records[conn.index]
        if error and not rec["finished"]:
            rec["error"] = error
        try:
            sel.unregister(conn.sock)
        except (KeyError, ValueError):
            pass
        conn.sock.close()
        open_conns.pop(conn.index, None)

    def on_bytes(conn: _Conn, data: bytes, now: float) -> None:
        rec = records[conn.index]
        conn.buf += data
        if not conn.head_done:
            end = conn.buf.find(b"\r\n\r\n")
            if end < 0:
                return
            status_line = conn.buf[:conn.buf.find(b"\r\n")]
            parts = status_line.split()
            rec["status"] = int(parts[1]) if len(parts) > 1 else None
            conn.buf = conn.buf[end + 4:]
            conn.head_done = True
        while True:
            nl = conn.buf.find(b"\n")
            if nl < 0:
                break
            line, conn.buf = conn.buf[:nl], conn.buf[nl + 1:]
            if _TOKEN_MARK in line:
                rec["token_times"].append(now)
            elif line.startswith(_DONE_MARK):
                rec["finished"] = True

    while True:
        now = time.monotonic() - t0
        while (next_i < len(requests) and requests[next_i]["due"] <= now
               and now < window_s):
            sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            sock.setblocking(False)
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            rc = sock.connect_ex((host, port))
            conn = _Conn(next_i, sock, _request_bytes(
                host, port, path, requests[next_i]["body"]))
            records[next_i]["sent"] = now
            open_conns[next_i] = conn
            if rc not in (0, errno.EINPROGRESS):
                close(conn, f"connect: {errno.errorcode.get(rc, rc)}")
            else:
                sel.register(sock, selectors.EVENT_WRITE, conn)
            next_i += 1
        sending_over = next_i >= len(requests) or now >= window_s
        if now >= hard_stop or (sending_over and not open_conns):
            break
        if not spec.get("drain") and now >= window_s:
            break
        waits = [0.05, hard_stop - now]
        if now < window_s:
            waits.append(window_s - now)
            if next_i < len(requests):
                waits.append(requests[next_i]["due"] - now)
        for key, events in sel.select(max(0.0, min(waits))):
            conn = key.data
            stamp = time.monotonic() - t0
            try:
                if events & selectors.EVENT_WRITE:
                    err = conn.sock.getsockopt(socket.SOL_SOCKET,
                                               socket.SO_ERROR)
                    if err:
                        close(conn, f"connect: {errno.errorcode.get(err, err)}")
                        continue
                    sent = conn.sock.send(conn.out)
                    conn.out = conn.out[sent:]
                    if not conn.out:
                        sel.modify(conn.sock, selectors.EVENT_READ, conn)
                if events & selectors.EVENT_READ:
                    data = conn.sock.recv(65536)
                    if not data:
                        rec = records[conn.index]
                        close(conn, None if rec["finished"]
                              else "closed before [DONE]")
                        continue
                    on_bytes(conn, data, stamp)
            except BlockingIOError:
                continue
            except OSError as exc:
                close(conn, f"{type(exc).__name__}: {exc}")
    in_flight = len(open_conns)
    for conn in list(open_conns.values()):
        close(conn)
    sel.close()
    return {"t0_monotonic": t0, "records": records,
            "in_flight_at_end": in_flight,
            "ended_s": time.monotonic() - t0}


def main() -> None:
    spec = json.loads(sys.stdin.read())
    json.dump(run_window(spec), sys.stdout)
    sys.stdout.write("\n")


if __name__ == "__main__":
    main()
