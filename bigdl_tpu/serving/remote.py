"""Cross-process serving fabric (PR 14): host any serving backend in a
child process behind the ReplicaSet contract.

Two halves, one wire (:mod:`bigdl_tpu.serving.rpc`):

- :class:`ReplicaServer` wraps a backend (GenerationEngine,
  InferenceService, or any duck-typed stub) behind a listening socket.
  Requests are fully asynchronous — ``submit`` registers the backend
  handle's done-callback and the response frame goes out whenever the
  work finishes, so one slow stream never head-of-line-blocks the
  connection. Responses are cached by request id (bounded LRU), so a
  hedged or retried duplicate is answered from the cache instead of
  re-executed — idempotency is the server's job, not the client's hope.
- :class:`RemoteReplica` is the client proxy: ``submit`` returns a
  future-shaped handle (``result``/``exception``/``add_done_callback``/
  ``cancel``), exactly what :class:`~bigdl_tpu.serving.replica
  .ReplicaSet` tracks, so a remote process drops into a set next to
  in-process engines with no adapter.

The robustness layer is the point of the PR:

- **deadlines propagate.** The remaining budget rides the request
  header; the server fails an already-expired request immediately and
  otherwise hands the budget to the backend (engines/services natively
  retire expired work — no zombie in-flight). The client keeps a local
  backstop: at ``deadline + grace`` a pending future fails with
  :class:`DeadlineExceeded` even if the remote is wedged.
- **circuit breaker.** Consecutive transport failures open the breaker
  for a cooldown; while open, ``submit`` fast-fails with
  :class:`TransportError` — which the ReplicaSet counts as an engine
  error, so the breaker FEEDS the existing consecutive-failure
  eviction instead of duplicating it. Probes go through half-open.
- **reconnect under RetryPolicy.** Connects are paced by the shared
  :class:`~bigdl_tpu.faults.RetryPolicy` (deterministic jitter), and
  every failure mode is injectable at the seeded ``rpc.*`` fault sites.
- **draining disconnects.** ``close(drain=True)`` waits for in-flight
  responses before the socket drops, and the server's draining close
  waits for its backend — rolling reloads never drop work.

``python -m bigdl_tpu.serving.remote --factory pkg.mod:fn`` is the
child-process entry (prints ``RPC_READY host port`` once listening);
:func:`start_replica_process` wraps the spawn/handshake and
``RemoteReplica.revive()`` relaunches a SIGKILLed child so the
ReplicaSet prober drives the whole death-and-rejoin cycle."""

from __future__ import annotations

import argparse
import collections
import heapq
import importlib
import logging
import os
import signal
import socket
import subprocess
import sys
import threading
import time
import uuid
from concurrent.futures import Future
from concurrent.futures import TimeoutError as _FutureTimeout
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from bigdl_tpu import faults
from bigdl_tpu.faults import RetryPolicy
from bigdl_tpu.obs.recorder import record_event
from bigdl_tpu.serving import rpc
from bigdl_tpu.serving.errors import DeadlineExceeded, TransportError

log = logging.getLogger("bigdl_tpu.serving")


def _handle_outcome(handle) -> Tuple[Any, Optional[BaseException]]:
    """(result, error) of a COMPLETED backend handle — the same probing
    order as ReplicaSet._handle_error (``.error`` streams first, then
    future ``.exception()``)."""
    err = getattr(handle, "error", None)
    if err is None and hasattr(handle, "exception"):
        try:
            err = handle.exception(timeout=0)
        except TypeError:
            err = handle.exception()
        except BaseException as e:
            err = e
    if err is not None:
        return None, err
    try:
        return handle.result(timeout=5), None
    except BaseException as e:
        return None, e


# ================================================================ server ==

class _Conn:
    """One accepted client connection: socket + a send lock (responses
    come from backend callback threads; frames must not interleave)."""

    __slots__ = ("sock", "lock", "alive")

    def __init__(self, sock: socket.socket):
        self.sock = sock
        self.lock = threading.Lock()
        self.alive = True

    def send_bytes(self, packed: bytes) -> bool:
        try:
            with self.lock:
                self.sock.sendall(packed)
            return True
        except OSError:
            self.alive = False
            return False

    def close(self) -> None:
        self.alive = False
        try:
            self.sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        try:
            self.sock.close()
        except OSError:
            pass


class ReplicaServer:
    """Serve one backend over the rpc wire. Listening starts in the
    constructor (``port=0`` binds an ephemeral port — read ``.port``);
    ``hard_exit=True`` (the ``__main__`` entry sets it) makes an
    injected ``rpc.peer_kill`` fault hard-exit the PROCESS — the
    in-band, seeded equivalent of SIGKILL; thread-hosted servers
    instead drop every socket without drain, which is what the peer
    observes either way."""

    def __init__(self, backend, *, host: str = "127.0.0.1", port: int = 0,
                 name: str = "remote", idempotency_cap: int = 256,
                 hard_exit: bool = False):
        self.backend = backend
        self.name = name
        self._hard_exit = hard_exit
        self._lock = threading.Lock()
        self._drain_cond = threading.Condition(self._lock)
        self._inflight: Dict[str, dict] = {}     # rid -> {handle, conns}
        self._done_cache: "collections.OrderedDict[str, bytes]" = \
            collections.OrderedDict()
        self._idem_cap = int(idempotency_cap)
        self._req_count = 0
        self.served = 0
        self.duplicates = 0                       # answered from the cache
        self._conns: List[_Conn] = []
        self._closed = threading.Event()
        self._aborted = False
        self._listener = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._listener.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._listener.bind((host, int(port)))
        self._listener.listen(16)
        self.host, self.port = self._listener.getsockname()[:2]
        self._accept_thread = threading.Thread(
            target=self._accept_loop, name="bigdl-rpc-accept", daemon=True)
        self._accept_thread.start()

    # ------------------------------------------------------- socket IO ----

    def _accept_loop(self) -> None:
        # the listener is closed HERE, after the loop: closing a socket
        # another thread is blocked in accept() on does not reliably
        # release the kernel listen queue (the in-flight syscall pins
        # the file), so close()/abort() instead set _closed, poke the
        # port awake, and let this thread do the real close
        while not self._closed.is_set():
            try:
                sock, _ = self._listener.accept()
            except OSError:
                break
            if self._closed.is_set():
                try:
                    sock.close()
                except OSError:
                    pass
                break
            conn = _Conn(sock)
            with self._lock:
                self._conns.append(conn)
            threading.Thread(target=self._client_loop, args=(conn,),
                             name="bigdl-rpc-serve", daemon=True).start()
        try:
            self._listener.close()
        except OSError:
            pass

    def _client_loop(self, conn: _Conn) -> None:
        try:
            conn.sock.setsockopt(socket.IPPROTO_TCP,
                                 socket.TCP_NODELAY, 1)
            rpc.server_handshake(conn.sock)
            while conn.alive:
                msg = rpc.recv_frame(conn.sock)
                self._handle(conn, msg)
        except (OSError, ConnectionError, TransportError):
            pass  # peer went away; in-flight work keeps running and its
            #       responses stay in the idempotency cache for a retry
        finally:
            conn.close()
            with self._lock:
                if conn in self._conns:
                    self._conns.remove(conn)

    def _stop_listening(self) -> None:
        """Set _closed, wake a blocked accept with a throwaway connect,
        and wait for the accept thread to close the listener itself."""
        self._closed.set()
        try:
            poke = socket.create_connection((self.host, self.port),
                                            timeout=0.5)
            poke.close()
        except OSError:
            pass  # already released
        self._accept_thread.join(timeout=5)
        try:
            self._listener.close()
        except OSError:
            pass

    def _reply(self, conn: _Conn, rid, ok: bool, payload) -> None:
        tree = {"id": rid, "ok": ok,
                ("result" if ok else "error"): payload}
        try:
            packed = rpc.pack_frame(tree)
        except TypeError as e:
            # un-encodable RESULT: degrade to a typed error, never a
            # silent hang on the client's pending future
            packed = rpc.pack_frame(
                {"id": rid, "ok": False,
                 "error": TransportError(f"unencodable response: {e}")})
        conn.send_bytes(packed)

    # ------------------------------------------------------- dispatch ----

    def _handle(self, conn: _Conn, msg: dict) -> None:
        rid = msg.get("id")
        method = msg.get("method")
        with self._lock:
            self._req_count += 1
            idx = self._req_count
        try:
            faults.fire("rpc.peer_kill", key=idx, method=method)
        except BaseException:
            # the seeded SIGKILL: a child process dies for real; a
            # thread-hosted server drops every socket without drain
            # (exactly what the peer of a killed process observes)
            if self._hard_exit:
                os._exit(137)
            self.abort()
            return
        try:
            if method == "submit":
                self._handle_submit(conn, rid, msg)
                return
            if method == "ping":
                result = "pong"
            elif method == "snapshot":
                result = self.snapshot()
            elif method == "reload":
                state = msg.get("state")
                if state is None:
                    self.backend.reload(msg["params"])
                else:
                    self.backend.reload(msg["params"], state)
                result = "reloaded"
            elif method == "warmup":
                self.backend.warmup(*(msg.get("args") or []),
                                    **(msg.get("kwargs") or {}))
                result = "warm"
            elif method == "arm_fault":
                spec = faults.arm(msg["site"], **(msg.get("spec") or {}))
                result = {"site": spec.site}
            elif method == "disarm_fault":
                faults.disarm(msg["site"])
                result = "disarmed"
            elif method == "reset_faults":
                faults.reset()
                result = "reset"
            elif method == "fault_snapshot":
                result = faults.snapshot()
            elif method == "recorder_count":
                from bigdl_tpu.obs import flight_recorder

                result = flight_recorder().count(msg["kind"])
            elif method == "close":
                self._handle_close(conn, rid, msg)
                return
            else:
                raise ValueError(f"unknown rpc method {method!r}")
        except BaseException as e:
            self._reply(conn, rid, False, e)
            return
        self._reply(conn, rid, True, result)

    def _handle_submit(self, conn: _Conn, rid, msg: dict) -> None:
        kwargs = dict(msg.get("kwargs") or {})
        deadline_ms = msg.get("deadline_ms")
        if deadline_ms is not None:
            if deadline_ms <= 0:
                # expired in flight: abandon BEFORE the backend sees it
                self._reply(conn, rid, False,
                            DeadlineExceeded(0.0, deadline_ms / 1e3))
                return
            kwargs["deadline"] = deadline_ms / 1e3
        with self._lock:
            cached = self._done_cache.get(rid)
            if cached is not None:
                self._done_cache.move_to_end(rid)
                self.duplicates += 1
            else:
                rec = self._inflight.get(rid)
                if rec is not None:
                    # duplicate of RUNNING work (a hedge retry): attach
                    # this connection, never execute twice
                    self.duplicates += 1
                    if conn not in rec["conns"]:
                        rec["conns"].append(conn)
                    return
        if cached is not None:
            conn.send_bytes(cached)
            return
        try:
            handle = self.backend.submit(msg.get("x"), **kwargs)
        except BaseException as e:
            self._reply(conn, rid, False, e)
            return
        with self._lock:
            self._inflight[rid] = {"handle": handle, "conns": [conn]}
        handle.add_done_callback(lambda h: self._finish_submit(rid, h))

    def _finish_submit(self, rid, handle) -> None:
        result, err = _handle_outcome(handle)
        tree = {"id": rid, "ok": err is None,
                ("result" if err is None else "error"):
                    result if err is None else err}
        try:
            packed = rpc.pack_frame(tree)
        except TypeError as e:
            packed = rpc.pack_frame(
                {"id": rid, "ok": False,
                 "error": TransportError(f"unencodable response: {e}")})
        with self._drain_cond:
            rec = self._inflight.pop(rid, None)
            self._done_cache[rid] = packed
            while len(self._done_cache) > self._idem_cap:
                self._done_cache.popitem(last=False)
            if err is None:
                self.served += 1
            conns = list(rec["conns"]) if rec else []
            self._drain_cond.notify_all()
        for conn in conns:
            conn.send_bytes(packed)

    def _handle_close(self, conn: _Conn, rid, msg: dict) -> None:
        drain = bool(msg.get("drain", True))
        timeout = msg.get("timeout")
        if drain:
            self.drain(timeout)
        self._reply(conn, rid, True, "closing")
        threading.Thread(target=self.close, kwargs={"drain": False},
                         name="bigdl-rpc-shutdown", daemon=True).start()

    # ------------------------------------------------------ lifecycle ----

    def drain(self, timeout: Optional[float] = None) -> bool:
        """Wait for every in-flight backend handle to finish."""
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._drain_cond:
            while self._inflight:
                left = None if deadline is None \
                    else deadline - time.monotonic()
                if left is not None and left <= 0:
                    return False
                self._drain_cond.wait(timeout=left if left is not None
                                      else 0.5)
            return True

    def abort(self) -> None:
        """Drop the listener and every connection WITHOUT drain — the
        thread-hosted stand-in for a killed process."""
        self._aborted = True
        self._stop_listening()
        with self._lock:
            conns = list(self._conns)
        for c in conns:
            c.close()

    def close(self, drain: bool = True,
              timeout: Optional[float] = None) -> None:
        if drain:
            self.drain(timeout)
        self._stop_listening()
        with self._lock:
            conns = list(self._conns)
        for c in conns:
            c.close()

    def wait_closed(self, timeout: Optional[float] = None) -> bool:
        return self._closed.wait(timeout)

    @property
    def inflight(self) -> int:
        with self._lock:
            return len(self._inflight)

    def snapshot(self) -> dict:
        with self._lock:
            out = {"name": self.name, "inflight": len(self._inflight),
                   "served": self.served, "duplicates": self.duplicates,
                   "requests": self._req_count,
                   "connections": len(self._conns)}
        pages = getattr(self.backend, "pages_in_use", None)
        if pages is not None:
            out["pages_in_use"] = pages
        m = getattr(self.backend, "metrics", None)
        if m is not None:
            out["backend"] = m.snapshot()
        return out


# ================================================================ client ==

class _RemoteHandle(Future):
    """Future-shaped handle for one remote submit (``request_id`` rides
    along so hedged re-dispatch can reuse it)."""

    def __init__(self, request_id: str):
        super().__init__()
        self.request_id = request_id


def _safe_fail(fut: Future, exc: BaseException) -> None:
    try:
        if not fut.cancelled():
            fut.set_exception(exc)
    except Exception:
        pass  # already resolved (a race with the receiver) — first wins


def _safe_resolve(fut: Future, value) -> None:
    try:
        if not fut.cancelled():
            fut.set_result(value)
    except Exception:
        pass


class _Pending:
    """One outstanding request id; ``futs`` is a LIST because a
    duplicate submit with the same id (hedge retry on this client)
    attaches to the outstanding request instead of re-sending."""

    __slots__ = ("futs", "t_submit", "rel_deadline", "abs_deadline")

    def __init__(self, fut, t_submit, rel_deadline):
        self.futs = [fut]
        self.t_submit = t_submit
        self.rel_deadline = rel_deadline
        self.abs_deadline = None if rel_deadline is None \
            else t_submit + rel_deadline

    def fail_all(self, exc: BaseException) -> None:
        for f in self.futs:
            _safe_fail(f, exc)

    def resolve_all(self, value) -> None:
        for f in self.futs:
            _safe_resolve(f, value)


class RemoteReplica:
    """Client proxy for one :class:`ReplicaServer` — a drop-in
    ReplicaSet backend whose engine lives across a socket (and usually
    a process). See the module docstring for the robustness contract.

    ``connect_policy`` paces reconnects (default 3 attempts, 50 ms
    doubling, deterministic jitter); ``breaker_threshold`` consecutive
    transport failures open the breaker for ``breaker_cooldown``
    seconds; ``deadline_grace`` is the slack the local backstop gives
    the server to answer a deadline itself before the client fails the
    future locally."""

    accepts_request_id = True  # ReplicaSet hedging reuses request ids

    def __init__(self, address: Tuple[str, int], *, name: str = "remote",
                 proc: Optional[subprocess.Popen] = None,
                 launch: Optional[dict] = None,
                 connect_policy: Optional[RetryPolicy] = None,
                 connect_timeout: float = 5.0,
                 breaker_threshold: int = 3,
                 breaker_cooldown: float = 1.0,
                 deadline_grace: float = 0.25):
        self.host, self.port = address[0], int(address[1])
        self.name = name
        self._proc = proc
        self._launch = launch
        self._policy = connect_policy or RetryPolicy(
            max_attempts=3, base_delay=0.05, max_delay=2.0,
            transient=(OSError, ConnectionError, TransportError))
        self._connect_timeout = float(connect_timeout)
        self.breaker_threshold = int(breaker_threshold)
        self.breaker_cooldown = float(breaker_cooldown)
        self.deadline_grace = float(deadline_grace)
        self._lock = threading.Lock()
        self._connect_lock = threading.Lock()
        self._send_lock = threading.Lock()
        self._sock: Optional[socket.socket] = None
        self._pending: Dict[str, _Pending] = {}
        self._closed = False
        self._closing = False  # deliberate close: disconnects are not
        #                        failures, keep the gauges honest
        self._send_count = 0
        # transport gauges (scraped via snapshot() -> MetricsRegistry)
        self._connects = 0
        self.rpc_reconnects = 0
        self.rpc_deadline_exceeded = 0
        self.rpc_hedges_won = 0
        self.breaker_trips = 0
        self._consec_failures = 0
        self._breaker_open_until = 0.0
        # deadline backstop: one heap, one thread, started on first use
        self._dl_cond = threading.Condition()
        self._dl_heap: List[Tuple[float, str]] = []
        self._dl_thread: Optional[threading.Thread] = None

    @property
    def endpoint(self) -> str:
        return f"{self.host}:{self.port}"

    # -------------------------------------------------------- breaker ----

    def _breaker_failure(self) -> None:
        with self._lock:
            self._consec_failures += 1
            if self._consec_failures >= self.breaker_threshold \
                    and time.monotonic() >= self._breaker_open_until:
                self._breaker_open_until = (time.monotonic()
                                            + self.breaker_cooldown)
                self.breaker_trips += 1
                record_event("rpc.breaker_open", endpoint=self.endpoint,
                             failures=self._consec_failures,
                             cooldown_s=self.breaker_cooldown)

    def _breaker_success(self) -> None:
        with self._lock:
            self._consec_failures = 0
            self._breaker_open_until = 0.0

    @property
    def breaker_state(self) -> str:
        with self._lock:
            return ("open" if time.monotonic() < self._breaker_open_until
                    else "closed")

    # ----------------------------------------------------- connection ----

    def _connect_once(self) -> socket.socket:
        faults.fire("rpc.connect", endpoint=self.endpoint)
        s = socket.create_connection((self.host, self.port),
                                     timeout=self._connect_timeout)
        try:
            s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            s.settimeout(None)
            rpc.client_handshake(s)
        except BaseException:
            s.close()
            raise
        return s

    def _ensure_conn(self, half_open: bool = False) -> socket.socket:
        with self._lock:
            if self._closed:
                raise TransportError("replica client is closed",
                                     endpoint=self.endpoint)
            if self._sock is not None:
                return self._sock
            if not half_open \
                    and time.monotonic() < self._breaker_open_until:
                raise TransportError(
                    f"circuit breaker open after "
                    f"{self._consec_failures} consecutive failures",
                    endpoint=self.endpoint)
        with self._connect_lock:
            with self._lock:
                if self._sock is not None:
                    return self._sock
            try:
                s = self._policy.call(
                    self._connect_once,
                    describe=f"rpc connect {self.endpoint}")
            except (OSError, ConnectionError, TransportError) as e:
                self._breaker_failure()
                if isinstance(e, TransportError):
                    raise
                raise TransportError(f"connect failed: {e}",
                                     endpoint=self.endpoint) from e
            with self._lock:
                self._sock = s
                self._connects += 1
                if self._connects > 1:
                    self.rpc_reconnects += 1
            self._breaker_success()
            threading.Thread(target=self._recv_loop, args=(s,),
                             name="bigdl-rpc-client-recv",
                             daemon=True).start()
            record_event("rpc.connected", endpoint=self.endpoint,
                         connects=self._connects)
            return s

    def _recv_loop(self, sock: socket.socket) -> None:
        try:
            while True:
                msg = rpc.recv_frame(sock)
                # latency-oriented site; an exc arm is a poisoned pipe
                faults.fire("rpc.recv_delay", endpoint=self.endpoint)
                self._dispatch(msg)
        except BaseException as e:
            self._conn_lost(sock, e)

    def _dispatch(self, msg: dict) -> None:
        rid = msg.get("id")
        with self._lock:
            ent = self._pending.pop(rid, None)
            # any response frame proves the transport: close the breaker
            self._consec_failures = 0
            self._breaker_open_until = 0.0
        if ent is None:
            return  # deadline backstop (or a cancel) got there first
        if msg.get("ok"):
            ent.resolve_all(msg.get("result"))
        else:
            err = msg.get("error")
            if not isinstance(err, BaseException):
                err = TransportError(f"malformed error frame: {err!r}",
                                     endpoint=self.endpoint)
            if isinstance(err, DeadlineExceeded):
                with self._lock:
                    self.rpc_deadline_exceeded += 1
            ent.fail_all(err)

    def _conn_lost(self, sock: socket.socket,
                   error: BaseException, count: bool = True) -> None:
        with self._lock:
            if self._sock is not sock:
                return  # a newer connection already took over
            self._sock = None
            pend = list(self._pending.values())
            self._pending.clear()
            closing = self._closed or self._closing
        try:
            sock.shutdown(socket.SHUT_RDWR)  # wake a blocked receiver
        except OSError:
            pass
        try:
            sock.close()
        except OSError:
            pass
        if count and not closing:
            self._breaker_failure()
        if not closing:
            record_event("rpc.disconnected", endpoint=self.endpoint,
                         error=type(error).__name__, pending=len(pend))
        terr = TransportError(f"connection lost: {error}",
                              endpoint=self.endpoint)
        for ent in pend:
            ent.fail_all(terr)

    # ----------------------------------------------- deadline backstop ----

    def _watch_deadline(self, rid: str, ent: _Pending) -> None:
        with self._dl_cond:
            heapq.heappush(self._dl_heap,
                           (ent.abs_deadline + self.deadline_grace, rid))
            if self._dl_thread is None or not self._dl_thread.is_alive():
                self._dl_thread = threading.Thread(
                    target=self._deadline_loop,
                    name="bigdl-rpc-deadline", daemon=True)
                self._dl_thread.start()
            self._dl_cond.notify_all()

    def _deadline_loop(self) -> None:
        while True:
            with self._dl_cond:
                while True:
                    if self._closed and not self._dl_heap:
                        return
                    now = time.monotonic()
                    if self._dl_heap and self._dl_heap[0][0] <= now:
                        _, rid = heapq.heappop(self._dl_heap)
                        break
                    if self._closed:
                        self._dl_heap.clear()
                        return
                    self._dl_cond.wait(
                        timeout=None if not self._dl_heap
                        else max(self._dl_heap[0][0] - now, 0.005))
            with self._lock:
                ent = self._pending.pop(rid, None)
                if ent is not None:
                    self.rpc_deadline_exceeded += 1
            if ent is None:
                continue  # the server answered in time
            waited = time.monotonic() - ent.t_submit
            record_event("rpc.deadline_backstop", endpoint=self.endpoint,
                         waited_ms=round(waited * 1e3, 1))
            ent.fail_all(DeadlineExceeded(waited, ent.rel_deadline))

    # -------------------------------------------------------- requests ----

    def _send(self, sock: socket.socket, msg: dict, method: str) -> None:
        with self._lock:
            self._send_count += 1
            idx = self._send_count
        try:
            faults.fire("rpc.send", key=idx, endpoint=self.endpoint,
                        method=method)
            with self._send_lock:
                rpc.send_frame(sock, msg)
        except BaseException as e:
            self._breaker_failure()
            self._conn_lost(sock, e, count=False)
            raise TransportError(f"send failed: {e}",
                                 endpoint=self.endpoint) from e

    def submit(self, x, request_id: Optional[str] = None,
               deadline: Optional[float] = None, **kwargs):
        """Place one request on the remote backend; returns a
        future-shaped handle. ``deadline`` is seconds from now and
        propagates in the header; transport failures raise
        :class:`TransportError` (an engine error — the ReplicaSet
        evicts and fails over)."""
        sock = self._ensure_conn()
        rid = request_id or uuid.uuid4().hex
        fut = _RemoteHandle(rid)
        rel = None if deadline is None else float(deadline)
        ent = _Pending(fut, time.monotonic(), rel)
        with self._lock:
            if self._closed:
                raise TransportError("replica client is closed",
                                     endpoint=self.endpoint)
            existing = self._pending.get(rid)
            if existing is not None:
                # duplicate id while the original is outstanding:
                # attach, don't re-send — one wire request, N futures
                existing.futs.append(fut)
                return fut
            self._pending[rid] = ent
        msg = {"id": rid, "method": "submit", "x": x, "kwargs": kwargs,
               "deadline_ms": None if rel is None else rel * 1e3}
        try:
            self._send(sock, msg, "submit")
        except BaseException:
            with self._lock:
                self._pending.pop(rid, None)
            raise
        if ent.abs_deadline is not None:
            self._watch_deadline(rid, ent)
        return fut

    def _call(self, method: str, extra: Optional[dict] = None,
              timeout: float = 60.0, half_open: bool = False):
        sock = self._ensure_conn(half_open=half_open)
        rid = uuid.uuid4().hex
        fut: Future = Future()
        ent = _Pending(fut, time.monotonic(), None)
        with self._lock:
            self._pending[rid] = ent
        msg = {"id": rid, "method": method}
        if extra:
            msg.update(extra)
        try:
            self._send(sock, msg, method)
        except BaseException:
            with self._lock:
                self._pending.pop(rid, None)
            raise
        try:
            return fut.result(timeout)
        except (_FutureTimeout, TimeoutError):
            with self._lock:
                self._pending.pop(rid, None)
            raise TransportError(f"{method} timed out after {timeout}s",
                                 endpoint=self.endpoint)

    def predict(self, x, timeout: Optional[float] = None, **kwargs):
        return self.submit(x, **kwargs).result(timeout)

    def ping(self, timeout: float = 5.0) -> str:
        """Liveness probe; goes through the breaker HALF-OPEN (a probe
        is allowed to test a tripped endpoint; success closes it)."""
        return self._call("ping", timeout=timeout, half_open=True)

    def reload(self, params, state=None, *, timeout: float = 120.0):
        extra = {"params": params}
        if state is not None:
            extra["state"] = state
        return self._call("reload", extra, timeout=timeout)

    def warmup(self, *args, timeout: float = 300.0, **kwargs):
        return self._call("warmup", {"args": list(args), "kwargs": kwargs},
                          timeout=timeout)

    def remote_snapshot(self, timeout: float = 10.0) -> dict:
        """The SERVER's view (in-flight count, backend metrics) — a
        network call, unlike :meth:`snapshot`."""
        return self._call("snapshot", timeout=timeout)

    # fault-plane plumbing: chaos harnesses arm the CHILD's injector and
    # reconcile its counts, keeping cross-process schedules replayable
    def arm_fault(self, site: str, **spec):
        return self._call("arm_fault", {"site": site, "spec": spec})

    def disarm_fault(self, site: str):
        return self._call("disarm_fault", {"site": site})

    def reset_faults(self):
        return self._call("reset_faults")

    def fault_snapshot(self) -> dict:
        return self._call("fault_snapshot")

    def recorder_count(self, kind: str) -> int:
        return self._call("recorder_count", {"kind": kind})

    def record_hedge_win(self) -> None:
        with self._lock:
            self.rpc_hedges_won += 1

    # ------------------------------------------------------ lifecycle ----

    def kill(self) -> None:
        """SIGKILL the owned child process (chaos harness hook)."""
        if self._proc is not None and self._proc.poll() is None:
            os.kill(self._proc.pid, signal.SIGKILL)
            self._proc.wait(timeout=10)

    def revive(self, timeout: float = 10.0) -> str:
        """Probe hook for a process-owning replica: relaunch the child
        if it died, then ping. Wire this as the ReplicaSet ``probe`` and
        the prober drives the whole SIGKILL-to-rejoin cycle."""
        if self._proc is not None and self._proc.poll() is not None \
                and self._launch is not None:
            proc, (host, port) = _spawn_replica(**self._launch)
            with self._lock:
                self._proc = proc
                self.host, self.port = host, int(port)
                self._consec_failures = 0
                self._breaker_open_until = 0.0
            record_event("rpc.respawned", endpoint=self.endpoint)
        return self.ping(timeout=timeout)

    @property
    def process_alive(self) -> Optional[bool]:
        return None if self._proc is None else self._proc.poll() is None

    def close(self, drain: bool = True,
              timeout: Optional[float] = None) -> None:
        budget = 10.0 if timeout is None else float(timeout)
        deadline = time.monotonic() + budget
        with self._lock:
            if self._closed:
                return
            self._closing = True
        if drain:
            while time.monotonic() < deadline:
                with self._lock:
                    if not self._pending:
                        break
                time.sleep(0.01)
        with self._lock:
            has_conn = self._sock is not None
        if has_conn:
            try:
                self._call("close", {"drain": drain,
                                     "timeout": max(
                                         deadline - time.monotonic(), 0.1)},
                           timeout=max(deadline - time.monotonic(), 0.5))
            except Exception:
                pass  # a dead server is already closed
        with self._lock:
            self._closed = True
            sock, self._sock = self._sock, None
            pend = list(self._pending.values())
            self._pending.clear()
        terr = TransportError("replica client closed",
                              endpoint=self.endpoint)
        for ent in pend:
            ent.fail_all(terr)
        if sock is not None:
            try:
                sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                sock.close()
            except OSError:
                pass
        with self._dl_cond:
            self._dl_cond.notify_all()
        if self._proc is not None:
            try:
                self._proc.wait(timeout=max(deadline - time.monotonic(),
                                            0.5))
            except subprocess.TimeoutExpired:
                self._proc.terminate()
                try:
                    self._proc.wait(timeout=5)
                except subprocess.TimeoutExpired:
                    self._proc.kill()
                    self._proc.wait(timeout=5)

    def __enter__(self) -> "RemoteReplica":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -------------------------------------------------------- queries ----

    def snapshot(self) -> dict:
        """LOCAL transport gauges (no network — registry-scrape safe)."""
        with self._lock:
            state = ("open"
                     if time.monotonic() < self._breaker_open_until
                     else "closed")
            return {
                "endpoint": self.endpoint,
                "connected": self._sock is not None,
                "process_alive": self.process_alive,
                "inflight": len(self._pending),
                "rpc_connects": self._connects,
                "rpc_reconnects": self.rpc_reconnects,
                "rpc_deadline_exceeded": self.rpc_deadline_exceeded,
                "rpc_hedges_won": self.rpc_hedges_won,
                "breaker": {"state": state,
                            "consecutive_failures": self._consec_failures,
                            "trips": self.breaker_trips,
                            "threshold": self.breaker_threshold},
                "connect_policy": self._policy.snapshot(),
            }

    transport_snapshot = snapshot  # ReplicaSet.snapshot() looks for this


# ============================================================= launcher ==

def _spawn_replica(factory: str, host: str = "127.0.0.1",
                   env: Optional[dict] = None,
                   startup_timeout: float = 60.0
                   ) -> Tuple[subprocess.Popen, Tuple[str, int]]:
    # -c instead of -m: the package __init__ imports this module, so
    # `-m` would re-execute it under runpy and warn about the stale
    # sys.modules entry on every child start
    cmd = [sys.executable, "-c",
           "import sys; from bigdl_tpu.serving import remote; "
           "sys.exit(remote.main(sys.argv[1:]))",
           "--factory", factory, "--host", host, "--port", "0"]
    full_env = dict(os.environ)
    if env:
        full_env.update({str(k): str(v) for k, v in env.items()})
    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE, text=True,
                            bufsize=1, env=full_env)
    ready = threading.Event()
    addr: List[Any] = [None]

    def _pump():
        # keep draining stdout for the child's whole life so it can
        # never block on a full pipe; only the READY line matters
        for line in proc.stdout:
            if line.startswith("RPC_READY "):
                _, h, p = line.split()
                addr[0] = (h, int(p))
                ready.set()
        ready.set()  # EOF: child died before (or after) ready

    threading.Thread(target=_pump, name="bigdl-rpc-stdout",
                     daemon=True).start()
    if not ready.wait(startup_timeout) or addr[0] is None:
        rc = proc.poll()
        if rc is None:
            proc.kill()
            proc.wait(timeout=10)
        raise TransportError(
            f"replica process {factory!r} did not report RPC_READY "
            f"(rc={rc})")
    return proc, addr[0]


def start_replica_process(factory: str, *, host: str = "127.0.0.1",
                          env: Optional[dict] = None,
                          startup_timeout: float = 60.0,
                          name: Optional[str] = None,
                          **replica_kw) -> RemoteReplica:
    """Spawn ``python -m bigdl_tpu.serving.remote --factory mod:fn`` and
    return the connected-on-demand :class:`RemoteReplica` that OWNS the
    child (``close`` reaps it, ``revive`` relaunches it). ``factory``
    is a ``module:function`` path resolving to a zero-arg callable that
    builds the backend INSIDE the child — nothing is pickled.

    A TPU chip belongs to one process: a parent that has touched JAX
    holds it, and a child whose factory needs the same chip fails (or
    waits on the device lock) until ``startup_timeout`` kills it and
    this raises :class:`TransportError`. Give each child its own chip
    through ``env=``, or a CPU backend (``env={"JAX_PLATFORMS": "cpu"}``)."""
    launch = {"factory": factory, "host": host, "env": env,
              "startup_timeout": startup_timeout}
    proc, addr = _spawn_replica(**launch)
    return RemoteReplica(addr, proc=proc, launch=launch,
                         name=name or factory, **replica_kw)


# ========================================================= toy backend ==

class ToyBackend:
    """Dependency-free deterministic backend for transport tests and
    demos: ``submit(x)`` answers ``2 * x`` after ``delay`` seconds on a
    worker thread, honouring the ``deadline`` contract (late work fails
    the future with :class:`DeadlineExceeded` instead of returning)."""

    def __init__(self, delay: float = 0.0):
        self.delay = float(delay)
        self.calls = 0
        self.reloads = 0
        self.warmups = 0

    def submit(self, x, deadline: Optional[float] = None, **kw):
        self.calls += 1
        fut: Future = Future()
        t0 = time.monotonic()
        delay = float(kw.pop("delay", self.delay))

        def run():
            if delay:
                time.sleep(delay)
            waited = time.monotonic() - t0
            if deadline is not None and waited > deadline:
                _safe_fail(fut, DeadlineExceeded(waited, deadline))
                return
            _safe_resolve(fut, np.asarray(x) * 2)

        threading.Thread(target=run, name="bigdl-rpc-toy",
                         daemon=True).start()
        return fut

    def reload(self, params, state=None):
        self.reloads += 1

    def warmup(self, *a, **kw):
        self.warmups += 1

    def close(self, drain: bool = True, timeout=None):
        pass


def toy_backend():
    return ToyBackend()


def slow_toy_backend():
    return ToyBackend(delay=0.2)


# ========================================================== child entry ==

def _resolve_factory(spec: str):
    mod, _, fn = spec.partition(":")
    module = importlib.import_module(mod)
    return getattr(module, fn or "create_backend")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="host a serving backend behind the rpc wire")
    ap.add_argument("--factory", required=True,
                    help="module:function building the backend")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--name", default=None)
    args = ap.parse_args(argv)
    backend = _resolve_factory(args.factory)()
    server = ReplicaServer(backend, host=args.host, port=args.port,
                           name=args.name or args.factory, hard_exit=True)
    print(f"RPC_READY {server.host} {server.port}", flush=True)
    server.wait_closed()
    try:
        backend.close()
    except Exception:
        pass
    return 0


if __name__ == "__main__":
    sys.exit(main())
