"""ServiceServer — the socket front of a :class:`~repro.service.broker.
DataService`.

The broker is the policy layer (admission, fairness/QoS, shared cache);
this module only moves its frames: an accept loop hands each connection to
a reader thread that decodes :data:`~repro.service.wire.KIND_REQUEST`
frames and feeds them straight into the *existing* admission queue via
``DataService.submit``.  Everything the broker already guarantees therefore
holds for remote clients unchanged:

* **backpressure is typed** — a full queue raises ``AdmissionError`` at
  submit time, which the connection answers *immediately* with a
  :data:`~repro.service.wire.KIND_BUSY` frame carrying the queue depth and
  client id (the client re-raises a faithful ``AdmissionError``);
* **errors survive the hop** — request failures become
  :data:`~repro.service.wire.KIND_ERROR` frames carrying the exception
  class and message, so a corrupt chunk still *names* the offending chunk
  on the far side of the socket;
* **pipelining without head-of-line blocking** — responses are sent as
  their futures complete (possibly out of request order; the echoed
  ``req_id`` re-associates them): inline on the completing worker when the
  wire is free, else by a dedicated per-connection sender thread.  A
  worker can therefore spend time *transferring* to a live socket, but can
  never be wedged by a dead or stalled one: every connection socket
  carries a send timeout (``ServiceServer(send_timeout_s=...)``), and a
  peer that stops reading for that long is disconnected (slow-consumer
  eviction — the standard broker policy) and its worker freed.

Each connection opens with a :data:`~repro.service.wire.KIND_HELLO` frame
declaring the QoS class for the clients it carries
(``DataService.set_client_class`` on first sight).  The server binds a
Unix-domain socket (address = path) or TCP (address = ``(host, port)``;
port 0 picks an ephemeral port, see :attr:`ServiceServer.address`).
"""

from __future__ import annotations

import logging
import os
import queue
import socket
import struct
import threading
import time

from repro.obs.trace import SPAN_WIRE_SEND, TRACER

from . import wire
from .broker import AdmissionError, DataService
from .requests import SubscribeRequest

_SENTINEL = None  # sender-queue shutdown marker

log = logging.getLogger("repro.service.transport")


class _Conn:
    """One accepted connection: reader thread (frames → broker) + sender
    thread (completed futures → frames)."""

    def __init__(self, server: "ServiceServer", sock: socket.socket, name: str):
        self.server = server
        self.sock = sock
        self.out: "queue.SimpleQueue[tuple | None]" = queue.SimpleQueue()
        self._wlock = threading.Lock()  # one frame on the wire at a time
        self._dead = False
        self.qos = server.service.config.default_class
        self._known_clients: set[str] = set()
        # admitted-but-unanswered requests on this connection (drain gauge):
        # incremented after a successful submit, decremented once the
        # response frame is handed to the wire
        self.inflight = 0
        self._inflight_lock = threading.Lock()
        # live subscriptions keyed by the SUBSCRIBE frame's req_id (the
        # sub_id PUSH frames echo); mutated only on the reader thread,
        # including the conn-death cleanup in _read_loop's finally
        self._subs: dict[int, object] = {}
        self.reader = threading.Thread(
            target=self._read_loop, name=f"{name}-rx", daemon=True
        )
        self.sender = threading.Thread(
            target=self._send_loop, name=f"{name}-tx", daemon=True
        )

    def start(self) -> None:
        """Begin serving.  Separate from construction so the server can
        register the connection FIRST — otherwise an immediately-dying
        peer's cleanup (``_forget``) could run before the registration and
        leak the dead connection into the registry forever."""
        self.reader.start()
        self.sender.start()

    # -- reader half ---------------------------------------------------------

    def _read_loop(self) -> None:
        svc = self.server.service
        hello_done = False
        try:
            frame = wire.recv_frame(self.sock)
            if frame is None:
                return
            if frame.kind != wire.KIND_HELLO:
                raise wire.WireError("expected HELLO as the first frame")
            if frame.meta.get("version") != wire.WIRE_VERSION:
                raise wire.WireError(
                    f"client wire version {frame.meta.get('version')} !="
                    f" {wire.WIRE_VERSION}"
                )
            qos = frame.meta.get("qos")
            if qos is not None:
                try:
                    svc.config.qos_class(qos)  # validate before accepting
                except KeyError:
                    raise wire.WireError(f"unknown QoS class {qos!r}") from None
                self.qos = str(qos)
            hello_done = True
            while True:
                frame = wire.recv_frame(self.sock)
                if frame is None:
                    return  # clean goodbye
                if frame.kind == wire.KIND_PING:
                    # liveness probe: answered inline, never queued — PONGs
                    # must keep flowing while the admission queue is full
                    self._put(wire.KIND_PONG, frame.req_id, {}, None)
                    continue
                if frame.kind == wire.KIND_SUBSCRIBE:
                    self._subscribe(frame)
                    continue
                if frame.kind == wire.KIND_UNSUBSCRIBE:
                    self._unsubscribe(frame)
                    continue
                if frame.kind != wire.KIND_REQUEST:
                    raise wire.WireError(f"unexpected frame kind {frame.kind}")
                self._dispatch(frame)
        except (wire.WireDisconnect, ConnectionError, BrokenPipeError):
            if not hello_done:
                self.server._count_hello_failure("peer vanished during HELLO")
            return  # peer vanished: nothing to answer
        except wire.WireError as e:
            # framing no longer trustworthy: best-effort error frame, close
            if not hello_done:
                self.server._count_hello_failure(str(e))
            self._put(wire.KIND_ERROR, 0, wire.encode_error(e), None)
        except OSError:
            return  # socket torn down under us (server close)
        finally:
            # a dead connection must leak NO broker state: every live
            # subscription it carried is torn down with it (a reconnecting
            # client re-subscribes from its cursor on the new connection)
            subs, self._subs = list(self._subs.values()), {}
            for sub in subs:
                try:
                    svc.unsubscribe(sub)
                except Exception:  # pragma: no cover - teardown best-effort
                    pass
            self.out.put(_SENTINEL)
            self.server._forget(self)

    def _dispatch(self, frame: wire.Frame) -> None:
        svc = self.server.service
        req_id = frame.req_id
        try:
            client, request = wire.decode_request(frame.meta, frame.payload)
        except (KeyError, ValueError, TypeError) as e:
            self._put(wire.KIND_ERROR, req_id, wire.encode_error(e), None)
            return
        if client not in self._known_clients:
            self._known_clients.add(client)
            svc.set_client_class(client, self.qos)
        deadline = frame.meta.get("deadline_s")
        # adopt the client's trace context (if sampled there) so the
        # broker's phase spans join the client's trace_id
        tctx = wire.get_trace(frame.meta) if TRACER.enabled else None
        try:
            fut = svc.submit(
                client, request, deadline_s=float(deadline) if deadline else None, trace=tctx
            )
        except AdmissionError as e:
            self._put(
                wire.KIND_BUSY,
                req_id,
                {
                    "message": str(e),
                    "queue_depth": e.queue_depth,
                    "client": e.client,
                    "max_queue": svc.config.max_queue,
                },
                None,
            )
            return
        except Exception as e:  # e.g. service closed
            self._put(wire.KIND_ERROR, req_id, wire.encode_error(e), None)
            return
        with self._inflight_lock:
            self.inflight += 1
        fut.add_done_callback(
            lambda f, rid=req_id, cid=client, tc=tctx: self._complete(rid, cid, f, tc)
        )

    def _complete(self, req_id: int, client: str, fut, tctx=None) -> None:
        """Future→frame, on whichever thread completed the future (a
        service worker).  Fast path: if the wire is uncontended, send
        right here and skip the sender-thread handoff (worth ~a thread
        wakeup per response on a GIL-bound box); a contended wire — or a
        peer slow enough to back it up — falls back to the queue so
        workers never line up behind one connection's socket."""
        try:
            exc = fut.exception()
            if exc is not None:
                if isinstance(exc, AdmissionError):
                    # a routing service (the sharded front node) learns of a
                    # data node's rejection through the future — it is still
                    # typed backpressure, so it still travels as BUSY
                    self._put(
                        wire.KIND_BUSY,
                        req_id,
                        {
                            "message": str(exc),
                            "queue_depth": exc.queue_depth,
                            "client": exc.client,
                            "max_queue": self.server.service.config.max_queue,
                        },
                        None,
                    )
                    return
                self._put(wire.KIND_ERROR, req_id, wire.encode_error(exc), None)
                return
            resp = fut.result()
            try:
                desc, payload = wire.encode_value(resp.value)
            except TypeError as e:  # pragma: no cover - un-wireable value type
                self._put(wire.KIND_ERROR, req_id, wire.encode_error(e), None)
                return
            if tctx is not None and TRACER.enabled:
                t0 = time.perf_counter()
                self._put(wire.KIND_OK, req_id, wire.response_meta(client, resp, desc), payload)
                TRACER.record(
                    SPAN_WIRE_SEND,
                    tctx,
                    t0,
                    time.perf_counter(),
                    {"req_id": req_id, "nbytes": resp.nbytes},
                )
            else:
                self._put(wire.KIND_OK, req_id, wire.response_meta(client, resp, desc), payload)
        finally:
            with self._inflight_lock:
                self.inflight -= 1

    # -- subscriptions -------------------------------------------------------

    def _subscribe(self, frame: wire.Frame) -> None:
        """Register a push subscription: the frame's ``req_id`` becomes the
        sub_id every PUSH frame echoes.  A SUBSCRIBE reusing a live sub_id
        replaces it (the reconnect path re-subscribes under the same id on
        a fresh connection; same-connection reuse behaves identically)."""
        svc = self.server.service
        sub_id = frame.req_id
        try:
            client, request = wire.decode_request(frame.meta, frame.payload)
            if not isinstance(request, SubscribeRequest):
                raise TypeError(
                    f"SUBSCRIBE frame carried {type(request).__name__},"
                    " want SubscribeRequest"
                )
        except (KeyError, ValueError, TypeError) as e:
            self._put(wire.KIND_ERROR, sub_id, wire.encode_error(e), None)
            return
        if client not in self._known_clients:
            self._known_clients.add(client)
            svc.set_client_class(client, self.qos)

        def sink(push_meta: dict, rows, _sid=sub_id) -> bool:
            desc, payload = wire.encode_value(rows)
            return self.send_push(_sid, {**push_meta, "value": desc}, payload)

        def on_error(exc: Exception | None, _sid=sub_id) -> None:
            # terminal event for the stream: a pump failure becomes the
            # typed error; a clean end (broker unsubscribe / shutdown)
            # becomes an explicit end-of-stream frame so the remote
            # iterator stops instead of waiting forever
            if exc is None:
                self._put(
                    wire.KIND_OK, _sid, {"value": {"kind": "none"}, "eos": True}, None
                )
            else:
                self._put(wire.KIND_ERROR, _sid, wire.encode_error(exc), None)

        old = self._subs.pop(sub_id, None)
        if old is not None:
            svc.unsubscribe(old)
        try:
            sub = svc.subscribe(client, request, sink=sink, on_error=on_error)
        except Exception as e:
            self._put(wire.KIND_ERROR, sub_id, wire.encode_error(e), None)
            return
        self._subs[sub_id] = sub
        # the pump may already be framing pushes; the client treats any OK
        # on a sub_id as the ack and PUSH frames are self-describing, so
        # ack/push ordering does not matter
        self._put(
            wire.KIND_OK,
            sub_id,
            {"client": client, "value": {"kind": "none"}, "subscribed": True},
            None,
        )

    def _unsubscribe(self, frame: wire.Frame) -> None:
        svc = self.server.service
        sub_id = frame.meta.get("sub_id")
        sub = self._subs.pop(sub_id, None) if sub_id is not None else None
        if sub is not None:
            svc.unsubscribe(sub)
        self._put(
            wire.KIND_OK,
            frame.req_id,
            {"client": "", "value": {"kind": "none"}, "unsubscribed": sub is not None},
            None,
        )

    def send_push(self, sub_id: int, meta: dict, payload) -> bool:
        """Frame one PUSH onto the wire, BLOCKING on the write lock (unlike
        ``_put``'s queue fallback): backpressure from a slow socket must
        reach the pump thread, not pile frames into the unbounded sender
        queue.  SO_SNDTIMEO still bounds the stall (slow-consumer
        eviction).  False = connection dead, the subscription should end."""
        with self._wlock:
            if self._dead:
                return False
            try:
                wire.send_frame(self.sock, wire.KIND_PUSH, sub_id, meta, payload)
                return True
            except (ConnectionError, BrokenPipeError, OSError):
                self._kill_locked()
                return False

    def _put(self, kind: int, req_id: int, meta: dict, payload) -> None:
        if self._wlock.acquire(blocking=False):
            try:
                if not self._dead:
                    wire.send_frame(self.sock, kind, req_id, meta, payload)
            except (ConnectionError, BrokenPipeError, OSError):
                # peer gone, or SO_SNDTIMEO fired (peer stopped reading): a
                # frame may be half-written, so the stream is dead either
                # way — tear it down and wake the reader
                self._kill_locked()
            finally:
                self._wlock.release()
        else:
            self.out.put((kind, req_id, meta, payload))

    def _kill_locked(self) -> None:
        """Mark the stream unusable (caller holds ``_wlock``) and shut the
        socket down so the reader unblocks and runs the cleanup path.  The
        fd itself is closed only by the sender thread's exit (under
        ``_wlock``), never concurrently with a send — a close racing a
        late fast-path send could otherwise write a stale frame into an
        unrelated connection that reused the fd number."""
        self._dead = True
        try:
            self.sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass

    # -- sender half ---------------------------------------------------------

    def _send_loop(self) -> None:
        try:
            while True:
                item = self.out.get()
                if item is _SENTINEL:
                    return
                kind, req_id, meta, payload = item
                with self._wlock:
                    if self._dead:
                        continue
                    try:
                        wire.send_frame(self.sock, kind, req_id, meta, payload)
                    except (ConnectionError, BrokenPipeError, OSError):
                        self._kill_locked()  # keep draining the queue
        finally:
            with self._wlock:
                self._dead = True
                try:
                    self.sock.close()
                except OSError:  # pragma: no cover
                    pass

    def shutdown(self) -> None:
        try:
            self.sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass

    def join(self, timeout: float | None = None) -> None:
        self.reader.join(timeout)
        self.sender.join(timeout)


class ServiceServer:
    """Accept loop serving one :class:`DataService` over sockets.

    ``address`` is a filesystem path (Unix-domain socket) or a
    ``(host, port)`` tuple (TCP; port 0 = ephemeral).  The resolved address
    — with the real port — is :attr:`address`; hand it to
    :class:`~repro.service.client.RemoteDataService`.  Closing the server
    closes its connections but NOT the service (the owner does that)."""

    def __init__(
        self,
        service: DataService,
        address: str | tuple[str, int],
        *,
        backlog: int = 64,
        sock_buf_bytes: int = 1 << 20,
        send_timeout_s: float = 20.0,
        drain_timeout_s: float = 5.0,
    ):
        self.service = service
        self._sock_buf = int(sock_buf_bytes)
        self._send_timeout = float(send_timeout_s)
        self._drain_timeout = float(drain_timeout_s)
        self._hello_failures = 0
        self._unix_path: str | None = None
        if isinstance(address, (str, os.PathLike)):
            path = os.fspath(address)
            if os.path.exists(path):
                os.unlink(path)  # stale socket from a previous run
            lsock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
            lsock.bind(path)
            self._unix_path = path
            self.address: str | tuple[str, int] = path
        else:
            host, port = address
            lsock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            lsock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            lsock.bind((host, int(port)))
            self.address = lsock.getsockname()[:2]
        lsock.listen(backlog)
        self._lsock = lsock
        self._lock = threading.Lock()
        self._conns: set[_Conn] = set()
        self._closed = False
        self._n_accepted = 0
        self._acceptor = threading.Thread(
            target=self._accept_loop, name="th5-wire-accept", daemon=True
        )
        self._acceptor.start()

    def _accept_loop(self) -> None:
        while True:
            try:
                sock, _peer = self._lsock.accept()
            except OSError:
                return  # listener closed
            try:
                self._setup_conn(sock)
            except OSError as e:
                # one bad accept (peer already gone before setsockopt, fd
                # pressure, ...) must never take down the listener serving
                # every other client
                log.warning("connection setup failed: %s", e)
                try:
                    sock.close()
                except OSError:
                    pass
                continue
            with self._lock:
                if self._closed:
                    sock.close()
                    return
                self._n_accepted += 1
                conn = _Conn(self, sock, f"th5-wire-{self._n_accepted}")
                self._conns.add(conn)  # registered BEFORE its threads run
                # started under the lock too: close() joins every registered
                # connection, and a thread not yet started cannot be joined
                conn.start()

    def _setup_conn(self, sock: socket.socket) -> None:
        if sock.family == socket.AF_INET:
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        if self._sock_buf:
            # one LOD window is commonly larger than the default socket
            # buffer; deeper buffers keep the payload plane moving while
            # the GIL is elsewhere (kernel clamps to its own maximum)
            sock.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, self._sock_buf)
            sock.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, self._sock_buf)
        if self._send_timeout > 0:
            # slow-consumer eviction: a peer that stops reading for this
            # long gets disconnected instead of wedging the thread
            # (worker or sender) that is mid-frame on its socket
            sec = int(self._send_timeout)
            usec = int((self._send_timeout - sec) * 1e6)
            sock.setsockopt(
                socket.SOL_SOCKET, socket.SO_SNDTIMEO,
                struct.pack("@ll", sec, usec),
            )

    def _count_hello_failure(self, reason: str) -> None:
        with self._lock:
            self._hello_failures += 1
        log.info("connection rejected before HELLO completed: %s", reason)

    def _forget(self, conn: _Conn) -> None:
        with self._lock:
            self._conns.discard(conn)

    @property
    def n_connections(self) -> int:
        with self._lock:
            return len(self._conns)

    def stats(self) -> dict:
        """Transport-level gauges: ``accepted`` connections over the
        server's lifetime, currently ``active`` ones, admitted-but-
        unanswered ``inflight`` requests across them, and ``hello_failures``
        (connections dropped before completing HELLO — garbage, version
        mismatch, or a peer dying mid-handshake)."""
        with self._lock:
            conns = list(self._conns)
            return {
                "accepted": self._n_accepted,
                "active": len(conns),
                "inflight": sum(c.inflight for c in conns),
                "hello_failures": self._hello_failures,
            }

    def close(self) -> None:
        """Stop accepting, drain, tear down connections, join threads.

        Drain-on-shutdown: after the listener closes, live connections get
        up to ``drain_timeout_s`` for their admitted requests to finish and
        their response frames to reach the wire before the sockets are
        severed — a shutdown ordered while replies are in flight must not
        turn completed work into torn frames."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
            conns = list(self._conns)
        try:
            # shutdown BEFORE close: closing the fd does not wake a thread
            # blocked in accept(); shutdown does, so the acceptor exits now
            # instead of leaking past its join timeout
            self._lsock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        try:
            self._lsock.close()
        except OSError:  # pragma: no cover
            pass
        deadline = time.monotonic() + self._drain_timeout
        while time.monotonic() < deadline:
            if all(c.inflight == 0 for c in conns):
                break
            time.sleep(0.005)
        for c in conns:
            c.shutdown()
        for c in conns:
            c.join(timeout=10.0)
        self._acceptor.join(timeout=10.0)
        if self._unix_path and os.path.exists(self._unix_path):
            try:
                os.unlink(self._unix_path)
            except OSError:  # pragma: no cover
                pass

    def __enter__(self) -> "ServiceServer":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def serve(path: str, address: str | tuple[str, int], config=None) -> tuple[DataService, ServiceServer]:
    """Convenience: open a broker over ``path`` and serve it at
    ``address``.  Returns ``(service, server)`` — close the server first,
    then the service."""
    svc = DataService(path, config)
    try:
        return svc, ServiceServer(svc, address)
    except BaseException:
        svc.close()
        raise
