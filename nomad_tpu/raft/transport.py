"""Raft + server-RPC transport (reference nomad/raft_rpc.go and
nomad/rpc.go:31,445 — msgpack-RPC over yamux TCP).

The node logic only needs `send(peer, message) -> reply`. Two
implementations:

- InProcTransport: direct dispatch, used by tests and single-process
  multi-server topologies, with a partitionable failure set.
- SocketTransport: length-prefixed wire-codec frames over TCP, one
  listener per server, persistent client connections per peer. Carries
  two frame kinds on the same connection: "raft" (the consensus
  messages) and "call" (server-to-server endpoint forwarding — the
  reference's forwardLeader). Payloads go through structs.wire so raft
  log commands containing domain structs survive the trip.
"""

from __future__ import annotations

import json
import logging
import socket
import socketserver
import struct
import threading
import time
from typing import Callable, Dict, Optional, Tuple

from ..utils.backoff import Backoff

log = logging.getLogger("nomad_tpu.raft")


class InProcTransport:
    """A registry of node handlers; send() is a function call with a
    configurable failure set for partition tests.

    Failure model, consulted in order per message:
    - per-node partitions (symmetric: the node is cut from everyone);
    - directed per-link cuts (partition_link(a, b) drops a->b only —
      the asymmetric failures real networks produce);
    - an optional chaos FaultPlan (chaos/plan.py) deciding
      drop/delay/duplicate/reorder per message.
    """

    def __init__(self):
        self._handlers: Dict[str, Callable[[dict], dict]] = {}
        self._lock = threading.Lock()
        self._partitioned: set = set()  # node ids cut off from everyone
        self._cut_links: set = set()    # directed (src, dst) pairs
        self._timers: set = set()       # outstanding late-delivery timers
        self.fault_plan = None          # chaos.FaultPlan or None

    def register(self, node_id: str, handler: Callable[[dict], dict]) -> None:
        with self._lock:
            self._handlers[node_id] = handler

    def unregister(self, node_id: str) -> None:
        """Crashed process: its handler vanishes (chaos crash path)."""
        with self._lock:
            self._handlers.pop(node_id, None)

    def partition(self, node_id: str) -> None:
        with self._lock:
            self._partitioned.add(node_id)

    def partition_link(self, src: str, dst: str) -> None:
        """Cut src -> dst only; dst -> src still delivers."""
        with self._lock:
            self._cut_links.add((src, dst))

    def heal_link(self, src: str, dst: str) -> None:
        with self._lock:
            self._cut_links.discard((src, dst))

    def heal(self, node_id: Optional[str] = None) -> None:
        """Heal one node's symmetric partition, or — with no argument —
        heal everything: node partitions and directed link cuts."""
        with self._lock:
            if node_id is None:
                self._partitioned.clear()
                self._cut_links.clear()
            else:
                self._partitioned.discard(node_id)

    def set_fault_plan(self, plan) -> None:
        self.fault_plan = plan

    def _deliver_later(self, to_id: str, msg: dict, delay: float) -> None:
        """Late/duplicate delivery: hand the message to whoever holds
        the node id at delivery time (survives crash-restart) and drop
        the reply — the sender already moved on."""
        def fire():
            with self._lock:
                self._timers.discard(t)
                if to_id in self._partitioned:
                    return
                handler = self._handlers.get(to_id)
            if handler is None:
                return
            try:
                handler(msg)
            except Exception:
                log.debug("late-delivered message to %s raised",
                          to_id, exc_info=True)
        t = threading.Timer(delay, fire)
        t.daemon = True
        with self._lock:
            self._timers.add(t)
        t.start()

    def close(self) -> None:
        """Cancel any outstanding late-delivery timers (shutdown path;
        a timer that already fired removed itself)."""
        with self._lock:
            timers = list(self._timers)
            self._timers.clear()
        for t in timers:
            t.cancel()

    def send(self, from_id: str, to_id: str, msg: dict) -> Optional[dict]:
        with self._lock:
            if from_id in self._partitioned or to_id in self._partitioned:
                return None
            if (from_id, to_id) in self._cut_links:
                return None
            handler = self._handlers.get(to_id)
        if handler is None:
            return None
        plan = self.fault_plan
        if plan is not None:
            verdict = plan.decide(from_id, to_id, msg)
            if verdict.drop:
                return None
            if verdict.reorder_after > 0:
                # late delivery out of order with successors; the sender
                # sees message loss (raft tolerates both)
                self._deliver_later(to_id, msg, verdict.reorder_after)
                return None
            if verdict.delay > 0:
                time.sleep(verdict.delay)
            if verdict.duplicate_after > 0:
                self._deliver_later(to_id, msg, verdict.duplicate_after)
        try:
            return handler(msg)
        except Exception:
            log.debug("in-proc handler on %s raised for message from %s",
                      to_id, from_id, exc_info=True)
            return None


# ---------------------------------------------------------------------------
# TCP sockets
# ---------------------------------------------------------------------------


def _encode_frame(payload: dict) -> bytes:
    """Serialize once, outside any connection lock: batched
    append_entries frames are the largest thing on the wire now, and
    encoding them while holding the per-connection lock would stall the
    next frame behind CPU work instead of just the socket."""
    data = json.dumps(payload).encode()
    return struct.pack(">I", len(data)) + data


def _send_frame(sock: socket.socket, payload: dict) -> None:
    sock.sendall(_encode_frame(payload))


def _recv_exact(sock: socket.socket, n: int) -> Optional[bytes]:
    buf = b""
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            return None
        buf += chunk
    return buf


def _recv_frame(sock: socket.socket) -> Optional[dict]:
    head = _recv_exact(sock, 4)
    if head is None:
        return None
    (length,) = struct.unpack(">I", head)
    if length > 256 * 1024 * 1024:
        raise ValueError(f"frame too large: {length}")
    body = _recv_exact(sock, length)
    if body is None:
        return None
    return json.loads(body)


class SocketTransport:
    """TCP transport for one server process.

    bind_addr/peer_addrs are "host:port" strings; peers maps server id ->
    address. Incoming frames dispatch to the registered raft handler or
    the call handler; outgoing sends hold one persistent connection per
    peer and treat any socket error as message loss (raft tolerates it).
    """

    # nomadload ingress bounds: a flooding peer is answered RetryLater
    # instead of queueing unbounded handler threads. Raft/snap frames
    # (consensus liveness = tier 0) and tier-0 forwarded calls are
    # never bounded.
    DEFAULT_MAX_INFLIGHT_PER_PEER = 64
    # pending-accept backlog (listen(2) queue) — beyond it the kernel
    # refuses new connections instead of parking them invisibly
    ACCEPT_BACKLOG = 128

    def __init__(self, node_id: str, bind_addr: str,
                 peer_addrs: Dict[str, str], timeout: float = 5.0,
                 connect_timeout: float = 0.3, retry_cooldown: float = 0.5,
                 raft_timeout: float = 0.5,
                 max_inflight_per_peer: Optional[int] = None):
        self.node_id = node_id
        self.bind_addr = bind_addr
        self.peer_addrs = dict(peer_addrs)
        self.timeout = timeout
        self.max_inflight_per_peer = (
            self.DEFAULT_MAX_INFLIGHT_PER_PEER
            if max_inflight_per_peer is None else max_inflight_per_peer)
        self._inflight: Dict[str, int] = {}   # peer host -> frames in dispatch
        self._inflight_lock = threading.Lock()
        self.dropped_frames = 0
        # sends that got no reply, by channel: a timeout, a reset, a
        # refused connect, a peer in its reconnect cooldown
        self.failed_sends: Dict[str, int] = {}
        # Raft ticks send to every peer serially: connecting to a dead
        # peer must fail fast and then back off, or one crashed server
        # stalls heartbeats to the live ones and triggers elections. The
        # same goes for a HUNG peer (SIGSTOP, IO stall): raft frames get
        # their own short recv timeout, and any raft-channel failure puts
        # the peer in the cooldown so subsequent ticks skip it instead of
        # blocking the heartbeat fan-out.
        self.connect_timeout = connect_timeout
        self.retry_cooldown = retry_cooldown
        self.raft_timeout = raft_timeout
        self._raft_handler: Optional[Callable[[dict], dict]] = None
        self._call_handler: Optional[Callable[[str, tuple, dict], object]] = None
        self._conns: Dict[Tuple[str, str], socket.socket] = {}
        self._conn_locks: Dict[Tuple[str, str], threading.Lock] = {}
        self._down_until: Dict[Tuple[str, str], float] = {}
        # per-link escalating reconnect backoff (utils/backoff.py): a
        # peer that stays down is probed ever more slowly up to the cap,
        # and a restarted peer resets to the base on first contact
        self._backoffs: Dict[Tuple[str, str], Backoff] = {}
        self._exhaustion_logged: set = set()
        self._lock = threading.Lock()
        self._server: Optional[socketserver.ThreadingTCPServer] = None
        self.fault_plan = None  # chaos.FaultPlan or None

    # -- registration (transport interface) --

    def register(self, node_id: str, handler: Callable[[dict], dict]) -> None:
        assert node_id == self.node_id, "socket transport serves one node"
        self._raft_handler = handler

    def register_call_handler(
            self, handler: Callable[[str, tuple, dict], object]) -> None:
        """handler(method, args, kwargs) -> result; exceptions propagate
        back to the caller as typed error replies."""
        self._call_handler = handler

    def set_fault_plan(self, plan) -> None:
        """Attach a chaos FaultPlan consulted per outgoing raft frame."""
        self.fault_plan = plan

    # -- server side --

    def start(self) -> "SocketTransport":
        host, port = self._split(self.bind_addr)
        transport = self

        class Handler(socketserver.BaseRequestHandler):
            def handle(self):
                peer = self.client_address[0]
                while True:
                    try:
                        frame = _recv_frame(self.request)
                    except Exception:
                        log.debug("rpc connection to %s dropped mid-frame",
                                  transport.node_id, exc_info=True)
                        return
                    if frame is None:
                        return
                    try:
                        reply = transport._dispatch(frame, peer=peer)
                    except Exception as e:  # typed error back to caller
                        reply = {"ok": False, "error": str(e),
                                 "error_type": type(e).__name__,
                                 "leader_id": getattr(e, "leader_id", None)}
                    try:
                        _send_frame(self.request, reply)
                    except Exception:
                        log.debug("rpc reply from %s lost: peer closed "
                                  "the connection", transport.node_id,
                                  exc_info=True)
                        return

        class Server(socketserver.ThreadingTCPServer):
            allow_reuse_address = True
            daemon_threads = True
            # bounded pending-accept backlog (nomadload ingress bounds)
            request_queue_size = SocketTransport.ACCEPT_BACKLOG

        self._server = Server((host, port), Handler)
        t = threading.Thread(target=self._server.serve_forever, daemon=True,
                             name=f"rpc-{self.node_id}")
        t.start()
        return self

    def stop(self) -> None:
        if self._server is not None:
            self._server.shutdown()
            self._server.server_close()
        with self._lock:
            for s in self._conns.values():
                try:
                    s.close()
                except OSError:
                    pass
            self._conns.clear()

    def _dispatch(self, frame: dict, peer: str = "") -> dict:
        from ..structs.wire import wire_decode, wire_encode

        kind = frame.get("t")
        if kind in ("raft", "snap"):
            # consensus traffic is tier 0: never bounded, never shed
            if self._raft_handler is None:
                return {"ok": False, "error": "no raft handler"}
            reply = self._raft_handler(wire_decode(frame["m"]))
            return {"ok": True, "m": wire_encode(reply)}
        if kind == "call":
            if self._call_handler is None:
                return {"ok": False, "error": "no call handler"}
            from ..core import loadctl

            method = frame.get("method", "")
            tier = loadctl.tier_for_method(method)
            if tier > loadctl.TIER_LIVENESS \
                    and not self._frame_slot(peer):
                # per-peer inflight cap tripped: refuse the frame with
                # a typed RetryLater the forwarding server decodes and
                # passes through to its client as 429 — never applies
                # to tier-0 (liveness) calls
                self.dropped_frames += 1
                from ..core.metrics import REGISTRY
                REGISTRY.incr("nomad.transport.dropped_frames")
                err = loadctl.RetryLater(
                    tier, 0.25, reason="transport inflight cap")
                return {"ok": False, "error": str(err),
                        "error_type": "RetryLater", "leader_id": None}
            with self._inflight_lock:
                self._inflight[peer] = self._inflight.get(peer, 0) + 1
            try:
                # the forwarded request's absolute deadline rides the
                # frame; expired work is dropped before dispatch
                with loadctl.bind_deadline(frame.get("dl")), \
                        loadctl.bind_tier(tier):
                    if loadctl.drop_if_expired("transport_dispatch"):
                        raise TimeoutError(
                            "request deadline passed before dispatch")
                    result = self._call_handler(
                        method,
                        tuple(wire_decode(frame.get("args", []))),
                        wire_decode(frame.get("kwargs", {})))
            finally:
                with self._inflight_lock:
                    left = self._inflight.get(peer, 1) - 1
                    if left <= 0:
                        self._inflight.pop(peer, None)
                    else:
                        self._inflight[peer] = left
            return {"ok": True, "result": wire_encode(result)}
        return {"ok": False, "error": f"unknown frame kind {kind!r}"}

    def _frame_slot(self, peer: str) -> bool:
        """True when the peer is under its inflight-frame cap."""
        if self.max_inflight_per_peer <= 0:
            return True
        with self._inflight_lock:
            return self._inflight.get(peer, 0) < self.max_inflight_per_peer

    # -- client side --

    @staticmethod
    def _split(addr: str) -> Tuple[str, int]:
        host, _, port = addr.rpartition(":")
        return host or "127.0.0.1", int(port)

    def _mark_down(self, key: Tuple[str, str]) -> None:
        """Peer unreachable: schedule the next probe on an escalating
        jittered backoff; log once when the backoff saturates (retry
        exhaustion — the peer has been down for many probes)."""
        with self._lock:
            self.failed_sends[key[1]] = self.failed_sends.get(key[1], 0) + 1
            bo = self._backoffs.get(key)
            if bo is None:
                bo = self._backoffs[key] = Backoff(
                    base=self.retry_cooldown, factor=2.0,
                    cap=max(self.retry_cooldown * 8, 2.0), jitter=0.2)
            at_cap = bo.at_cap()
            self._down_until[key] = time.monotonic() + bo.next_delay()
            if at_cap and key not in self._exhaustion_logged:
                self._exhaustion_logged.add(key)
                log.warning(
                    "%s: peer %s (%s channel) unreachable after %d "
                    "attempts; retrying at the capped interval",
                    self.node_id, key[0], key[1], bo.attempt)

    def _mark_up(self, key: Tuple[str, str]) -> None:
        with self._lock:
            self._down_until.pop(key, None)
            bo = self._backoffs.get(key)
            if bo is not None:
                bo.reset()
            if key in self._exhaustion_logged:
                self._exhaustion_logged.discard(key)
                log.info("%s: peer %s (%s channel) reachable again",
                         self.node_id, key[0], key[1])

    def _conn(self, key: Tuple[str, str]) \
            -> Tuple[socket.socket, threading.Lock, bool]:
        """Returns (socket, per-connection lock, was_cached). A cached
        socket may be stale (peer restarted since) — callers sending
        idempotent frames retry once on a fresh connection."""
        with self._lock:
            lock = self._conn_locks.setdefault(key, threading.Lock())
            sock = self._conns.get(key)
            if sock is None and time.monotonic() < self._down_until.get(key, 0):
                raise TransportError(f"{key[0]} in reconnect cooldown")
        if sock is not None:
            return sock, lock, True
        host, port = self._split(self.peer_addrs[key[0]])
        try:
            sock = socket.create_connection((host, port),
                                            timeout=self.connect_timeout)
        except OSError:
            self._mark_down(key)
            raise
        self._mark_up(key)
        sock.settimeout(self.raft_timeout if key[1] == "raft" else self.timeout)
        with self._lock:
            # lost a race? keep the first connection
            existing = self._conns.get(key)
            if existing is not None:
                sock.close()
                return existing, lock, True
            self._conns[key] = sock
        return sock, lock, False

    def peer_alive(self, peer_id: str) -> Optional[bool]:
        """Does the peer's process still hold its raft port? A connect
        is completed by the kernel of the peer's machine, so it succeeds
        while the peer's interpreter stands still and is refused once
        the process is gone. None under a fault plan: chaos decides
        reachability frame by frame, and a bare connect sees through it."""
        if self.fault_plan is not None:
            return None
        addr = self.peer_addrs.get(peer_id)
        if addr is None:
            return False
        try:
            socket.create_connection(self._split(addr),
                                     timeout=self.connect_timeout).close()
        except OSError:
            return False
        return True

    def _drop(self, key: Tuple[str, str]) -> None:
        with self._lock:
            sock = self._conns.pop(key, None)
        if sock is not None:
            try:
                sock.close()
            except OSError:
                pass

    def _roundtrip(self, to_id: str, frame: dict) -> Optional[dict]:
        if to_id not in self.peer_addrs:
            return None
        # separate connections per frame kind so a large forwarded call
        # can't stall raft heartbeats behind it (the reference gets this
        # from yamux stream multiplexing)
        key = (to_id, frame["t"])
        # encode before taking the connection lock, and only once even
        # if the stale-connection retry below resends the frame
        wire_frame = _encode_frame(frame)
        for attempt in (0, 1):
            try:
                sock, lock, cached = self._conn(key)
            except Exception:
                log.debug("%s: cannot reach %s", self.node_id, to_id,
                          exc_info=True)
                return None
            try:
                with lock:  # one in-flight request per connection
                    sock.sendall(wire_frame)
                    reply = _recv_frame(sock)
            except Exception:
                self._drop(key)
                if cached and attempt == 0:
                    # a cached connection that dies is the signature of
                    # a RESTARTED peer: raft frames are idempotent, so
                    # retry once on a fresh connection instead of
                    # failing the send permanently
                    continue
                # hung or dead peer: back off so serial raft fan-outs
                # keep heartbeating the healthy peers
                self._mark_down(key)
                return None
            if reply is None:
                self._drop(key)
                if cached and attempt == 0:
                    continue
                self._mark_down(key)
                return None
            return reply
        return None

    def send(self, from_id: str, to_id: str, msg: dict) -> Optional[dict]:
        """Raft message send (transport interface). Snapshot installs get
        their own channel: even chunked frames (SNAPSHOT_CHUNK_BYTES per
        install_snapshot message) are large enough to want the long
        timeout, and the short raft timeout exists precisely so
        heartbeats never wait on a transfer like that."""
        from ..structs.wire import wire_decode, wire_encode

        channel = "snap" if msg.get("kind") == "install_snapshot" else "raft"
        frame = {"t": channel, "m": wire_encode(msg)}
        plan = self.fault_plan
        if plan is not None:
            verdict = plan.decide(self.node_id, to_id, msg)
            if verdict.drop:
                return None
            if verdict.reorder_after > 0:
                # deliver late from a side thread, reply discarded;
                # raft treats the original send as lost
                t = threading.Timer(verdict.reorder_after,
                                    self._roundtrip, (to_id, frame))
                t.daemon = True
                t.start()
                return None
            if verdict.delay > 0:
                time.sleep(verdict.delay)
            if verdict.duplicate_after > 0:
                t = threading.Timer(verdict.duplicate_after,
                                    self._roundtrip, (to_id, frame))
                t.daemon = True
                t.start()
        reply = self._roundtrip(to_id, frame)
        if reply is None or not reply.get("ok"):
            return None
        return wire_decode(reply["m"])

    def call(self, to_id: str, method: str, args: tuple = (),
             kwargs: Optional[dict] = None):
        """Forwarded server call; raises RemoteCallError on typed errors
        and TransportError on connectivity loss. TransportError carries
        maybe_delivered=True when the frame left this host before the
        connection died — the peer may have executed the call, so the
        caller must not blindly retry non-idempotent methods."""
        from ..structs.wire import wire_decode, wire_encode

        if to_id not in self.peer_addrs:
            raise TransportError(f"unknown peer {to_id}")
        frame = {"t": "call", "method": method,
                 "args": wire_encode(list(args)),
                 "kwargs": wire_encode(kwargs or {})}
        from ..core import loadctl

        dl = loadctl.current_deadline()
        if dl is not None:
            frame["dl"] = dl  # absolute deadline rides the wire

        key = (to_id, "call")
        wire_frame = _encode_frame(frame)
        for attempt in (0, 1):
            try:
                sock, lock, _cached = self._conn(key)
            except TransportError:
                raise
            except Exception as e:  # connect failed: definitely not delivered
                raise TransportError(f"cannot reach {to_id}: {e}") from e
            try:
                with lock:
                    try:
                        sock.sendall(wire_frame)
                    except OSError as e:
                        # another thread dropped this shared socket before
                        # we sent a byte (EBADF/ENOTCONN): provably not
                        # delivered, so one fresh-connection retry is safe
                        self._drop(key)
                        import errno

                        if attempt == 0 and e.errno in (errno.EBADF,
                                                        errno.ENOTCONN):
                            continue
                        err = TransportError(
                            f"send to {to_id} failed mid-call: {e}")
                        err.maybe_delivered = True
                        raise err from e
                    reply = _recv_frame(sock)
            except TransportError:
                raise
            except Exception as e:
                self._drop(key)
                err = TransportError(f"connection to {to_id} lost mid-call: {e}")
                err.maybe_delivered = True
                raise err from e
            break
        if reply is None:
            self._drop(key)
            err = TransportError(f"{to_id} closed the connection before replying")
            err.maybe_delivered = True
            raise err
        if not reply.get("ok"):
            raise RemoteCallError(reply.get("error_type", "Exception"),
                                  reply.get("error", ""),
                                  reply.get("leader_id"))
        return wire_decode(reply["result"])


class TransportError(Exception):
    maybe_delivered = False


class RemoteCallError(Exception):
    def __init__(self, error_type: str, message: str, leader_id=None):
        super().__init__(f"{error_type}: {message}")
        self.error_type = error_type
        self.leader_id = leader_id
