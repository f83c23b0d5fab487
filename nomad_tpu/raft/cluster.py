"""Replicated server composition (reference nomad/server.go multi-server
+ leader.go establishLeadership/revokeLeadership).

Each ReplicatedServer owns a local MVCC store replicated via its raft
node; the embedded core.Server's leader-only subsystems (broker, plan
applier, workers, watchers) run only while this node holds leadership —
exactly the reference's establish/revoke cycle. Requests landing on a
follower are forwarded to the leader (reference nomad/rpc.go forward).
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Callable, Dict, List, Optional

from ..core import loadctl
from ..core.heartbeat import HeartbeatPlaneInactive
from ..core.loadctl import RetryLater
from ..core.server import Server, ServerConfig
from ..state import StateStore
from ..utils.backoff import Backoff, Retryer
from .fsm import FSM, RaftStore
from .node import NotLeaderError, RaftNode
from .transport import InProcTransport, RemoteCallError, TransportError

log = logging.getLogger("nomad_tpu.raft")


def _is_loopback_bind(bind: str) -> bool:
    """True when a host:port bind string stays on the local machine
    (loopback or unspecified-but-local test binds are NOT included:
    0.0.0.0/:: listen on every interface)."""
    host = bind.rsplit(":", 1)[0].strip("[]").lower()
    return (host in ("localhost", "::1")
            or host.startswith("127."))

FORWARD = ("register_job", "deregister_job", "dispatch_job",
           "scale_job", "revert_job",
           "register_node", "register_nodes", "heartbeat", "heartbeat_batch",
           "update_node_status", "update_node_drain",
           "update_node_eligibility", "deregister_node",
           "update_allocs_from_client", "stop_alloc",
           "create_eval", "create_job_eval",
           "set_scheduler_config",
           "promote_deployment", "fail_deployment",
           "put_variable", "delete_variable",
           "register_volume", "deregister_volume",
           "upsert_node_pool", "delete_node_pool",
           "upsert_namespace", "delete_namespace", "force_gc",
           "upsert_service_registrations", "delete_service_registrations",
           "delete_services_by_alloc",
           "upsert_acl_policy", "create_acl_token", "acl_bootstrap",
           "upsert_acl_role", "delete_acl_role",
           "upsert_auth_method", "delete_auth_method",
           "upsert_binding_rule", "delete_binding_rule", "acl_login",
           "oidc_auth_url", "oidc_complete_auth",
           "create_one_time_token", "exchange_one_time_token",
           "sign_workload_identity",
           "upsert_region", "delete_region")


class ReplicatedServer:
    def __init__(self, node_id: str, peers: List[str], transport,
                 config: Optional[ServerConfig] = None,
                 peer_lookup: Optional[Callable[[str], "ReplicatedServer"]] = None,
                 data_dir: Optional[str] = None,
                 snapshot_threshold: int = 1024,
                 bootstrap: bool = True,
                 dead_server_cleanup_s: Optional[float] = None,
                 gossip_bind: Optional[str] = None,
                 gossip_seeds: Optional[List[str]] = None):
        self.id = node_id
        self.crashed = False  # set by crash(); chaos invariants skip dead nodes
        self.local_store = StateStore()
        self.fsm = FSM(self.local_store)
        self.data_dir = data_dir
        raft_log = stable = snapshots = None
        fsm_snapshot = fsm_restore = None
        fsm_capture = fsm_serialize = None
        if data_dir is not None:
            # durable mode: boltdb-equivalent log + stable + snapshot
            # files under <data_dir>/raft (reference server.go:1365)
            import os

            from ..state.persist import (capture_store, dump_store,
                                         restore_store, serialize_capture)
            from .durable import DurableLog, SnapshotStore, StableStore

            raft_dir = os.path.join(data_dir, "raft")
            os.makedirs(raft_dir, exist_ok=True)
            stable = StableStore(raft_dir)
            snapshots = SnapshotStore(raft_dir)
            raft_log = DurableLog(raft_dir)
            fsm_snapshot = lambda: dump_store(self.local_store)  # noqa: E731
            fsm_restore = lambda data: restore_store(self.local_store, data)  # noqa: E731
            # stall-free path: capture pins an MVCC generation under the
            # node lock (O(1)); serialization runs on the snapshot worker
            fsm_capture = lambda: capture_store(self.local_store)  # noqa: E731
            fsm_serialize = lambda cap: serialize_capture(self.local_store, cap)  # noqa: E731
        self.raft = RaftNode(node_id, peers, transport, self.fsm.apply,
                             on_leadership=self._on_leadership,
                             log=raft_log, stable=stable,
                             snapshots=snapshots,
                             fsm_snapshot=fsm_snapshot,
                             fsm_restore=fsm_restore,
                             fsm_capture=fsm_capture,
                             fsm_serialize=fsm_serialize,
                             snapshot_threshold=snapshot_threshold,
                             peer_addrs=getattr(transport, "peer_addrs", None),
                             on_config_change=self._on_config_change,
                             bootstrap=bootstrap,
                             dead_server_cleanup_s=dead_server_cleanup_s)
        self.store = RaftStore(self.local_store, self.raft)
        self.server = Server(config, store=self.store)
        # nomadload: proposes consult the server's admission plane, and
        # the proposal queue is its primary commit-path watermark
        self.raft.admission = self.server.loadctl
        self.server.loadctl.register_queue(
            "proposals", lambda: len(self.raft._proposals),
            self.server.config.loadctl_proposal_soft,
            self.server.config.loadctl_proposal_hard,
            commit_path=True)
        self._peer_lookup = peer_lookup
        self.transport = transport
        self._lock = threading.Lock()
        # cross-process forwarding: a SocketTransport dispatches incoming
        # "call" frames here (reference nomad/rpc.go forwardLeader)
        if hasattr(transport, "register_call_handler"):
            transport.register_call_handler(self._handle_remote_call)
        # gossip membership (reference nomad/serf.go): when enabled the
        # leader auto-joins gossip-discovered servers into the raft
        # configuration and reaps gossip-dead ones — `server join`
        # becomes "point a new server at ANY gossip address"
        self.gossip = None
        self._gossip_seeds = list(gossip_seeds or [])
        self._gossip_stop = threading.Event()
        self._gossip_dead_since = {}
        self._gossip_auto_join_disabled = False
        # seed (re-)join backoff: a lone agent whose seeds weren't up yet
        # keeps introducing itself, ever more slowly (utils/backoff.py)
        self._seed_backoff = Backoff(base=0.5, factor=2.0, cap=10.0)
        self._next_seed_join = 0.0
        if gossip_bind is not None:
            from .gossip import GossipAgent

            cfg = config or ServerConfig()
            if not cfg.gossip_key and not _is_loopback_bind(gossip_bind):
                # unkeyed gossip on a routable interface: anyone on the
                # network can inject ALIVE members, and the leader would
                # auto-join them as raft voters — a cluster takeover.
                # Keep membership visibility but refuse to act on it
                # (reference serf requires encrypt for WAN exposure)
                self._gossip_auto_join_disabled = True
                log.warning(
                    "gossip on %s binds a non-loopback interface with no "
                    "gossip_key: auto-join of gossip-discovered servers "
                    "is DISABLED (set gossip_key to enable)", gossip_bind)
            self.gossip = GossipAgent(
                node_id, gossip_bind,
                key=(cfg.gossip_key.encode() if cfg.gossip_key else None),
                meta={"rpc": getattr(transport, "bind_addr", ""),
                      "region": cfg.region})

    def _on_config_change(self, servers: Dict[str, str]) -> None:
        """Membership changed (config entry applied): teach the socket
        transport any new peer addresses so replication can reach them."""
        transport = self.transport
        addrs = getattr(transport, "peer_addrs", None)
        if addrs is None:
            return
        for sid, addr in servers.items():
            if addr and addrs.get(sid) != addr:
                addrs[sid] = addr

    def _handle_remote_call(self, method: str, args: tuple, kwargs: dict):
        if method == "raft_add_server":
            return self._membership_change("add_server", *args)
        if method == "raft_remove_server":
            return self._membership_change("remove_server", *args)
        if method == "raft_read_index":
            # follower read support: a remote follower asks us (the
            # presumed leader) for a read index (reference nomad's
            # forwarded Status.Peers/blocking-query pattern)
            consistent, timeout = args
            return self.raft.read_index(timeout=timeout,
                                        lease=not consistent)
        if method not in FORWARD:
            raise ValueError(f"method {method!r} is not forwardable")
        if not self.is_leader():
            raise NotLeaderError(self.raft.leader_id)
        return getattr(self.server, method)(*args, **kwargs)

    def _membership_change(self, op: str, *args):
        """Run a membership change on the leader: locally when this node
        leads, else one forwarded hop (the joiner only knows the address
        it contacted; this member knows the leader — reference
        nomad/serf.go join forwarding)."""
        for _ in Retryer(deadline_s=10.0, base=0.05, cap=0.5, jitter=0.25):
            if self.raft.is_leader():
                getattr(self.raft, op)(*args)
                return {"ok": True}
            lid = self.raft.leader_id
            if lid and lid != self.id and hasattr(self.transport, "call"):
                try:
                    return self.transport.call(
                        lid, f"raft_{op}", args, {})
                except RemoteCallError as e:
                    # real outcomes (unknown id, leader-removal refusal)
                    # must surface, not retry until the deadline
                    cls = self._WIRE_ERRORS.get(e.error_type)
                    if cls is not None:
                        raise cls(str(e)) from e
                    if e.error_type != "NotLeaderError":
                        raise
                except TransportError:
                    pass
        raise NotLeaderError(self.raft.leader_id)

    def join(self, contact_addr: str, timeout: float = 15.0) -> None:
        """Joiner-side: ask any live member at contact_addr to add this
        server to the cluster (agent `server join` — reference
        nomad/server.go:1602 Join via serf, here an explicit RPC)."""
        transport = self.transport
        if not hasattr(transport, "call"):
            raise RuntimeError("join requires the socket transport")
        contact_id = f"_join:{contact_addr}"
        transport.peer_addrs[contact_id] = contact_addr
        last_err = None
        try:
            for _ in Retryer(deadline_s=timeout, base=0.2, cap=1.0):
                try:
                    transport.call(contact_id, "raft_add_server",
                                   (self.id, transport.bind_addr), {})
                    return
                except (RemoteCallError, TransportError) as e:
                    last_err = e
        finally:
            transport.peer_addrs.pop(contact_id, None)
        raise TimeoutError(f"join via {contact_addr} failed: {last_err}")

    # -- lifecycle --

    def start(self) -> None:
        self.raft.start()
        if self.gossip is not None:
            self.gossip.start()
            for seed in self._gossip_seeds:
                self.gossip.join(seed)
            t = threading.Thread(target=self._run_gossip_reconcile,
                                 daemon=True,
                                 name=f"gossip-reconcile-{self.id}")
            t.start()

    def stop(self) -> None:
        self._gossip_stop.set()
        if self.gossip is not None:
            self.gossip.stop()
        # same lock as the leadership flip threads: a concurrent
        # establish/revoke must not interleave with shutdown
        with self._lock:
            if self.server._running:
                self.server.stop()
        self.raft.stop()

    def crash(self) -> None:
        """Abrupt kill (chaos harness): the node stops answering and
        sending immediately — no graceful leader handoff, no flush
        beyond what each append already fsynced — so the durable state
        left on disk is exactly what a real process crash leaves.
        Restart by building a fresh ReplicatedServer over the same
        data_dir (RaftCluster.restart)."""
        self.crashed = True
        if hasattr(self.transport, "unregister"):
            self.transport.unregister(self.id)
        self._gossip_stop.set()
        if self.gossip is not None:
            self.gossip.stop()
        self.raft.stop()
        with self._lock:
            if self.server._running:
                self.server.stop()
        if hasattr(self.raft.log, "close"):
            self.raft.log.close()

    def set_gossip_http(self, http_addr: str) -> None:
        """Advertise this server's agent HTTP address in gossip meta
        (WAN members use it to keep the federation region registry
        fresh). Bumps our incarnation so the change disseminates."""
        if self.gossip is None:
            return
        with self.gossip._lock:
            me = self.gossip.members[self.id]
            me["meta"]["http"] = http_addr
            me["inc"] += 1

    # -- gossip-driven autopilot (reference nomad/serf.go serverJoin /
    #    serverFailed feeding autopilot member reconciliation) --

    GOSSIP_RECONCILE_INTERVAL = 1.0

    def _run_gossip_reconcile(self) -> None:
        while not self._gossip_stop.wait(self.GOSSIP_RECONCILE_INTERVAL):
            self._maybe_rejoin_seeds()
            if not self.raft.is_leader():
                continue
            try:
                self._gossip_reconcile_once()
            except Exception:
                # transient raft state changes; next tick retries
                log.debug("gossip reconcile tick failed on %s",
                          self.id, exc_info=True)

    def _maybe_rejoin_seeds(self) -> None:
        """A single UDP join datagram to a not-yet-listening seed is
        simply lost: while this agent knows nobody but itself, keep
        re-introducing it to the seeds on an escalating backoff."""
        if self.gossip is None or not self._gossip_seeds:
            return
        if len(self.gossip.alive_members()) > 1:
            self._seed_backoff.reset()
            self._next_seed_join = 0.0
            return
        now = time.time()
        if now < self._next_seed_join:
            return
        self._next_seed_join = now + self._seed_backoff.next_delay()
        for seed in self._gossip_seeds:
            self.gossip.join(seed)

    # a gossip-DEAD verdict must persist this long before the leader
    # removes the voter: one dropped UDP probe or a brief stall must not
    # churn raft membership (the reference's autopilot applies the same
    # kind of grace before dead-server cleanup)
    GOSSIP_DEAD_REAP_S = 15.0

    def _gossip_reconcile_once(self) -> None:
        from .gossip import ALIVE, DEAD

        cfg_region = self.server.config.region
        members = self.gossip.snapshot()
        current = dict(self.raft.servers)
        now = time.time()
        dead_since = self._gossip_dead_since
        for mid in list(dead_since):
            m = members.get(mid)
            if m is None or m["status"] != DEAD:
                dead_since.pop(mid, None)
        for mid, m in members.items():
            meta = m.get("meta") or {}
            region = meta.get("region", cfg_region)
            if region != cfg_region:
                # WAN members maintain the federation registry instead
                # of joining this region's raft quorum
                http = meta.get("http", "")
                if http:
                    try:
                        snap_region = self.server.store.snapshot().region(
                            region)
                        if m["status"] != DEAD and (
                                snap_region is None
                                or snap_region.address != http):
                            self.server.upsert_region(
                                {"name": region, "address": http})
                    except Exception:
                        log.debug("federation registry upsert for region "
                                  "%s failed", region, exc_info=True)
                continue
            rpc = meta.get("rpc", "")
            if m["status"] == DEAD:
                if mid not in current or mid == self.id:
                    continue
                since = dead_since.setdefault(mid, now)
                if now - since < self.GOSSIP_DEAD_REAP_S:
                    continue
                # never remove a voter if the remaining set would lack
                # a gossip-alive majority (availability over cleanup)
                remaining = [sid for sid in current if sid != mid]
                alive = sum(
                    1 for sid in remaining
                    if sid == self.id
                    or (members.get(sid) or {}).get("status") == ALIVE)
                if remaining and alive < len(remaining) // 2 + 1:
                    continue
                try:
                    self.raft.remove_server(mid)
                except Exception:
                    log.debug("autopilot removal of dead server %s failed",
                              mid, exc_info=True)
            elif mid not in current and rpc:
                if self._gossip_auto_join_disabled:
                    # unkeyed non-loopback gossip (see __init__): treat
                    # discovered members as advisory only
                    continue
                try:
                    self.raft.add_server(mid, rpc)
                except Exception:
                    log.debug("autopilot join of gossip member %s failed",
                              mid, exc_info=True)

    def _on_leadership(self, is_leader: bool) -> None:
        # runs on raft threads; establish/revoke the leader subsystems
        # (leader.go:357/1488)
        def flip():
            with self._lock:
                if is_leader and not self.server._running:
                    self.server.start()
                elif not is_leader and self.server._running:
                    self.server.stop()

        threading.Thread(target=flip, daemon=True,
                         name=f"leadership-{self.id}").start()

    def remove_peer(self, server_id: str):
        """Operator removal of a server (reference `operator raft
        remove-peer`, nomad/operator_endpoint.go RaftRemovePeer)."""
        return self._membership_change("remove_server", server_id)

    # -- forwarded endpoint surface --

    def is_leader(self) -> bool:
        return self.raft.is_leader() and self.server._running

    # -- read path (follower reads) --

    def known_leader(self) -> bool:
        """X-Nomad-KnownLeader: does this server currently know who the
        leader is? A crashed/stopped node's stale leader_id doesn't
        count — its belief is frozen, not current."""
        if self.crashed or self.raft._stop.is_set():
            return False
        return bool(self.raft.leader_id)

    def last_contact(self) -> float:
        """X-Nomad-LastContact: seconds since last leader contact (0.0
        on the leader, inf when no leader was ever heard)."""
        return self.raft.last_contact_age()

    def read_index(self, consistent: bool = False, timeout: float = 2.0
                   ) -> int:
        """Obtain a linearizable read index from the leader — locally
        when this node leads, else one hop to the leader (in-process via
        peer_lookup or over the socket transport). The caller then waits
        for its LOCAL store to reach the index and serves the read from
        any server (the Raft §6.4 follower-read protocol)."""
        if self.raft.is_leader():
            return self.raft.read_index(timeout=timeout,
                                        lease=not consistent)
        lid = self.raft.leader_id
        if not lid or lid == self.id:
            raise NotLeaderError(lid)
        if self._peer_lookup is not None:
            peer = self._peer_lookup(lid)
            if peer is None:
                raise NotLeaderError(lid)
            return peer.raft.read_index(timeout=timeout,
                                        lease=not consistent)
        if hasattr(self.transport, "call"):
            try:
                return self.transport.call(
                    lid, "raft_read_index", (consistent, timeout), {})
            except RemoteCallError as e:
                if e.error_type in ("NotLeaderError", "TimeoutError"):
                    raise NotLeaderError(lid) from e
                cls = self._WIRE_ERRORS.get(e.error_type)
                if cls is not None:
                    raise cls(str(e)) from e
                raise
            except TransportError as e:
                # reads are idempotent: a torn call is just "no index"
                raise NotLeaderError(lid) from e
        raise NotLeaderError(lid)

    def wait_applied(self, index: int, timeout: float = 5.0) -> None:
        """Wait until the LOCAL fsm reaches a read_index() result."""
        self.raft.wait_applied(index, timeout)

    # forwarded endpoints raise these; the HTTP layer maps them to status
    # codes, so they must survive the socket hop as their concrete types.
    # RetryLater is nomadload's structured admission rejection (429 +
    # Retry-After): it must arrive typed so the follower's _forward does
    # NOT retry it — server-side retries of a shed request are exactly
    # the amplification the admission plane exists to prevent.
    _WIRE_ERRORS = {"KeyError": KeyError, "ValueError": ValueError,
                    "PermissionError": PermissionError,
                    "TimeoutError": TimeoutError, "RuntimeError": RuntimeError,
                    "RetryLater": RetryLater}

    def _forward(self, name: str, args: tuple, kwargs: dict):
        """Run the endpoint on the leader: locally if this node leads,
        in-process via peer_lookup, or over the socket transport
        (reference nomad/rpc.go:445 forward)."""
        # nomadload deadline propagation: the forward hop inherits the
        # request deadline bound at ingress — already-expired work drops
        # here, and the retry window never outlives the client
        rem = loadctl.remaining()
        if rem is not None and loadctl.drop_if_expired("forward"):
            raise TimeoutError("request deadline passed before forward")
        fwd_deadline = 5.0 if rem is None else max(0.05, min(5.0, rem))
        # jittered backoff instead of a fixed 20 ms poll: during an
        # election every forwarder on every node spins this loop, and
        # synchronized polls pile onto the freshly elected leader
        for _ in Retryer(deadline_s=fwd_deadline, base=0.02, cap=0.25,
                         jitter=0.5):
            try:
                if self.is_leader():
                    return getattr(self.server, name)(*args, **kwargs)
                lid = self.raft.leader_id
                peer = (self._peer_lookup(lid) if lid and lid != self.id
                        and self._peer_lookup is not None else None)
                if peer is not None and peer.is_leader():
                    return getattr(peer.server, name)(*args, **kwargs)
            except HeartbeatPlaneInactive:
                # elected a moment ago and still establishing: the
                # heartbeat plane comes up a few statements after the
                # server counts as running (Server.start)
                continue
            if (lid and lid != self.id and self._peer_lookup is None
                    and hasattr(self.transport, "call")):
                try:
                    return self.transport.call(lid, name, args, kwargs)
                except RemoteCallError as e:
                    if e.error_type == "NotLeaderError":
                        # stale leader hint: wait for the next election
                        continue
                    cls = self._WIRE_ERRORS.get(e.error_type)
                    if cls is not None:
                        raise cls(str(e)) from e
                    raise
                except TransportError as e:
                    # "connection died after the frame left" is NOT
                    # retriable: the leader may have applied the
                    # mutation, and these endpoints are not idempotent
                    # (create_acl_token, register_job evals)
                    if getattr(e, "maybe_delivered", False):
                        raise
                    # connect failure: definitely not delivered; retry
        raise NotLeaderError(self.raft.leader_id)

    def __getattr__(self, name: str):
        if name in FORWARD:
            def call(*args, **kwargs):
                return self._forward(name, args, kwargs)

            return call
        raise AttributeError(name)


class RaftCluster:
    """N in-process replicated servers on one transport (the reference's
    in-process multi-server test topology, nomad/testing.go)."""

    def __init__(self, n: int = 3, config_fn: Optional[Callable[[int], ServerConfig]] = None,
                 data_dir: Optional[str] = None, snapshot_threshold: int = 1024):
        self.transport = InProcTransport()
        ids = [f"server-{i}" for i in range(n)]
        self._ids = ids
        self._config_fn = config_fn
        self._data_dir = data_dir
        self._snapshot_threshold = snapshot_threshold
        self.servers: Dict[str, ReplicatedServer] = {}
        for i, node_id in enumerate(ids):
            cfg = config_fn(i) if config_fn else ServerConfig(heartbeat_ttl=30.0)
            node_dir = None
            if data_dir is not None:
                import os
                node_dir = os.path.join(data_dir, node_id)
                os.makedirs(node_dir, exist_ok=True)
            self.servers[node_id] = ReplicatedServer(
                node_id, ids, self.transport, cfg,
                peer_lookup=self.servers.get, data_dir=node_dir,
                snapshot_threshold=snapshot_threshold)

    def start(self) -> "RaftCluster":
        for s in self.servers.values():
            s.start()
        return self

    def stop(self) -> None:
        for s in self.servers.values():
            s.stop()
        if hasattr(self.transport, "close"):
            self.transport.close()

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()

    # -- chaos crash/restart (the harness's server-death injection) --

    def crash(self, node_id: str) -> ReplicatedServer:
        """Kill one server abruptly (see ReplicatedServer.crash). The
        dead instance stays in self.servers until restart() replaces
        it, like a dead process whose data_dir persists."""
        server = self.servers[node_id]
        server.crash()
        return server

    def restart(self, node_id: str) -> ReplicatedServer:
        """Start a fresh ReplicatedServer over the crashed one's
        data_dir — the durable-recovery path a real restart takes.
        Meaningful only for clusters built with data_dir (otherwise the
        replacement boots empty and rejoins via snapshot transfer)."""
        old = self.servers[node_id]
        i = self._ids.index(node_id)
        cfg = (self._config_fn(i) if self._config_fn
               else ServerConfig(heartbeat_ttl=30.0))
        replacement = ReplicatedServer(
            node_id, self._ids, self.transport, cfg,
            peer_lookup=self.servers.get, data_dir=old.data_dir,
            snapshot_threshold=self._snapshot_threshold)
        self.servers[node_id] = replacement
        replacement.start()
        return replacement

    def wait_for_leader(self, timeout: float = 10.0) -> Optional[ReplicatedServer]:
        deadline = time.time() + timeout
        while time.time() < deadline:
            for s in self.servers.values():
                if s.is_leader():
                    return s
            time.sleep(0.02)
        return None

    def leader(self) -> Optional[ReplicatedServer]:
        for s in self.servers.values():
            if s.is_leader():
                return s
        return None

    def followers(self) -> List[ReplicatedServer]:
        return [s for s in self.servers.values() if not s.raft.is_leader()]

    def any_server(self) -> ReplicatedServer:
        return next(iter(self.servers.values()))
