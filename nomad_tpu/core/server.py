"""Server: single-process control-plane composition
(reference nomad/server.go + leader.go establishLeadership).

Wires the MVCC state store to the eval broker, blocked-evals tracker,
plan queue/applier, scheduler worker pool, and heartbeat manager, and
exposes the RPC-endpoint-shaped API (Job.Register, Node.Register,
Node.UpdateStatus, Node.UpdateAlloc, Eval.*) that the HTTP layer and CLI
sit on. Leadership is implicit (single server); the replicated-log
boundary is the store's commit path, so a Raft transport can slot in
beneath without touching this layer.
"""

from __future__ import annotations

import copy as _copy
import functools
import logging
import queue
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from ..state import StateStore
from ..structs import enums
from ..structs.evaluation import Evaluation
from ..structs.job import Job
from ..structs.node import Node
from ..structs.operator import SchedulerConfiguration
from ..utils import generate_uuid
from .blocked import BlockedEvals
from .broker import EvalBroker
from .core_sched import CoreScheduler
from .deployments import DeploymentWatcher
from .drainer import NodeDrainer
from .events import EventBroker
from .heartbeat import HeartbeatManager, HeartbeatPlaneInactive
from .loadctl import TIER_COMMIT, TIER_LIVENESS, TIER_SUBMIT, bind_tier
from .periodic import PeriodicDispatcher
from .plan_apply import PlanApplier, PlanQueue
from .worker import Worker


def _tiered(tier: int, source: str):
    """Admission + tier binding for an RPC-endpoint method (nomadload):
    consult the server's AdmissionController — RetryLater propagates to
    the caller as HTTP 429 / a typed wire error — then bind the tier
    thread-locally so every downstream consult point on this request
    (raft propose, broker enqueue) classifies the work identically.
    Tier 0 records its admit (the evidence chaos invariant 10 audits)
    but is never shed while the server is alive; a stopping server's
    heartbeat plane already rejects truthfully via
    HeartbeatPlaneInactive."""
    def deco(fn):
        @functools.wraps(fn)
        def wrapper(self, *args, **kwargs):
            if tier <= TIER_LIVENESS:
                self.loadctl.try_admit(tier, source=source)
            else:
                self.loadctl.admit(tier, source=source)
            with bind_tier(tier):
                return fn(self, *args, **kwargs)
        return wrapper
    return deco


@dataclass
class ServerConfig:
    num_workers: int = 2
    heartbeat_ttl: float = 10.0
    # Heartbeat manager sharding (fleet-scale node plane): timers are
    # spread over this many timer-wheel shards, each drained by one
    # expiry thread. 1 restores the single-lock manager (A/B baseline).
    heartbeat_shards: int = 8
    # Expiry-rate limiter: max missed-TTL mark-downs per second across
    # all shards — a mass expiry (partitioned rack, dead leader's
    # backlog) degrades to a paced trickle of mark-down batches instead
    # of an FSM thundering herd. <= 0 disables the limiter.
    heartbeat_expiry_rate: float = 512.0
    nack_timeout: float = 60.0
    eval_delivery_limit: int = 3
    # eval_batch_size: max ready evals a scheduler worker drains per
    # dequeue and runs against one shared snapshot + ClusterStatic
    eval_batch_size: int = 8
    # backoff before a delivery-limited eval is retried
    # (reference leader.go failedEvalUnblockInterval)
    failed_eval_followup_delay: float = 60.0
    # cadence for retrying evals blocked by plan-attempt exhaustion
    # (reference leader.go:443 periodicUnblockFailedEvals)
    failed_eval_unblock_interval: float = 60.0
    # Bad-node quarantine: a node rejecting this many plans inside the
    # window is marked ineligible. Off by default with a high threshold,
    # like the reference (plan_rejection_tracker is opt-in, node_threshold
    # 100): ordinary optimistic-concurrency losses on hot binpack nodes
    # also count as rejections, and quarantine is not auto-reverted.
    plan_rejection_tracker_enabled: bool = False
    plan_rejection_threshold: int = 100
    plan_rejection_window: float = 300.0
    gc_interval: float = 60.0
    # event-broker fan-out shards (per-topic-hash rings/locks; see
    # core/events.py) and per-shard ring capacity
    event_shards: int = 8
    event_ring_size: int = 4096
    acl_enabled: bool = False
    # workload-identity JWT lifetime (client/widmgr renews at ~half TTL;
    # reference nomad/structs WorkloadIdentity TTL)
    identity_ttl: float = 3600.0
    # shared secret authenticating gossip datagrams (reference: Serf
    # encrypt key); empty = unauthenticated gossip (dev only)
    gossip_key: str = ""
    # multi-region federation (reference nomad/rpc.go region forwarding
    # + leader.go replication loops)
    region: str = "global"
    authoritative_region: str = ""
    acl_replication_interval: float = 30.0
    replication_token: str = ""
    # -- nomadload overload envelope (ROBUSTNESS.md) -----------------
    # queue-depth watermarks feeding the shed floor: soft sheds reads,
    # hard sheds submits too (loadctl.AdmissionController). Generous by
    # design — they bound collapse, they don't police steady state.
    loadctl_proposal_soft: int = 512
    loadctl_proposal_hard: int = 2048
    loadctl_plan_soft: int = 256
    loadctl_plan_hard: int = 1024
    loadctl_broker_soft: int = 8192
    loadctl_broker_hard: int = 32768
    loadctl_parked_soft: int = 16384
    loadctl_parked_hard: int = 65536
    # brownout hysteresis: sustained commit-path hard pressure for
    # `brownout_after` s enters degraded mode (stale-only reads,
    # coalesced watch wakeups); `brownout_exit` s of calm leaves it
    loadctl_brownout_after: float = 1.0
    loadctl_brownout_exit: float = 3.0
    # poison-eval quarantine (core/broker.py): a job whose evals hit
    # the delivery limit this many times in a row is quarantined — its
    # serialization token released, no more hot followups
    eval_quarantine_threshold: int = 3
    sched_config: SchedulerConfiguration = field(default_factory=SchedulerConfiguration)


class Server:
    def __init__(self, config: Optional[ServerConfig] = None,
                 store: Optional[StateStore] = None, logger=None):
        self.config = config or ServerConfig()
        self.store = store or StateStore()
        self.logger = logger or logging.getLogger("nomad_tpu.server")
        self.sched_config = self.config.sched_config

        from .loadctl import AdmissionController

        # nomadload admission plane: one controller per server, wired
        # to the live queue depths below (ROBUSTNESS.md "Overload
        # envelope"). Constructed first so every subsystem can take it.
        self.loadctl = AdmissionController(
            brownout_after=self.config.loadctl_brownout_after,
            brownout_exit=self.config.loadctl_brownout_exit)
        self.broker = EvalBroker(
            nack_timeout=self.config.nack_timeout,
            delivery_limit=self.config.eval_delivery_limit,
            quarantine_threshold=self.config.eval_quarantine_threshold,
            admission=self.loadctl)
        self.blocked = BlockedEvals(self._requeue_unblocked,
                                    persist_fn=self.store.upsert_evals)
        self.plan_queue = PlanQueue()
        from .plan_apply import BadNodeTracker

        self.plan_applier = PlanApplier(
            self.store, self.plan_queue, self.logger,
            bad_node_tracker=BadNodeTracker(
                threshold=self.config.plan_rejection_threshold,
                window=self.config.plan_rejection_window,
                on_bad_node=self._on_bad_node))
        self.heartbeats = HeartbeatManager(
            self, ttl=self.config.heartbeat_ttl,
            shards=self.config.heartbeat_shards,
            expiry_rate=self.config.heartbeat_expiry_rate)
        self.workers: List[Worker] = [
            Worker(self, i) for i in range(self.config.num_workers)]
        from .encrypter import Encrypter

        self.encrypter = Encrypter()
        # pending OIDC auth requests: state -> request (leader-local,
        # reference acl_endpoint.go oidcRequestCache)
        self._oidc_lock = threading.Lock()
        self._oidc_requests = {}
        self.acl_enabled = self.config.acl_enabled
        self.deployment_watcher = DeploymentWatcher(self)
        self.drainer = NodeDrainer(self)
        self.periodic = PeriodicDispatcher(self)
        self.core_gc = CoreScheduler(self, interval=self.config.gc_interval)
        self.events = EventBroker(self.store,
                                  ring_size=self.config.event_ring_size,
                                  shards=self.config.event_shards)
        # nomadflow shadow replica (NOMAD_TPU_SAN=1, else a no-op):
        # replays this server's event stream and diff-checks it against
        # MVCC snapshot rebuilds — see analysis/shadow.py
        from ..analysis import shadow as _shadow

        _shadow.maybe_attach(self.store, self.events)
        # nomadstate incremental feed: maintains the device-resident
        # cluster usage base off this same event stream —
        # tensor/incremental.py
        from ..tensor import incremental as _incremental

        _incremental.maybe_attach(self.store, self.events)
        from .allocsync import AllocSyncHub, ClientUpdateBatcher

        # delta alloc push to clients + batched client status commits
        self.alloc_sync = AllocSyncHub(self)
        self.client_updates = ClientUpdateBatcher(self.store)
        self._running = False
        # Commit listeners fire inline on the store's write path — which
        # under raft is the apply thread. The unblock path re-proposes
        # through the store (RaftStore), so running it inline would
        # deadlock the apply loop on itself; pump events through a queue
        # to a dedicated thread instead (the reference's Unblock() is a
        # channel send consumed by the blocked-evals watcher goroutine).
        self._commit_q: "queue.Queue" = queue.Queue()
        self.store.add_commit_listener(
            lambda index, events: self._commit_q.put((index, events)))
        self._commit_pump = threading.Thread(
            target=self._run_commit_pump, daemon=True, name="commit-pump")
        self._commit_pump.start()
        # watermark sources: the live queue depths the gauges already
        # export. The raft proposal queue registers itself when a
        # ReplicatedServer attaches (raft/cluster.py).
        self.loadctl.register_queue(
            "plan", self.plan_queue.depth,
            self.config.loadctl_plan_soft, self.config.loadctl_plan_hard,
            commit_path=True)
        self.loadctl.register_queue(
            "broker", self.broker.pending_count,
            self.config.loadctl_broker_soft,
            self.config.loadctl_broker_hard)
        self.loadctl.register_queue(
            "parked", self.store.watches.parked,
            self.config.loadctl_parked_soft,
            self.config.loadctl_parked_hard)
        self.store.watches.admission = self.loadctl

    # -- lifecycle (leader.go:357 establishLeadership) --

    def start(self) -> None:
        if self._running:
            return
        self._running = True
        self.loadctl.set_alive(True)
        self.plan_queue.set_enabled(True)
        self.plan_applier.start()
        self.broker.set_enabled(True)
        self.blocked.set_enabled(True)
        self.alloc_sync.start()
        self.client_updates.start()
        self.heartbeats.set_enabled(True)
        self._restore_heartbeats()
        self._restore_scheduler_config()
        self._restore_evals()
        for w in self.workers:
            w.start()
        self.deployment_watcher.start()
        self.drainer.start()
        self.periodic.start()
        self.core_gc.start()
        self._reaper = threading.Thread(target=self._run_reaper, daemon=True,
                                        name="eval-reaper")
        self._reaper.start()
        if (self.config.authoritative_region
                and self.config.authoritative_region != self.config.region):
            self._repl_stop = threading.Event()
            t = threading.Thread(target=self._run_acl_replication,
                                 daemon=True, name="acl-replication")
            t.start()
            self._repl_thread = t

    def _run_acl_replication(self) -> None:
        """Leader-only pull replication of ACL metadata from the
        authoritative region (reference nomad/leader.go
        replicateACLPolicies/Roles; ours pulls over the region's agent
        HTTP with the replication token). Non-authoritative regions
        converge to the authoritative region's policies/roles so a
        token minted anywhere means the same thing everywhere."""
        from ..api.client import ApiClient, ApiError
        from ..raft.node import NotLeaderError

        interval = self.config.acl_replication_interval
        while not self._repl_stop.wait(interval):
            # leader-only for real: in a replicated region a follower's
            # store.apply raises NotLeaderError — without this gate the
            # thread died on its first write and replication silently
            # stopped after any failover (ADVICE r4)
            if not self._is_raft_leader():
                continue
            addr = self.region_address(self.config.authoritative_region)
            if not addr:
                continue
            api = ApiClient(addr, token=self.config.replication_token,
                            timeout=10.0)
            try:
                upstream_p = api.get("/v1/acl/policies")[0] or []
                upstream_r = api.get("/v1/acl/roles")[0] or []
            except (ApiError, OSError, ValueError):
                continue  # authoritative region unreachable: retry
            snap = self.store.snapshot()
            seen_p = set()
            for p in upstream_p:
                name = p.get("name", "")
                seen_p.add(name)
                # per-object isolation: one malformed policy must not
                # stall convergence of everything after it
                try:
                    detail, _ = api.get(f"/v1/acl/policy/{name}")
                    if not detail:
                        continue
                    local = snap.acl_policy(name)
                    rules = detail.get("rules", "{}")
                    desc = detail.get("description", "")
                    # change detection: blind re-upserts would churn
                    # the raft log and wake every blocking query each
                    # interval
                    if (local is not None and local.rules == rules
                            and local.description == desc):
                        continue
                    self.upsert_acl_policy(name, rules, desc)
                except (ApiError, OSError, ValueError, NotLeaderError):
                    continue
            seen_r = set()
            for r in upstream_r:
                name = r.get("name", "")
                seen_r.add(name)
                try:
                    local = snap.acl_role(name)
                    pols = list(r.get("policies", []))
                    desc = r.get("description", "")
                    if (local is not None and list(local.policies) == pols
                            and local.description == desc):
                        continue
                    self.upsert_acl_role(name, pols, desc)
                except (ApiError, OSError, ValueError, NotLeaderError):
                    continue
            # full mirror: names revoked upstream must stop granting
            # here (reference replication deletes too). A leadership
            # change mid-cycle must never kill the thread — the next
            # cycle's gate skips until this replica leads again.
            try:
                for local_p in list(snap.acl_policies()):
                    if local_p.name not in seen_p:
                        self.store.delete_acl_policy(local_p.name)
                for local_r in list(snap.acl_roles()):
                    if local_r.name not in seen_r:
                        self.store.delete_acl_role(local_r.name)
            except NotLeaderError:
                continue

    def _is_raft_leader(self) -> bool:
        """True when this server may write: always in a single-server
        deployment, leader-only under raft (the store facade is a
        RaftStore there)."""
        raft = getattr(self.store, "_raft", None)
        return raft is None or raft.is_leader()

    def stop(self) -> None:
        if not self._running:
            return
        self._running = False
        # a stopping server may truthfully reject liveness traffic
        # (the HeartbeatPlaneInactive contract); flip BEFORE teardown
        # so invariant 10 never sees a live server shed tier 0
        self.loadctl.set_alive(False)
        if getattr(self, "_repl_stop", None) is not None:
            self._repl_stop.set()
        for w in self.workers:
            w.stop()
        for w in self.workers:
            w.join()
        self.core_gc.stop()
        self.periodic.stop()
        self.drainer.stop()
        self.deployment_watcher.stop()
        self.heartbeats.set_enabled(False)
        self.client_updates.stop()
        self.alloc_sync.stop()
        self.blocked.set_enabled(False)
        self.broker.set_enabled(False)
        self.plan_applier.stop()
        self.store.watches.teardown()
        self._reaper.join(timeout=2.0)

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()

    def _restore_scheduler_config(self) -> None:
        cfg = self.store.snapshot().scheduler_configuration()
        if cfg is not None:
            self._apply_scheduler_config(cfg)

    def _restore_heartbeats(self) -> None:
        """Arm TTL timers from replicated state on establishLeadership
        (reference heartbeat.go initializeHeartbeatTimers). Without
        this, a client that went silent during a leader failover is
        never invalidated by the new leader — its timer lived only on
        the old one — and its allocs are never rescheduled."""
        ready = [n.id for n in self.store.snapshot().nodes()
                 if n.status == enums.NODE_STATUS_READY]
        self.heartbeats.restore(ready)

    def _restore_evals(self) -> None:
        """Re-enqueue non-terminal evals and re-track periodic parents
        after (re)start (leader.go:389-403 restoreEvals + :412 periodic
        restore)."""
        snap = self.store.snapshot()
        for ev in snap.evals():
            if ev.should_enqueue():
                self.broker.enqueue(ev)
            elif ev.should_block():
                self.blocked.block(ev)
        for job in snap.jobs():
            if job.is_periodic and job.periodic.enabled and not job.stopped():
                self.periodic.add(job)

    # -- commit listener: unblock blocked evals on cluster changes --

    def _run_commit_pump(self) -> None:
        while True:
            index, events = self._commit_q.get()
            try:
                self._on_commit(index, events)
            except Exception:
                if self.logger:
                    self.logger.exception("commit listener failed")

    def _on_commit(self, index: int, events: list) -> None:
        for kind, payload in events:
            if kind == "scheduler-config" and payload is not None:
                # idempotent apply — the leader already applied its own
                # update synchronously; replicas apply here
                self._apply_scheduler_config(payload)
                continue
            if kind == "restore":
                # operator snapshot restore replaced the whole store:
                # the restored scheduler config must govern the RUNNING
                # server too, not just the next restart
                self._restore_scheduler_config()
                continue
            if kind in ("node-upsert", "node-status", "node-eligibility", "node-drain"):
                if payload is not None and payload.ready():
                    self.blocked.unblock(payload.computed_class)
            elif kind in ("alloc-stop", "alloc-preempt", "alloc-client-update",
                          "alloc-transition"):
                # capacity freed by a terminal alloc can unblock evals
                # (reference fsm.go:412,470 Unblock on alloc updates)
                a = payload
                if a is not None and (a.terminal_status() or a.server_terminal()):
                    self.blocked.unblock("")

    def _on_bad_node(self, node_id: str) -> None:
        """A node crossed the plan-rejection threshold: quarantine it so
        schedulers stop wasting retries on it (reference
        plan_apply_node_tracker.go -> Node.UpdateEligibility)."""
        if not self.config.plan_rejection_tracker_enabled:
            return
        if self.logger:
            self.logger.warning(
                "node %s exceeded the plan rejection threshold; "
                "marking ineligible", node_id)
        # commit the eligibility flip BEFORE announcing it: a subscriber
        # woken by the quarantine event must see the node ineligible in
        # any snapshot it takes (flow-publish-before-commit)
        try:
            self.update_node_eligibility(node_id, enums.NODE_SCHED_INELIGIBLE)
        except KeyError:
            pass  # node vanished; nothing to quarantine
        self.events.publish("Node", "node-quarantined",
                            {"node_id": node_id,
                             "reason": "plan rejection threshold exceeded"})

    def _requeue_unblocked(self, ev: Evaluation) -> None:
        """An unblocked eval re-enters the broker as pending; persist the
        transition on a copy (store snapshots share the object)."""
        upd = _copy.copy(ev)
        upd.status = enums.EVAL_STATUS_PENDING
        upd.wait_until = 0.0
        self.store.upsert_evals([upd])
        self.broker.enqueue(upd)

    # -- failed-eval reaper (leader.go:1162 reapFailedEvaluations) --

    def _run_reaper(self) -> None:
        next_unblock_failed = time.time() + self.config.failed_eval_unblock_interval
        while self._running:
            # condition wait, not a busy-poll: wakes the moment the
            # broker produces reaper work (failed-queue eval, cancelled
            # pending evals), at the unblock-failed deadline, or when a
            # stopping server disables the broker — an idle server burns
            # zero wakeups between deadlines
            self.broker.wait_for_reaper_work(
                timeout=max(0.05, next_unblock_failed - time.time()))
            if not self._running:
                return
            # persist cancellations of superseded pending evals
            cancelled = self.broker.drain_cancelled()
            if cancelled:
                self.store.upsert_evals(cancelled)
            # quarantined poison evals: mark failed, NO follow-up — the
            # chain already burned quarantine_threshold failed-queue
            # rounds and the job's serialization token is released
            quarantined = self.broker.drain_quarantined()
            if quarantined:
                updates = []
                for ev in quarantined:
                    failed = _copy.copy(ev)
                    failed.status = enums.EVAL_STATUS_FAILED
                    failed.status_description = (
                        "evaluation quarantined after repeated delivery failures")
                    updates.append(failed)
                self.store.upsert_evals(updates)
            # retry conflict-stranded (max-plan) blocked evals on a timer
            if time.time() >= next_unblock_failed:
                self.blocked.unblock_failed()
                next_unblock_failed = (time.time()
                                       + self.config.failed_eval_unblock_interval)
            # delivery-limited evals: mark failed, schedule a follow-up
            from .broker import FAILED_QUEUE

            ev, token = self.broker.dequeue([FAILED_QUEUE], timeout=0)
            if ev is None:
                continue
            failed = _copy.copy(ev)
            failed.status = enums.EVAL_STATUS_FAILED
            failed.status_description = "evaluation reached delivery limit"
            followup = Evaluation(
                id=generate_uuid(),
                namespace=ev.namespace,
                priority=ev.priority,
                type=ev.type,
                triggered_by=enums.TRIGGER_FAILED_FOLLOW_UP,
                job_id=ev.job_id,
                status=enums.EVAL_STATUS_PENDING,
                wait_until=time.time() + self.broker.followup_delay(
                    ev, self.config.failed_eval_followup_delay),
                previous_eval=ev.id,
                create_time=time.time(),
            )
            self.store.upsert_evals([failed, followup])
            try:
                self.broker.ack(ev.id, token)
            except ValueError:
                pass
            self.broker.enqueue(followup)

    # -- Job endpoints (nomad/job_endpoint.go) --

    @_tiered(TIER_SUBMIT, "job_register")
    def register_job(self, job: Job) -> str:
        """Job.Register: upsert + create an eval. Returns the eval id."""
        if self.sched_config.reject_job_registration:
            raise PermissionError("job registration disabled")
        self._check_namespace(job.namespace)
        self.store.upsert_job(job)
        if job.is_periodic:
            # periodic parents don't run; the dispatcher launches children
            # on the cron schedule (nomad/periodic.go); disabled configs
            # register but stay parked
            if job.periodic.enabled:
                self.periodic.add(job)
            else:
                self.periodic.remove(job.namespace, job.id)
            return ""
        # a re-registered job may have dropped its periodic stanza
        self.periodic.remove(job.namespace, job.id)
        if job.is_parameterized:
            # parameterized parents are templates: they never schedule;
            # dispatch mints runnable children (nomad/job_endpoint.go
            # Job.Dispatch)
            return ""
        return self._create_job_eval(job, enums.TRIGGER_JOB_REGISTER)

    @_tiered(TIER_SUBMIT, "job_dispatch")
    def dispatch_job(self, job_id: str, payload: bytes = b"",
                     meta: Optional[Dict[str, str]] = None,
                     namespace: str = "default") -> Dict[str, str]:
        """Job.Dispatch (reference nomad/job_endpoint.go dispatch path):
        validate payload/meta against the parent's parameterized config,
        mint a dispatched child job, register it, and return
        {dispatched_job_id, eval_id}."""
        meta = dict(meta or {})
        snap = self.store.snapshot()
        parent = snap.job_by_id(job_id, namespace)
        if parent is None or parent.stopped():
            # a stopped template is gone as far as dispatch is concerned
            raise KeyError(f"job {job_id} not found")
        if parent.parameterized is None or parent.dispatched:
            raise ValueError(f"job {job_id} is not parameterized")
        cfg = parent.parameterized
        if cfg.payload == "required" and not payload:
            raise ValueError("payload is required")
        if cfg.payload == "forbidden" and payload:
            raise ValueError("payload is forbidden")
        allowed = set(cfg.meta_required) | set(cfg.meta_optional)
        missing = [k for k in cfg.meta_required if k not in meta]
        if missing:
            raise ValueError(f"missing required dispatch meta: {missing}")
        unknown = [k for k in meta if k not in allowed]
        if unknown:
            raise ValueError(f"dispatch meta not allowed: {unknown}")

        child = _copy.deepcopy(parent)
        # reference DispatchedID: <parent>/dispatch-<unix>-<uuid-prefix>
        child.id = (f"{parent.id}/dispatch-{int(time.time())}-"
                    f"{generate_uuid()[:8]}")
        child.name = child.id
        child.parent_id = parent.id
        child.dispatched = True
        child.payload = payload
        child.meta = dict(parent.meta)
        child.meta.update(meta)
        child.status = enums.JOB_STATUS_PENDING
        child.version = 0
        child.create_index = 0
        child.modify_index = 0
        self.store.upsert_job(child)
        eval_id = self._create_job_eval(child, enums.TRIGGER_JOB_REGISTER)
        return {"dispatched_job_id": child.id, "eval_id": eval_id}

    @_tiered(TIER_SUBMIT, "job_deregister")
    def deregister_job(self, job_id: str, namespace: str = "default",
                       purge: bool = False) -> str:
        snap = self.store.snapshot()
        job = snap.job_by_id(job_id, namespace)
        self.store.delete_job(job_id, namespace, purge=purge)
        self.blocked.untrack_job(namespace, job_id)
        self.periodic.remove(namespace, job_id)
        if job is None:
            return ""
        return self._create_job_eval(job, enums.TRIGGER_JOB_DEREGISTER,
                                     namespace=namespace)

    @_tiered(TIER_SUBMIT, "job_evaluate")
    def create_job_eval(self, job: Job, trigger: str = enums.TRIGGER_JOB_REGISTER) -> str:
        """Public force-evaluation endpoint (reference Job.Evaluate);
        forwardable to the leader in a replicated deployment."""
        return self._create_job_eval(job, trigger)

    def set_scheduler_config(self, cfg: SchedulerConfiguration) -> None:
        """Operator scheduler-config update, stored in REPLICATED state
        (reference operator_endpoint.go SchedulerSetConfiguration ->
        scheduler_config table): every replica applies it via the
        commit listener, so a failover keeps the operator's settings."""
        self.store.set_scheduler_configuration(cfg)
        self._apply_scheduler_config(cfg)

    def _apply_scheduler_config(self, cfg: SchedulerConfiguration) -> None:
        """Make a (locally committed or replicated) scheduler config
        effective on this server."""
        # single-reference rebind of an immutable config object: readers
        # (workers mid-eval) tolerate either snapshot, GIL makes the
        # swap atomic, and the two fields need no mutual consistency
        self.sched_config = cfg  # san-ok: atomic reference swap by design
        self.config.sched_config = cfg
        # pause/resume the broker (reference operator.go PauseEvalBroker):
        # disabling flushes the in-memory queues, so resuming restores
        # pending evals from replicated state exactly like a leadership
        # transition does (leader.go:389-403)
        if self._running:
            was = self.broker.enabled
            self.broker.set_enabled(not cfg.pause_eval_broker)
            if not was and not cfg.pause_eval_broker:
                self._restore_evals()

    def _create_job_eval(self, job: Job, trigger: str,
                         namespace: Optional[str] = None) -> str:
        ev = Evaluation(
            id=generate_uuid(),
            namespace=namespace or job.namespace,
            priority=job.priority,
            type=job.type,
            triggered_by=trigger,
            job_id=job.id,
            status=enums.EVAL_STATUS_PENDING,
            create_time=time.time(),
        )
        # upsert_evals stamps create/modify_index on ev in-txn; restamping
        # here would mutate a row that is already shared MVCC history
        self.store.upsert_evals([ev])
        self.broker.enqueue(ev)
        return ev.id

    # -- Node endpoints (nomad/node_endpoint.go) --

    @_tiered(TIER_LIVENESS, "node_register")
    def register_node(self, node: Node) -> float:
        """Node.Register -> heartbeat TTL. A ready node triggers evals so
        system jobs land on it (node_endpoint.go createNodeEvals on
        node-up)."""
        if not node.id:
            # clients self-assign ids before registering (reference
            # node_endpoint.go Register: "missing node ID"); a
            # server-minted id would be lost across call forwarding,
            # and accepting "" silently collapses every id-less node
            # onto one row
            raise ValueError("node registration requires node.id")
        if not node.computed_class:
            node.compute_class()
        self.store.upsert_node(node)
        if node.ready():
            self._create_node_evals(node.id)
        return self.heartbeats.reset(node.id)

    @_tiered(TIER_LIVENESS, "node_register_batch")
    def register_nodes(self, nodes: List[Node]) -> float:
        """Batched Node.Register: one FSM command upserts the whole
        chunk, one eval pass covers every ready node (the swarm's
        registration path — 100K nodes cannot afford one raft round
        trip each)."""
        for node in nodes:
            if not node.id:
                raise ValueError("node registration requires node.id")
            if not node.computed_class:
                node.compute_class()
        if not nodes:
            return self.config.heartbeat_ttl
        self.store.upsert_nodes(list(nodes))
        ready = [n.id for n in nodes if n.ready()]
        if ready:
            self._create_node_evals_batch(ready)
        for node in nodes:
            self.heartbeats.reset(node.id)
        return self.config.heartbeat_ttl

    @_tiered(TIER_LIVENESS, "heartbeat")
    def heartbeat(self, node_id: str) -> float:
        """Node.UpdateStatus(ready) from a live client. A node that was
        marked down by a missed TTL comes back to ready here (the
        reference heartbeat is literally an UpdateStatus(ready) RPC).
        An UNKNOWN node raises KeyError instead of arming a ghost TTL
        timer for a row that does not exist — the client re-registers."""
        if not self.heartbeats.enabled:
            raise HeartbeatPlaneInactive(
                "heartbeat plane is not active on this server")
        snap = self.store.snapshot()
        node = snap.node_by_id(node_id)
        if node is None:
            raise KeyError(f"node {node_id} is not registered")
        if node.status != enums.NODE_STATUS_READY:
            self.update_node_status(node_id, enums.NODE_STATUS_READY)
            return self.config.heartbeat_ttl
        ttl = self.heartbeats.reset(node_id)
        # re-read AFTER arming: a missed-TTL mark that committed while
        # this call was in flight (first snapshot stale) must not
        # survive an acked heartbeat
        node = self.store.snapshot().node_by_id(node_id)
        if node is not None and node.status != enums.NODE_STATUS_READY:
            self.update_node_status(node_id, enums.NODE_STATUS_READY)
        return ttl

    @_tiered(TIER_LIVENESS, "heartbeat_batch")
    def heartbeat_batch(self, node_ids: List[str]) -> float:
        """Batched heartbeat for swarm-scale clients: ready nodes are a
        leader-local timer re-arm (NO FSM traffic); nodes coming back
        from down/disconnected flip to ready in one batched status
        command; unknown (deregistered mid-flight) ids are dropped. On a
        server whose expiry plane is down (lost leadership, stopping)
        the whole batch is rejected — an acked heartbeat that armed no
        timer is exactly the missed-TTL false positive this plane must
        not produce."""
        if not self.heartbeats.enabled:
            raise HeartbeatPlaneInactive(
                "heartbeat plane is not active on this server")
        snap = self.store.snapshot()
        known: List[str] = []
        stale: List[str] = []
        for node_id in node_ids:
            node = snap.node_by_id(node_id)
            if node is None:
                continue
            known.append(node_id)
            if node.status != enums.NODE_STATUS_READY:
                stale.append(node_id)
            else:
                self.heartbeats.reset(node_id)
        if known:
            # re-read AFTER arming: a missed-TTL mark that committed
            # while this batch was in flight saw none of these timers
            # armed — revive those nodes too, in the same ack
            snap2 = self.store.snapshot()
            seen = set(stale)
            for node_id in known:
                node = snap2.node_by_id(node_id)
                if (node is not None and node_id not in seen
                        and node.status != enums.NODE_STATUS_READY):
                    stale.append(node_id)
        if stale:
            self.store.update_nodes_status(stale, enums.NODE_STATUS_READY,
                                           ts=time.time())
            for node_id in stale:
                self.heartbeats.reset(node_id)
            self._create_node_evals_batch(stale)
        return self.config.heartbeat_ttl

    @_tiered(TIER_LIVENESS, "node_status")
    def update_node_status(self, node_id: str, status: str) -> None:
        self.store.update_node_status(node_id, status, ts=time.time())
        if status in (enums.NODE_STATUS_DOWN, enums.NODE_STATUS_DISCONNECTED):
            self.heartbeats.remove(node_id)
            self._create_node_evals(node_id)
        elif status == enums.NODE_STATUS_READY:
            self.heartbeats.reset(node_id)
            self._create_node_evals(node_id)

    def mark_node_down(self, node_id: str, reason: str = "") -> None:
        """Missed-TTL handler. If any alloc on the node tolerates client
        disconnects (max_client_disconnect), the node goes `disconnected`
        — its allocs turn unknown rather than lost — otherwise `down`
        (reference node_endpoint.go disconnect handling)."""
        self.mark_nodes_down([node_id], reason=reason)

    @_tiered(TIER_LIVENESS, "node_expiry")
    def mark_nodes_down(self, node_ids: List[str], reason: str = "") -> None:
        """Batched missed-TTL handler: one status command per status
        class and one eval pass for the whole expiry batch. A node that
        heartbeated AFTER its expiry was collected (its TTL is armed
        again) is skipped — expiry collection and the mark-down commit
        are not atomic, and marking a just-checked-in node down would be
        exactly the missed-TTL false positive this plane must not
        produce."""
        snap = self.store.snapshot()
        down: List[str] = []
        disconnected: List[str] = []
        for node_id in node_ids:
            if self.heartbeats.armed(node_id):
                continue
            if snap.node_by_id(node_id) is None:
                # node was deleted while its TTL timer was in flight
                self.heartbeats.remove(node_id)
                continue
            status = enums.NODE_STATUS_DOWN
            for alloc in snap.allocs_by_node(node_id):
                if alloc.terminal_status():
                    continue
                job = snap.job_by_id(alloc.job_id, alloc.namespace)
                tg = job.lookup_task_group(alloc.task_group) if job else None
                if tg is not None and tg.max_client_disconnect_s is not None:
                    status = enums.NODE_STATUS_DISCONNECTED
                    break
            if status == enums.NODE_STATUS_DOWN:
                down.append(node_id)
            else:
                disconnected.append(node_id)
        ts = time.time()
        revived: List[str] = []
        for group, status in ((down, enums.NODE_STATUS_DOWN),
                              (disconnected, enums.NODE_STATUS_DISCONNECTED)):
            if not group:
                continue
            self.store.update_nodes_status(group, status, ts=ts)
            for node_id in group:
                # a heartbeat that re-armed the TTL while the mark was
                # committing wins: leave its timer running and flip the
                # node straight back to ready below
                if self.heartbeats.armed(node_id):
                    revived.append(node_id)
                else:
                    self.heartbeats.remove(node_id)
        if revived:
            self.store.update_nodes_status(
                revived, enums.NODE_STATUS_READY, ts=time.time())
        if down or disconnected:
            self._create_node_evals_batch(down + disconnected)

    @_tiered(TIER_LIVENESS, "node_deregister")
    def deregister_node(self, node_id: str) -> None:
        """Node.Deregister: drop the node and reschedule its work."""
        self.heartbeats.remove(node_id)
        self.store.delete_node(node_id)
        self._create_node_evals(node_id)

    def update_node_drain(self, node_id: str, drain_strategy,
                          mark_eligible: bool = False) -> None:
        self.store.update_node_drain(node_id, drain_strategy, mark_eligible)
        self._create_node_evals(node_id)

    def update_node_eligibility(self, node_id: str, eligibility: str) -> None:
        self.store.update_node_eligibility(node_id, eligibility)

    def _create_node_evals(self, node_id: str) -> List[str]:
        """One eval per job with allocs on the node
        (node_endpoint.go:1645 createNodeEvals)."""
        return self._create_node_evals_batch([node_id])

    def _create_node_evals_batch(self, node_ids: List[str]) -> List[str]:
        """createNodeEvals over a whole node batch off ONE snapshot: one
        eval per (job, node) pair, one store write + one broker enqueue
        for the lot (the expiry/registration batches feed this)."""
        snap = self.store.snapshot()
        now = time.time()
        sys_jobs: Optional[List[Job]] = None
        out = []
        evals = []
        for node_id in node_ids:
            node = snap.node_by_id(node_id)
            jobs: Dict[tuple, Job] = {}
            for alloc in snap.allocs_by_node(node_id):
                if alloc.terminal_status():
                    continue
                job = snap.job_by_id(alloc.job_id, alloc.namespace)
                if job is not None:
                    jobs[(alloc.namespace, alloc.job_id)] = job
            # system jobs must also re-evaluate when a node comes up
            if node is not None and node.ready():
                if sys_jobs is None:
                    sys_jobs = [j for j in snap.jobs() if j.type in
                                (enums.JOB_TYPE_SYSTEM,
                                 enums.JOB_TYPE_SYSBATCH)]
                for job in sys_jobs:
                    jobs[(job.namespace, job.id)] = job
            for job in jobs.values():
                ev = Evaluation(
                    id=generate_uuid(),
                    namespace=job.namespace,
                    priority=job.priority,
                    type=job.type,
                    triggered_by=enums.TRIGGER_NODE_UPDATE,
                    job_id=job.id,
                    node_id=node_id,
                    status=enums.EVAL_STATUS_PENDING,
                    create_time=now,
                )
                evals.append(ev)
                out.append(ev.id)
        if evals:
            self.store.upsert_evals(evals)
            self.broker.enqueue_all(evals)
        return out

    @_tiered(TIER_COMMIT, "alloc_stop")
    def stop_alloc(self, alloc_id: str) -> str:
        """Alloc.Stop (reference nomad/alloc_endpoint.go Stop): mark the
        alloc for reschedule and evaluate — it stops in place and a
        replacement lands elsewhere. Returns the eval id."""
        from ..structs.alloc import DesiredTransition

        snap = self.store.snapshot()
        alloc = snap.alloc_by_id(alloc_id)
        if alloc is None:
            raise KeyError(f"alloc {alloc_id} not found")
        if alloc.terminal_status():
            raise ValueError(f"alloc {alloc_id} is already terminal")
        job = snap.job_by_id(alloc.job_id, alloc.namespace)
        ev = Evaluation(
            id=generate_uuid(),
            namespace=alloc.namespace,
            priority=job.priority if job else 50,
            type=job.type if job else enums.JOB_TYPE_SERVICE,
            triggered_by=enums.TRIGGER_ALLOC_STOP,
            job_id=alloc.job_id,
            status=enums.EVAL_STATUS_PENDING,
        )
        index = self.store.update_alloc_desired_transitions(
            {alloc_id: DesiredTransition(reschedule=True)}, evals=[ev])
        ev.modify_index = index
        self.broker.enqueue(ev)
        return ev.id

    @_tiered(TIER_COMMIT, "alloc_update")
    def update_allocs_from_client(self, updates: List) -> None:
        """Node.UpdateAlloc: batched client -> server alloc status sync;
        failed allocs trigger reschedule evals (node_endpoint.go
        UpdateAlloc -> createRescheduleEvals)."""
        if not updates:
            return
        if self.client_updates.running:
            # coalesce with every other client's in-flight sync round
            self.client_updates.submit(updates)
        else:
            self.store.update_allocs_from_client(updates)
        snap = self.store.snapshot()
        seen = set()
        evals = []
        for upd in updates:
            if upd.client_status not in (enums.ALLOC_CLIENT_FAILED,):
                continue
            key = (upd.namespace, upd.job_id)
            if key in seen:
                continue
            seen.add(key)
            job = snap.job_by_id(upd.job_id, upd.namespace)
            if job is None:
                continue
            evals.append(Evaluation(
                id=generate_uuid(),
                namespace=job.namespace,
                priority=job.priority,
                type=job.type,
                triggered_by=enums.TRIGGER_RETRY_FAILED_ALLOC,
                job_id=job.id,
                status=enums.EVAL_STATUS_PENDING,
                create_time=time.time(),
            ))
        if evals:
            self.store.upsert_evals(evals)
            self.broker.enqueue_all(evals)

    # -- Deployment endpoints (nomad/deployment_endpoint.go) --

    def promote_deployment(self, dep_id: str, groups: Optional[List[str]] = None) -> str:
        """Deployment.Promote: requires every (selected) canary group to
        have >= desired healthy canaries; flips promoted so the next eval
        rolls the remaining old-version allocs
        (reference deployment_endpoint.go Promote +
        deploymentwatcher PromoteDeployment)."""
        import copy as _copy

        from .deployments import alloc_healthy

        snap = self.store.snapshot()
        dep = snap.deployment_by_id(dep_id)
        if dep is None:
            raise KeyError(f"deployment {dep_id} not found")
        if not dep.active():
            raise ValueError(f"deployment {dep_id} is {dep.status}, not promotable")
        if not dep.requires_promotion():
            raise ValueError(f"deployment {dep_id} has no canaries awaiting promotion")
        job = snap.job_by_id(dep.job_id, dep.namespace)
        if job is None:
            raise ValueError(f"job {dep.job_id} not found")
        allocs = [a for a in snap.allocs_by_job(dep.job_id, dep.namespace)
                  if a.deployment_id == dep.id]
        now = time.time()
        upd = _copy.deepcopy(dep)
        for name, state in upd.task_groups.items():
            if state.desired_canaries <= 0 or state.promoted:
                continue
            if groups is not None and name not in groups:
                continue
            healthy = sum(1 for a in allocs
                          if a.task_group == name and a.canary
                          and alloc_healthy(a, job, now))
            if healthy < state.desired_canaries:
                raise ValueError(
                    f"group {name!r} has {healthy}/{state.desired_canaries} "
                    "healthy canaries; promotion refused")
            state.promoted = True
        upd.status_description = "Deployment is promoted"
        self.store.upsert_deployment(upd)
        ev = Evaluation(
            id=generate_uuid(),
            namespace=job.namespace,
            priority=dep.eval_priority,
            type=job.type,
            triggered_by=enums.TRIGGER_DEPLOYMENT_WATCHER,
            job_id=job.id,
            deployment_id=dep.id,
            status=enums.EVAL_STATUS_PENDING,
            create_time=time.time(),
        )
        return self.create_eval(ev)

    def fail_deployment(self, dep_id: str) -> None:
        """Deployment.Fail: operator-forced failure (auto-revert still
        applies via the watcher's failed handling)."""
        import copy as _copy

        snap = self.store.snapshot()
        dep = snap.deployment_by_id(dep_id)
        if dep is None:
            raise KeyError(f"deployment {dep_id} not found")
        if not dep.active():
            raise ValueError(f"deployment {dep_id} is already {dep.status}")
        upd = _copy.copy(dep)
        upd.status = enums.DEPLOYMENT_STATUS_FAILED
        upd.status_description = "Deployment marked as failed by operator"
        self.store.upsert_deployment(upd)

    # -- Eval endpoints --

    @_tiered(TIER_SUBMIT, "job_scale")
    def scale_job(self, job_id: str, task_group: str, count: int,
                  namespace: str = "default") -> str:
        """Job.Scale (reference job_endpoint.go Scale): registers a new
        version with the group count changed — a count-only change, so
        the scheduler applies it without touching running allocs beyond
        the count math."""
        snap = self.store.snapshot()
        job = snap.job_by_id(job_id, namespace)
        if job is None or job.stopped():
            raise KeyError(f"job {job_id} not found")
        if job.is_periodic or job.is_parameterized:
            raise ValueError("cannot scale periodic or parameterized jobs")
        tg = job.lookup_task_group(task_group)
        if tg is None:
            raise ValueError(f"task group {task_group!r} not found")
        if count < 0:
            raise ValueError("count must be >= 0")
        if tg.scaling is not None and tg.scaling.enabled:
            # scaling stanza bounds gate every scale (reference
            # Job.Scale validates against the policy's min/max)
            if count < tg.scaling.min or (tg.scaling.max
                                          and count > tg.scaling.max):
                raise ValueError(
                    f"count {count} outside scaling bounds "
                    f"[{tg.scaling.min}, "
                    f"{tg.scaling.max or 'unbounded'}]")
        updated = _copy.deepcopy(job)
        updated.lookup_task_group(task_group).count = count
        eval_id = self.register_job(updated)
        # scaling events ride the job row (reference scaling_event
        # table; GET /v1/job/<id>/scale serves them)
        self.store.append_scaling_event(job_id, namespace, {
            "task_group": task_group, "count": count,
            "previous_count": tg.count, "eval_id": eval_id,
            "time": time.time()})
        return eval_id

    def scaling_policies(self, namespace=None):
        """Every enabled scaling stanza as a policy row (reference
        /v1/scaling/policies; policies live on the job spec, so the
        listing is derived from the jobs table)."""
        out = []
        for job in self.store.snapshot().jobs():
            if namespace is not None and job.namespace != namespace:
                continue
            if job.stopped():
                continue
            for tg in job.task_groups:
                if tg.scaling is None:
                    continue
                out.append({
                    "id": f"{job.namespace}/{job.id}/{tg.name}",
                    "namespace": job.namespace,
                    "target": {"job": job.id, "group": tg.name},
                    "min": tg.scaling.min, "max": tg.scaling.max,
                    "enabled": tg.scaling.enabled,
                    "policy": tg.scaling.policy,
                })
        return out

    def revert_job(self, job_id: str, job_version: int,
                   namespace: str = "default") -> str:
        """Job.Revert (reference job_endpoint.go Revert): re-register a
        prior version's spec as the newest version."""
        snap = self.store.snapshot()
        current = snap.job_by_id(job_id, namespace)
        if current is None:
            raise KeyError(f"job {job_id} not found")
        if current.is_periodic or current.is_parameterized:
            raise ValueError("cannot revert periodic or parameterized jobs")
        if job_version == current.version:
            raise ValueError("cannot revert to the current version")
        old = snap.job_version(job_id, job_version, namespace)
        if old is None:
            raise KeyError(f"job {job_id} has no version {job_version}")
        revived = _copy.deepcopy(old)
        revived.stop = False
        return self.register_job(revived)

    def plan_job(self, job: Job) -> Dict:
        """Dry-run scheduling of a job update (reference Job.Plan,
        nomad/job_endpoint.go + scheduler/annotate.go): run the real
        scheduler against the current snapshot with a planner that
        commits nothing, and report per-TG desired-update annotations, a
        spec diff against the running version, and failed placements."""
        import copy as _c

        from ..structs.job import spec_diff

        if not self.workers:
            # a server built without scheduler workers resolved no
            # backend (cli.Agent); running a scheduler here would open
            # the device behind the back of the server that holds it
            raise RuntimeError(
                "this server runs no scheduler (--workers 0); ask a "
                "server that does for the dry run")
        snap = self.store.snapshot()
        prev = snap.job_by_id(job.id, job.namespace)
        planned = _c.copy(job)
        planned.version = (prev.version + 1) if prev is not None else 0
        planned.create_index = prev.create_index if prev is not None else 0

        class _PlanSnapshot:
            """The store snapshot with the planned job overlaid."""

            def __init__(self, base):
                self._base = base

            def job_by_id(self, job_id, namespace="default"):
                if job_id == planned.id and namespace == planned.namespace:
                    return planned
                return self._base.job_by_id(job_id, namespace)

            def __getattr__(self, name):
                return getattr(self._base, name)

        class _DryRunPlanner:
            """Planner that records the plan and commits nothing
            (the annotate-mode Harness, reference scheduler/testing.go)."""

            def __init__(self):
                self.plans = []
                self.evals = []

            def submit_plan(self, plan):
                from ..structs.plan import PlanResult

                self.plans.append(plan)
                result = PlanResult(
                    node_allocation=plan.node_allocation,
                    node_update=plan.node_update,
                    node_preemptions=plan.node_preemptions,
                    alloc_index=snap.index)
                # nothing commits in a dry run: the planner contract
                # still requires post-apply hooks to fire, with every
                # planned node marked rejected so a bulk solve's
                # solver-service ledger entry is corrected out of the
                # usage carry instead of lingering until its TTL
                rejected = set(plan.node_allocation)
                for b in plan.alloc_blocks:
                    rejected.update(b.node_ids)
                result.rejected_nodes = sorted(rejected)
                for hook in plan.post_apply_hooks:
                    try:
                        hook(result)
                    except Exception:
                        pass
                return result, None

            def update_eval(self, ev):
                self.evals.append(ev)

            def create_eval(self, ev):
                self.evals.append(ev)

            def reblock_eval(self, ev):
                self.evals.append(ev)

        planner = _DryRunPlanner()
        from ..scheduler.scheduler import NewScheduler

        sched = NewScheduler(
            planned.type, _PlanSnapshot(snap), planner,
            sched_config=self.sched_config, logger=self.logger)
        ev = Evaluation(
            id=generate_uuid(), namespace=planned.namespace,
            priority=planned.priority, type=planned.type,
            triggered_by=enums.TRIGGER_JOB_REGISTER, job_id=planned.id,
            status=enums.EVAL_STATUS_PENDING)
        sched.process(ev)
        return {
            "job_id": planned.id,
            "job_version": planned.version,
            "annotations": getattr(sched, "annotations", {}),
            "diff": spec_diff(prev, planned),
            "failed_tg_allocs": {
                name: {"nodes_filtered": m.nodes_filtered,
                       "nodes_exhausted": m.nodes_exhausted,
                       "coalesced_failures": m.coalesced_failures}
                for name, m in sched.failed_tg_allocs.items()},
        }

    # -- Namespace endpoints (reference nomad/namespace_endpoint.go) --

    def upsert_namespace(self, ns) -> None:
        if not ns.name:
            raise ValueError("namespace name is required")
        self.store.upsert_namespace(ns)

    def delete_namespace(self, name: str) -> None:
        self.store.delete_namespace(name)

    # -- Service registration endpoints (reference
    #    nomad/service_registration_endpoint.go) --

    def upsert_service_registrations(self, regs) -> None:
        for reg in regs:
            if not reg.service_name or not reg.id:
                raise ValueError("service registrations require id and name")
        self.store.upsert_service_registrations(regs)

    def delete_service_registrations(self, ids) -> None:
        self.store.delete_service_registrations(list(ids))

    def delete_services_by_alloc(self, alloc_id: str) -> None:
        self.store.delete_services_by_alloc(alloc_id)

    def force_gc(self) -> Dict:
        """`nomad system gc` (reference CoreJobForceGC); forwardable so
        followers route it to the leader."""
        return self.core_gc.force_gc(threshold_override=0)

    def _check_namespace(self, namespace: str) -> None:
        """Registrations into unregistered namespaces are rejected
        (reference Job.Register namespace validation)."""
        if self.store.snapshot().namespace(namespace) is None:
            raise ValueError(f"namespace {namespace!r} does not exist")

    # -- Node-pool endpoints (reference nomad/node_pool_endpoint.go) --

    def upsert_node_pool(self, pool) -> None:
        from ..structs.operator import BUILTIN_NODE_POOLS

        if pool.name in BUILTIN_NODE_POOLS:
            raise ValueError(f"cannot modify built-in node pool {pool.name!r}")
        if not pool.name:
            raise ValueError("node pool name is required")
        self.store.upsert_node_pool(pool)

    def delete_node_pool(self, name: str) -> None:
        self.store.delete_node_pool(name)

    # -- Volume endpoints (reference nomad/csi_endpoint.go register/deregister) --

    def register_volume(self, vol) -> None:
        self._check_namespace(vol.namespace)
        self.store.upsert_volume(vol)

    def deregister_volume(self, vol_id: str, namespace: str = "default",
                          force: bool = False) -> None:
        self.store.delete_volume(vol_id, namespace, force=force)

    @_tiered(TIER_SUBMIT, "eval_create")
    def create_eval(self, ev: Evaluation) -> str:
        self.store.upsert_evals([ev])
        if ev.should_enqueue():
            self.broker.enqueue(ev)
        return ev.id

    # -- ACL endpoints (nomad/acl_endpoint.go) --

    def acl_bootstrap(self):
        """One-time bootstrap: mint the initial management token."""
        from ..acl.tokens import TOKEN_TYPE_MANAGEMENT, AclToken

        snap = self.store.snapshot()
        if any(True for _ in snap.acl_tokens()):
            raise PermissionError("ACL already bootstrapped")
        token = AclToken.new("Bootstrap Token", TOKEN_TYPE_MANAGEMENT)
        token.create_time = time.time()
        self.store.upsert_acl_token(token)
        return token

    def upsert_acl_policy(self, name: str, rules, description: str = ""):
        from ..acl.policy import AclPolicy, parse_policy

        if not isinstance(rules, str):
            import json as _json

            rules = _json.dumps(rules)
        parse_policy(rules)  # validate before storing
        policy = AclPolicy(name=name, description=description, rules=rules)
        self.store.upsert_acl_policy(policy)
        return policy

    def create_acl_token(self, name: str, policies, token_type: str = "client",
                         roles=()):
        from ..acl.tokens import AclToken

        snap = self.store.snapshot()
        for p in policies:
            if snap.acl_policy(p) is None:
                raise ValueError(f"unknown policy {p!r}")
        for r in roles:
            if snap.acl_role(r) is None:
                raise ValueError(f"unknown role {r!r}")
        token = AclToken.new(name, token_type, policies, roles)
        token.create_time = time.time()
        self.store.upsert_acl_token(token)
        return token

    def upsert_acl_role(self, name: str, policies, description: str = ""):
        """ACL.UpsertRoles (reference nomad/acl_endpoint.go): a role
        bundles policies; tokens referencing it re-scope live."""
        from ..acl.tokens import AclRole

        snap = self.store.snapshot()
        for p in policies:
            if snap.acl_policy(p) is None:
                raise ValueError(f"unknown policy {p!r}")
        role = AclRole(name=name, policies=list(policies),
                       description=description)
        self.store.upsert_acl_role(role)
        return role

    def delete_acl_role(self, name: str) -> None:
        self.store.delete_acl_role(name)

    # -- ACL auth methods / SSO login (reference nomad/acl_endpoint.go
    #    Login, acl/ auth-method structs) --

    # -- regions (reference operator regions + serf WAN membership) --

    def upsert_region(self, region) -> None:
        from ..structs.operator import Region

        if isinstance(region, dict):
            region = Region(**region)
        if not region.name or not region.address:
            raise ValueError("region name and address are required")
        if not region.address.startswith(("http://", "https://")):
            raise ValueError("region address must be an http(s):// URL")
        self.store.upsert_region(region)

    def delete_region(self, name: str) -> None:
        self.store.delete_region(name)

    def region_address(self, name: str):
        r = self.store.snapshot().region(name)
        return r.address if r is not None else None

    def upsert_auth_method(self, method) -> None:
        from ..acl.auth import AUTH_TYPE_JWT, AUTH_TYPE_OIDC, AuthMethod

        if isinstance(method, dict):
            method = AuthMethod(**method)
        if not method.name:
            raise ValueError("auth method name is required")
        if method.type not in (AUTH_TYPE_JWT, AUTH_TYPE_OIDC):
            raise ValueError(f"unsupported auth method type {method.type!r}")
        if method.max_token_ttl_s < 0:
            raise ValueError("max_token_ttl_s must be >= 0")
        self.store.upsert_auth_method(method)

    def delete_auth_method(self, name: str) -> None:
        self.store.delete_auth_method(name)

    def upsert_binding_rule(self, rule) -> object:
        from ..acl.auth import (BIND_MANAGEMENT, BIND_POLICY, BIND_ROLE,
                                BindingRule)

        if isinstance(rule, dict):
            rule = BindingRule(**rule)
        if not rule.id:
            rule.id = generate_uuid()
        if self.store.snapshot().auth_method(rule.auth_method) is None:
            raise ValueError(f"unknown auth method {rule.auth_method!r}")
        if rule.bind_type not in (BIND_ROLE, BIND_POLICY, BIND_MANAGEMENT):
            raise ValueError(f"unknown bind_type {rule.bind_type!r}")
        if rule.bind_type != BIND_MANAGEMENT and not rule.bind_name:
            raise ValueError("bind_name is required")
        self.store.upsert_binding_rule(rule)
        return rule

    def delete_binding_rule(self, rule_id: str) -> None:
        self.store.delete_binding_rule(rule_id)

    def acl_login(self, auth_method: str, login_token: str):
        """Exchange an external JWT for an ephemeral ACL token
        (reference acl_endpoint.go Login)."""
        from ..acl import auth as a

        snap = self.store.snapshot()
        method = snap.auth_method(auth_method)
        if method is None:
            raise PermissionError(f"unknown auth method {auth_method!r}")
        claims = a.verify_jwt(login_token, method)
        return self._login_with_claims(snap, method, claims)

    def _login_with_claims(self, snap, method, claims: dict):
        """Shared bind-and-mint tail of the JWT and OIDC logins."""
        from ..acl import auth as a
        from ..acl.tokens import TOKEN_TYPE_MANAGEMENT, AclToken

        variables = a.map_claims(claims, method)
        rules = list(snap.binding_rules(method.name))
        management, roles, policies = a.evaluate_binding_rules(rules,
                                                               variables)
        if not management and not roles and not policies:
            raise PermissionError("no binding rules matched this identity")
        # bound names that don't exist simply don't grant (reference:
        # dangling bindings resolve to nothing at authorization time),
        # but a login that would grant nothing at all is refused
        roles = [r for r in roles if snap.acl_role(r) is not None]
        policies = [p for p in policies if snap.acl_policy(p) is not None]
        if not management and not roles and not policies:
            raise PermissionError("binding rules matched but none of the "
                                  "bound roles/policies exist")
        token = AclToken.new(
            f"{method.name} login ({variables.get('name', claims.get('sub', ''))})",
            TOKEN_TYPE_MANAGEMENT if management else "client",
            policies, roles)
        token.create_time = time.time()
        if method.max_token_ttl_s > 0:
            token.expiration_time = token.create_time + method.max_token_ttl_s
        self.store.upsert_acl_token(token)
        return token

    # -- OIDC login flow (reference acl_endpoint.go OIDCAuthURL /
    #    OIDCCompleteAuth; command/login.go drives the browser side) --

    OIDC_REQUEST_TTL = 600.0

    @staticmethod
    def _redirect_allowed(redirect_uri: str, allowed) -> bool:
        """An EMPTY allowlist denies everything (an unauthenticated
        auth-url endpoint with allow-any redirects is an authorization-
        code theft primitive — the reference requires registered
        redirect URIs too). Entries may use a `:*` port wildcard so the
        CLI's ephemeral-port loopback callback can be registered as
        e.g. "http://127.0.0.1:*/oidc/callback"."""
        if not redirect_uri or not allowed:
            return False
        for entry in allowed:
            if entry == redirect_uri:
                return True
            if ":*/" in entry:
                prefix, _, suffix = entry.partition(":*/")
                if (redirect_uri.startswith(prefix + ":")
                        and redirect_uri.endswith("/" + suffix)):
                    port = redirect_uri[len(prefix) + 1:
                                        -len(suffix) - 1]
                    if port.isdigit():
                        return True
        return False

    def oidc_auth_url(self, auth_method: str, redirect_uri: str,
                      client_nonce: str = "") -> dict:
        """Build the provider authorization URL for an OIDC auth method
        and remember the request state (leader-local, like the
        reference's oidcRequestCache)."""
        from ..acl.auth import AUTH_TYPE_OIDC
        from ..utils import generate_secret_uuid

        snap = self.store.snapshot()
        method = snap.auth_method(auth_method)
        if method is None or method.type != AUTH_TYPE_OIDC:
            raise PermissionError(f"unknown OIDC auth method {auth_method!r}")
        allowed = method.config.get("allowed_redirect_uris") or []
        if not self._redirect_allowed(redirect_uri, allowed):
            raise PermissionError(
                f"redirect_uri {redirect_uri!r} is not allowed")
        auth_ep = method.config.get("oidc_auth_endpoint", "")
        if not auth_ep:
            raise ValueError(
                f"auth method {auth_method!r} has no oidc_auth_endpoint")
        state = generate_secret_uuid()
        now = time.time()
        with self._oidc_lock:
            # opportunistic expiry sweep
            self._oidc_requests = {
                s: r for s, r in self._oidc_requests.items()
                if r["expires"] > now}
            self._oidc_requests[state] = {
                "method": auth_method, "redirect_uri": redirect_uri,
                "nonce": client_nonce, "expires": now + self.OIDC_REQUEST_TTL}
        from urllib.parse import urlencode

        q = urlencode({
            "response_type": "code",
            "client_id": method.config.get("oidc_client_id", ""),
            "redirect_uri": redirect_uri,
            "scope": " ".join(method.config.get("oidc_scopes")
                              or ["openid"]),
            "state": state,
            "nonce": client_nonce,
        })
        sep = "&" if "?" in auth_ep else "?"
        return {"auth_url": f"{auth_ep}{sep}{q}", "state": state}

    def oidc_complete_auth(self, auth_method: str, state: str, code: str,
                           redirect_uri: str, client_nonce: str = ""):
        """Exchange the provider's authorization code for an id_token at
        the token endpoint, validate it, and mint the bound ACL token."""
        import json as _json
        import urllib.request
        from urllib.parse import urlencode

        from ..acl import auth as a

        now = time.time()
        with self._oidc_lock:
            req = self._oidc_requests.pop(state, None)
        if req is None or req["expires"] <= now \
                or req["method"] != auth_method \
                or req["redirect_uri"] != redirect_uri \
                or req["nonce"] != client_nonce:
            raise PermissionError("unknown or expired OIDC request state")
        snap = self.store.snapshot()
        method = snap.auth_method(auth_method)
        if method is None:
            raise PermissionError(f"unknown auth method {auth_method!r}")
        token_ep = method.config.get("oidc_token_endpoint", "")
        if not token_ep:
            raise ValueError(
                f"auth method {auth_method!r} has no oidc_token_endpoint")
        body = urlencode({
            "grant_type": "authorization_code",
            "code": code,
            "redirect_uri": redirect_uri,
            "client_id": method.config.get("oidc_client_id", ""),
            "client_secret": method.config.get("oidc_client_secret", ""),
        }).encode()
        try:
            with urllib.request.urlopen(urllib.request.Request(
                    token_ep, data=body, headers={
                        "Content-Type": "application/x-www-form-urlencoded"}),
                    timeout=15.0) as resp:
                out = _json.loads(resp.read())
        except Exception as e:
            raise PermissionError(f"OIDC code exchange failed: {e}") from e
        id_token = out.get("id_token", "")
        if not id_token:
            raise PermissionError("provider returned no id_token")
        claims = a.verify_jwt(id_token, method)
        if client_nonce and claims.get("nonce") != client_nonce:
            # strict echo check: a bound nonce MUST come back verbatim.
            # Accepting a missing/empty nonce claim would let an
            # attacker-supplied id_token minted outside this auth
            # request (no nonce at all) complete the login — the
            # classic OIDC code/token-injection vector
            raise PermissionError("id_token nonce mismatch")
        return self._login_with_claims(snap, method, claims)

    # -- workload identities (reference nomad/structs WorkloadIdentity +
    #    plan-time SignClaims; renewed via client/widmgr) --

    def sign_workload_identity(self, alloc_id: str, task: str) -> dict:
        """Mint (or renew) a task's workload-identity JWT. The client's
        WIDMgr calls this before expiry for long-running tasks
        (reference client/widmgr/widmgr.go renewal loop)."""
        snap = self.store.snapshot()
        alloc = snap.alloc_by_id(alloc_id)
        if alloc is None:
            raise KeyError(f"alloc {alloc_id} not found")
        if alloc.terminal_status():
            raise PermissionError(
                f"alloc {alloc_id} is terminal; no identity")
        now = time.time()
        ttl = self.config.identity_ttl
        claims = {
            "sub": f"{alloc.namespace}:{alloc.job_id}:{alloc.task_group}"
                   f":{alloc_id}:{task}",
            "alloc_id": alloc_id,
            "job_id": alloc.job_id,
            "namespace": alloc.namespace,
            "task": task,
            "iat": now,
            "exp": now + ttl,
        }
        return {"token": self.encrypter.sign_identity(claims),
                "exp": claims["exp"]}

    # one-time tokens (reference acl_endpoint.go UpsertOneTimeToken /
    # ExchangeOneTimeToken; how `nomad ui -authenticate` hands a browser
    # a short-lived single-use credential instead of the real secret)

    ONE_TIME_TOKEN_TTL = 600.0

    def create_one_time_token(self, secret_id: str) -> dict:
        """Mint a single-use, short-TTL stand-in for the caller's token."""
        from ..utils import generate_secret_uuid

        snap = self.store.snapshot()
        token = snap.acl_token_by_secret(secret_id)
        if token is None:
            raise PermissionError("token not found")
        if token.expiration_time and time.time() >= token.expiration_time:
            raise PermissionError("token expired")
        ott = generate_secret_uuid()
        expires = time.time() + self.ONE_TIME_TOKEN_TTL
        self.store.upsert_one_time_token(
            {"secret": ott, "accessor_id": token.accessor_id,
             "expires": expires})
        return {"one_time_secret": ott, "expires": expires}

    def exchange_one_time_token(self, one_time_secret: str):
        """Burn the one-time token, return the underlying ACL token.
        The burn is atomic in the store (take_one_time_token) so two
        concurrent exchanges can never both win."""
        row = self.store.take_one_time_token(one_time_secret)
        if row is None:
            raise PermissionError("one-time token invalid or expired")
        token = self.store.snapshot().acl_token_by_accessor(
            row["accessor_id"])
        if token is None:
            raise PermissionError("underlying token no longer exists")
        return token

    def resolve_token(self, secret_id: str):
        """secret -> compiled ACL (reference nomad/auth/auth.go)."""
        from ..acl.policy import ACL, compile_acl

        if not secret_id:
            return None
        snap = self.store.snapshot()
        token = snap.acl_token_by_secret(secret_id)
        if token is None:
            raise PermissionError("token not found")
        if token.expiration_time and time.time() >= token.expiration_time:
            raise PermissionError("token expired")
        if token.is_management:
            return ACL(management=True)
        names = list(token.policies)
        for role_name in getattr(token, "roles", ()):
            role = snap.acl_role(role_name)
            if role is not None:
                names.extend(role.policies)
        policies = [snap.acl_policy(p) for p in dict.fromkeys(names)]
        return compile_acl([p for p in policies if p is not None])

    # -- variables endpoints (nomad/variables_endpoint.go) --

    def put_variable(self, path: str, items: Dict[str, str],
                     namespace: str = "default") -> None:
        import json as _json

        from ..structs.variables import Variable

        self._check_namespace(namespace)
        blob = self.encrypter.encrypt(_json.dumps(items).encode())
        self.store.upsert_variable(Variable(namespace=namespace, path=path,
                                            encrypted=blob))

    def get_variable(self, path: str, namespace: str = "default"):
        import json as _json

        var = self.store.snapshot().variable(path, namespace)
        if var is None:
            return None
        return _json.loads(self.encrypter.decrypt(var.encrypted))

    def list_variables(self, namespace: str = "default", prefix: str = ""):
        return [v.path for v in
                self.store.snapshot().variables(namespace, prefix)]

    def delete_variable(self, path: str, namespace: str = "default") -> None:
        self.store.delete_variable(path, namespace)

    # -- test/ops helpers --

    def wait_for_idle(self, timeout: float = 10.0,
                      include_delayed: bool = True) -> bool:
        """Block until no evals are ready, in flight, or (by default)
        parked in the delay heap (tests/ops)."""
        deadline = time.time() + timeout
        while time.time() < deadline:
            if (self.broker.ready_count() == 0
                    and self.broker.inflight() == 0
                    and self.broker.pending_count() == 0
                    and (not include_delayed or self.broker.delayed_count() == 0)
                    and self.plan_queue.depth() == 0):
                return True
            time.sleep(0.01)
        return False
