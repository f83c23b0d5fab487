"""nomadload: the overload-control & graceful-degradation plane
(ROBUSTNESS.md "Overload envelope").

PR 11's expiry rate-limiter proved the philosophy in one place — "a
partitioned rack is a trickle, not a storm" — this module generalizes
it system-wide. Three mechanisms, one module:

1. **Deadline propagation.** Every RPC/HTTP request carries an absolute
   deadline (derived from the client timeout), bound thread-locally at
   ingress and forwarded in the wire frame across `_forward` hops. Any
   stage that picks up work whose deadline already passed drops it with
   a `nomad.load.expired_drops` metric instead of burning an fsync or a
   scheduler pass on a reply nobody is waiting for.

2. **Priority-tiered admission.** A per-server ``AdmissionController``
   with per-tier token buckets and queue-depth watermarks, consulted at
   the HTTP ingress, ``RaftNode.apply`` enqueue, ``EvalBroker.enqueue``
   and ``WatchTable`` park. Watermarks read the LIVE queue depths
   (proposal queue, plan queue, broker pending, parked waiters) — the
   same numbers already exported as gauges. When a watermark trips, the
   lowest-value tier sheds first and the controller answers with a
   structured ``RetryLater(after=...)`` (HTTP 429 + Retry-After):

   ========  ======================================================
   tier 0    heartbeats / liveness RPCs — never shed while alive
   tier 1    plan commits + client alloc updates
   tier 2    job submits / eval enqueues
   tier 3    reads / watch registrations
   ========  ======================================================

3. **Brownout with hysteresis.** Sustained tier-1 pressure (a hard
   watermark held for ``brownout_after`` seconds) enters a degraded
   mode that sheds tier 2 and watch parks outright, coalesces watch
   wakeups, and downgrades plain reads to stale-local answers with a
   truthful ``X-Nomad-Consistency-Degraded`` header (refusing every
   read would be an outage, not degradation); it exits only after the
   queues stay calm for ``brownout_exit`` seconds (no flapping at the
   watermark edge). Client-side, ``utils/backoff.py``'s ``RetryBudget``
   keeps retries <= ~10% of requests so a rejection storm never
   amplifies itself.

The controller keeps a bounded admit/shed ledger per server so chaos
invariant 10 (tier ordering: no tier-0 request ever shed while any
tier-2 request is admitted) is checkable after the fact on every
replica.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional, Tuple

from ..obs import TRACER
from .metrics import REGISTRY

# -- tiers -----------------------------------------------------------

TIER_LIVENESS = 0   # heartbeats, node liveness, raft control traffic
TIER_COMMIT = 1     # plan commits, client alloc updates
TIER_SUBMIT = 2     # job submits, eval enqueues
TIER_READ = 3       # reads, watch registrations
TIER_NONE = 4       # sentinel: "no tier is shed"

TIER_NAMES = {TIER_LIVENESS: "liveness", TIER_COMMIT: "commit",
              TIER_SUBMIT: "submit", TIER_READ: "read"}


class RetryLater(Exception):
    """Structured admission rejection: the caller should back off for
    ``after`` seconds (HTTP maps this to 429 + Retry-After). Carries
    the shed tier so clients and tests can attribute the rejection.

    Rehydratable from its own str() so it survives the typed-error
    wire hop in ``ReplicatedServer._WIRE_ERRORS``.
    """

    def __init__(self, tier: int = TIER_SUBMIT, after: float = 0.5,
                 reason: str = ""):
        if isinstance(tier, str):
            # rehydrated from the wire as RetryLater(message): recover
            # the structured fields from the canonical message format
            msg = tier
            tier, after, reason = _parse_retry_later(msg)
            super().__init__(msg)
        else:
            super().__init__(
                f"overloaded: tier-{tier} ({TIER_NAMES.get(tier, '?')}) "
                f"shed, retry after {after:.3f}s"
                + (f" [{reason}]" if reason else ""))
        self.tier = int(tier)
        self.after = float(after)
        self.reason = reason


def _parse_retry_later(msg: str) -> Tuple[int, float, str]:
    tier, after, reason = TIER_SUBMIT, 0.5, ""
    try:
        if "tier-" in msg:
            tier = int(msg.split("tier-", 1)[1][:1])
        if "retry after " in msg:
            after = float(msg.split("retry after ", 1)[1].split("s", 1)[0])
        if "[" in msg and msg.rstrip().endswith("]"):
            reason = msg.rsplit("[", 1)[1].rstrip().rstrip("]")
    except (ValueError, IndexError):
        pass
    return tier, after, reason


# -- thread-local request context (deadline + tier) ------------------
#
# Bound at ingress (HTTP handler, transport dispatch), consulted by
# every downstream stage on the same thread. Stages that cross threads
# (proposal queue, plan queue) copy the values onto the work item at
# the boundary.

_TLS = threading.local()


class _Bind:
    __slots__ = ("_attr", "_prev")

    def __init__(self, attr: str, value):
        self._attr = attr
        self._prev = getattr(_TLS, attr, None)
        setattr(_TLS, attr, value)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        setattr(_TLS, self._attr, self._prev)


def bind_deadline(deadline: Optional[float]) -> _Bind:
    """Bind an ABSOLUTE deadline (time.time() base) on this thread for
    the duration of the with-block. None binds 'no deadline'."""
    return _Bind("deadline", deadline)


def bind_tier(tier: int) -> _Bind:
    """Bind the admission tier of the request being served."""
    return _Bind("tier", tier)


def current_deadline() -> Optional[float]:
    return getattr(_TLS, "deadline", None)


def current_tier(default: int = TIER_COMMIT) -> int:
    """Tier bound on this thread; internal (unbound) work defaults to
    tier 1 — control loops are few and must not be shed casually."""
    t = getattr(_TLS, "tier", None)
    return default if t is None else t


def remaining(default: Optional[float] = None) -> Optional[float]:
    """Seconds left until the bound deadline (may be negative), or
    ``default`` when no deadline is bound."""
    dl = current_deadline()
    if dl is None:
        return default
    return dl - time.time()


def deadline_expired() -> bool:
    dl = current_deadline()
    return dl is not None and time.time() >= dl


def drop_if_expired(stage: str) -> bool:
    """The deadline-propagation drop point: True (and counts the drop)
    when the bound deadline has passed — the caller should abandon the
    work instead of burning capacity on a reply nobody awaits."""
    if not deadline_expired():
        return False
    REGISTRY.incr("nomad.load.expired_drops")
    REGISTRY.incr(f"nomad.load.expired_drops.{stage}")
    return True


def check_expired(prop_deadline: Optional[float], stage: str,
                  now: Optional[float] = None) -> bool:
    """Same drop point for work items carrying an explicit deadline
    (proposals, pending plans) picked up on another thread."""
    if prop_deadline is None:
        return False
    if (now if now is not None else time.time()) < prop_deadline:
        return False
    REGISTRY.incr("nomad.load.expired_drops")
    REGISTRY.incr(f"nomad.load.expired_drops.{stage}")
    return True


# -- admission controller --------------------------------------------

class _Bucket:
    """Token bucket (the HeartbeatManager._take_tokens idiom, made a
    class): refills at ``rate``/s up to ``burst``."""

    __slots__ = ("rate", "burst", "tokens", "stamp")

    def __init__(self, rate: float, burst: float, now: float):
        self.rate = rate
        self.burst = burst
        self.tokens = burst
        self.stamp = now

    def take(self, want: float, now: float) -> float:
        """0.0 on success, else seconds until ``want`` tokens exist."""
        self.tokens = min(self.burst,
                          self.tokens + (now - self.stamp) * self.rate)
        self.stamp = now
        if self.tokens >= want:
            self.tokens -= want
            return 0.0
        if self.rate <= 0:
            return 1.0
        return (want - self.tokens) / self.rate


def _held(lock) -> bool:
    """Whether another thread holds `lock`, found without waiting: a
    Condition's RLock has no `locked()` before Python 3.14, so this is
    a non-blocking acquire, released at once when it succeeds."""
    if lock.acquire(blocking=False):
        lock.release()
        return False
    return True


class AdmissionController:
    """Per-server tiered admission: queue-depth watermarks pick the
    shed floor, per-tier token buckets smooth bursts, and a brownout
    state machine with hysteresis covers sustained tier-1 pressure.

    Thread-safe; `admit()` is called on every request hot path, so the
    watermark evaluation (which reads other subsystems' locked depth
    counters) is cached for ``refresh_s`` between recomputes.
    """

    #: per-tier steady-state admit rates (requests/s) and burst depths.
    #: Generous on purpose: watermarks are the load signal; the buckets
    #: only flatten pathological bursts. Tier 0 is unlimited.
    DEFAULT_RATES = {TIER_COMMIT: 16384.0, TIER_SUBMIT: 8192.0,
                     TIER_READ: 16384.0}

    def __init__(self, rates: Optional[Dict[int, float]] = None,
                 burst_s: float = 2.0,
                 refresh_s: float = 0.005,
                 brownout_after: float = 1.0,
                 brownout_exit: float = 3.0,
                 clock: Callable[[], float] = time.monotonic,
                 ledger_size: int = 4096):
        self._clock = clock
        self._lock = threading.Lock()
        now = clock()
        rates = dict(rates or self.DEFAULT_RATES)
        self._buckets: Dict[int, _Bucket] = {
            t: _Bucket(r, r * burst_s, now) for t, r in rates.items()
            if t != TIER_LIVENESS}
        # (name, depth_fn, soft, hard, commit_path)
        self._queues: List[Tuple[str, Callable[[], int], int, int, bool]] = []
        # the locks the depth functions take (register_queue's ``lock``)
        self._depth_locks: list = []
        self._refresh_s = refresh_s
        self._pressure = 0
        self._pressure_stamp = -1.0
        self._alive = True
        self.brownout_after = brownout_after
        self.brownout_exit = brownout_exit
        self._hot_since: Optional[float] = None
        self._calm_since: Optional[float] = None
        self._degraded = False
        # admit/shed ledger for chaos invariant 10 (tier ordering):
        # (mono_ts, tier, "admit"|"shed", source)
        self._ledger: deque = deque(maxlen=ledger_size)
        self.stats = {"admitted": 0, "shed": 0, "degraded_entries": 0}
        # degraded_now()'s counters, present at 0 before the first read
        REGISTRY.incr("nomad.load.commit_flag_reads", 0)
        REGISTRY.incr("nomad.load.commit_flag_reads_busy", 0)

    # -- wiring ------------------------------------------------------

    def register_queue(self, name: str, depth_fn: Callable[[], int],
                       soft: int, hard: int,
                       commit_path: bool = False,
                       lock=None) -> None:
        """Register a live queue-depth source. ``soft`` tripped sheds
        tier 3 (and tier 2 once any TWO soft marks trip), ``hard``
        tripped sheds tiers >= 2 (>= 1 when two hard marks trip).
        ``commit_path`` queues (raft proposals, plan queue) also feed
        the brownout detector — sustained pressure THERE is what
        degrades reads. ``lock`` is the lock ``depth_fn`` takes, if
        any: `degraded_now()` counts a read as busy while it is held
        and a pressure evaluation is due."""
        with self._lock:
            self._queues.append((name, depth_fn, soft, hard, commit_path))
            if lock is not None:
                self._depth_locks.append(lock)
            self._pressure_stamp = -1.0  # force recompute

    def set_alive(self, alive: bool) -> None:
        """A stopping server may reject tier 0 (HeartbeatPlaneInactive
        semantics); a live one never does. Gates invariant 10."""
        with self._lock:
            self._alive = alive

    # -- watermark/pressure machinery --------------------------------

    def _eval_pressure_locked(self, now: float) -> int:
        """0 = calm, 1 = soft watermark(s) tripped, 2 = hard tripped.
        Also advances the brownout hysteresis clock."""
        if now - self._pressure_stamp < self._refresh_s:
            return self._pressure
        soft_hits = hard_hits = 0
        commit_hot = False
        for name, fn, soft, hard, commit_path in self._queues:
            try:
                depth = fn()
            except Exception:
                continue
            REGISTRY.set_gauge(f"nomad.load.depth.{name}", depth)
            if depth >= hard:
                hard_hits += 1
                if commit_path:
                    commit_hot = True
            elif depth >= soft:
                soft_hits += 1
        if hard_hits:
            pressure = 2
        elif soft_hits:
            pressure = 1
        else:
            pressure = 0
        # brownout: commit-path hard pressure sustained for
        # brownout_after enters degraded; calm sustained for
        # brownout_exit leaves it (hysteresis — no edge flapping)
        if commit_hot:
            self._calm_since = None
            if self._hot_since is None:
                self._hot_since = now
            elif (not self._degraded
                  and now - self._hot_since >= self.brownout_after):
                self._degraded = True
                self.stats["degraded_entries"] += 1
                REGISTRY.incr("nomad.load.degraded_entries")
                TRACER.event("load.degraded", state="enter")
        else:
            self._hot_since = None
            if self._degraded:
                if self._calm_since is None:
                    if pressure == 0:
                        self._calm_since = now
                elif pressure != 0:
                    self._calm_since = None
                elif now - self._calm_since >= self.brownout_exit:
                    self._degraded = False
                    self._calm_since = None
                    TRACER.event("load.degraded", state="exit")
        self._pressure = pressure
        self._pressure_stamp = now
        REGISTRY.set_gauge("nomad.load.pressure", pressure)
        REGISTRY.set_gauge("nomad.load.degraded", 1.0 if self._degraded
                           else 0.0)
        return pressure

    def shed_floor(self) -> int:
        """Lowest tier currently being shed (TIER_NONE when calm):
        pressure 1 sheds tier 3, pressure 2 sheds tiers >= 2, degraded
        mode pins the floor at 2 until hysteresis releases it. Tier 0
        is never below the floor while the server is alive."""
        with self._lock:
            return self._shed_floor_locked(self._clock())

    def _shed_floor_locked(self, now: float) -> int:
        pressure = self._eval_pressure_locked(now)
        floor = TIER_NONE
        if pressure >= 2:
            floor = TIER_SUBMIT
        elif pressure == 1:
            floor = TIER_READ
        if self._degraded:
            floor = min(floor, TIER_SUBMIT)
        return floor

    def degraded(self) -> bool:
        """True while the brownout state machine holds the server in
        degraded mode (reads answer stale-only, watch wakeups
        coalesce)."""
        with self._lock:
            self._eval_pressure_locked(self._clock())
            return self._degraded

    def degraded_now(self) -> bool:
        """The brownout flag as the last pressure evaluation left it,
        for the store's commit path: one read, no lock, no evaluation,
        so a commit never waits behind an admission decision nor for
        the depth functions' locks that an evaluation takes. The flag
        is at most as old as the last admission, park or `degraded()`
        call, each of which advances the state machine. Counts the read
        in ``nomad.load.commit_flag_reads``, and in
        ``nomad.load.commit_flag_reads_busy`` when `degraded()` would
        have waited at it: the controller's lock was held, or an
        evaluation was due and a depth function's lock was held."""
        busy = self._lock.locked() or (
            self._clock() - self._pressure_stamp >= self._refresh_s
            and any(map(_held, self._depth_locks)))
        REGISTRY.incr("nomad.load.commit_flag_reads")
        if busy:
            REGISTRY.incr("nomad.load.commit_flag_reads_busy")
        return self._degraded

    # -- the admission gate ------------------------------------------

    def try_admit(self, tier: int, source: str = "http",
                  cost: float = 1.0) -> Optional[float]:
        """Non-raising admit: None on admission, else the suggested
        retry-after in seconds."""
        name = TIER_NAMES.get(tier, str(tier))
        with self._lock:
            now = self._clock()
            if tier <= TIER_LIVENESS:
                # tier 0 is the point of the whole plane: liveness
                # traffic survives at the expense of bulk traffic,
                # never the reverse. Shed only when the server itself
                # is going away (the caller's HeartbeatPlaneInactive
                # path already covers that truthfully).
                if self._alive:
                    self._ledger.append((now, tier, "admit", source))
                    self.stats["admitted"] += 1
                    REGISTRY.incr(f"nomad.load.admit.{name}")
                    return None
                after = 0.5
            else:
                floor = self._shed_floor_locked(now)
                after = 0.0
                shed = tier >= floor
                if shed and self._degraded and tier == TIER_READ \
                        and source != "watch":
                    # brownout pin carve-out: when the degraded pin —
                    # not live queue pressure — is what put reads below
                    # the floor, plain reads are ADMITTED and served
                    # stale-local with the X-Nomad-Consistency-Degraded
                    # header instead of refused; 429ing every read would
                    # turn graceful degradation into a read outage.
                    # Watch parks stay shed (each pins a thread + heap
                    # entry for the whole blocking window).
                    pressure_floor = (TIER_SUBMIT if self._pressure >= 2
                                      else TIER_READ if self._pressure == 1
                                      else TIER_NONE)
                    if tier < pressure_floor:
                        shed = False
                if shed:
                    # drain estimate: deeper pressure => longer back-off,
                    # higher tiers told to stay away longer
                    after = min(5.0, 0.25 * (1 + self._pressure)
                                * (1 + tier - floor))
                elif cost > 0.0:
                    b = self._buckets.get(tier)
                    if b is not None:
                        after = b.take(cost, now)
            if after <= 0.0:
                self._ledger.append((now, tier, "admit", source))
                self.stats["admitted"] += 1
                REGISTRY.incr(f"nomad.load.admit.{name}")
                return None
            self._ledger.append((now, tier, "shed", source))
            self.stats["shed"] += 1
        REGISTRY.incr("nomad.load.shed")
        REGISTRY.incr(f"nomad.load.shed.{name}")
        TRACER.event("load.shed", tier=tier, source=source, after=after)
        return after

    def admit(self, tier: int, source: str = "http",
              cost: float = 1.0) -> None:
        """Admission gate: returns on admit, raises RetryLater(after=)
        on shed. Consulted at HTTP ingress, RaftNode.apply enqueue,
        EvalBroker.enqueue and WatchTable park."""
        after = self.try_admit(tier, source=source, cost=cost)
        if after is not None:
            raise RetryLater(tier=tier, after=after, reason=source)

    # -- introspection -----------------------------------------------

    def ledger(self) -> List[Tuple[float, int, str, str]]:
        with self._lock:
            return list(self._ledger)

    def snapshot(self) -> dict:
        with self._lock:
            now = self._clock()
            floor = self._shed_floor_locked(now)
            return {"pressure": self._pressure,
                    "degraded": self._degraded, "shed_floor": floor,
                    "alive": self._alive, **self.stats}


# -- tier classification for the RPC surface -------------------------
#
# Keyed off the leader-forwarded endpoint names (raft/cluster.py
# FORWARD): the transport dispatch and the HTTP layer both map a
# request to its tier through here so the two ingresses can never
# disagree about what counts as liveness.

_TIER0_METHODS = frozenset({
    "heartbeat", "heartbeat_batch", "register_node", "register_nodes",
    "update_node_status", "mark_node_down", "mark_nodes_down",
    "deregister_node",
})
_TIER1_METHODS = frozenset({
    "update_allocs_from_client", "update_alloc", "stop_alloc",
    "signal_alloc", "restart_alloc",
})


def tier_for_method(name: str) -> int:
    """Admission tier for a forwarded RPC endpoint name."""
    if name in _TIER0_METHODS:
        return TIER_LIVENESS
    if name in _TIER1_METHODS:
        return TIER_COMMIT
    return TIER_SUBMIT
