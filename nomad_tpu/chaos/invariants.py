"""Safety invariants checked between chaos steps.

Four checks, mirroring the safety arguments in raft (Ongaro §5.2/§5.4)
and the reference scheduler's liveness contract:

  1. election safety — at most one leader per term, ever, across the
     whole run (crash/restart included);
  2. log matching — any two live nodes agree on (term, command) for
     every index both have committed;
  3. committed durability — once an entry is observed committed it is
     never lost or rewritten, across crashes and restarts (snapshot
     compaction counts as retention, not loss);
  4. convergence / reschedule — after a heal, every FSM reaches the
     same state, and every alloc on a heartbeat-invalidated node is
     eventually rescheduled off it.

The checker is stateful on purpose: election safety and durability are
*history* properties, so the same ``InvariantChecker`` must live for a
whole scenario and see every intermediate state the runner produces.
All reads snapshot one node at a time under that node's own lock —
never two node locks at once, so the checker cannot introduce a
lock-order cycle into the raft graph nomadsan watches.
"""

from __future__ import annotations

import hashlib
import json
import logging
import time
from typing import Dict, List, Optional, Tuple

from ..structs import enums

log = logging.getLogger("nomad_tpu.chaos")


class InvariantViolation(AssertionError):
    """A safety property was broken; chaos runs must fail loudly."""


def _digest(command) -> str:
    """Interleaving- and storage-independent fingerprint of a command.

    json round-trips tuples to lists, so an in-memory node (tuples) and
    a durably restarted one (lists from log.jsonl) digest identically.
    """
    payload = json.dumps(command, sort_keys=True, default=str)
    return hashlib.sha256(payload.encode()).hexdigest()[:16]


def _live(cluster) -> List:
    return [s for s in cluster.servers.values()
            if not s.crashed and not s.raft._stop.is_set()]


def _log_prefix(server, committed_only: bool = True,
                ) -> Tuple[int, int, List[Tuple[int, int, str]]]:
    """(first_index, commit_index, [(index, term, digest), ...]) for the
    entries this node holds in its log — the committed prefix by
    default, or the whole log (``committed_only=False``; used by the
    durability check because commit *knowledge* is volatile: a restarted
    leader re-derives commit_index after election while its log already
    holds everything). Entries below first_index were compacted into a
    snapshot — covered, not lost."""
    raft = server.raft
    with raft._lock:
        last = raft.log.last()[0]
        commit = min(raft.commit_index, last)
        first = raft.log.first_index() if hasattr(raft.log, "first_index") else 1
        upto = last if not committed_only else commit
        rows = []
        for idx in range(first, upto + 1):
            e = raft.log.get(idx)
            if e is None:  # compacted under us; harmless
                continue
            rows.append((idx, e.term, _digest(e.command)))
    return first, commit, rows


def _dump_comparable(server) -> dict:
    """FSM dump minus the MVCC index: a restarted replica that restored
    a snapshot and replayed the tail holds identical *contents* at a
    possibly different generation counter."""
    from ..state.persist import dump_store
    d = dump_store(server.local_store)
    d.pop("index", None)
    return d


class InvariantChecker:
    def __init__(self):
        # term -> leader id, accumulated over the whole scenario
        self._leaders_by_term: Dict[int, str] = {}
        # index -> (term, digest) once observed committed anywhere
        self._committed: Dict[int, Tuple[int, str]] = {}
        self.stats = {"checks": 0, "violations": 0}

    # -- 1: election safety ------------------------------------------

    def check_election_safety(self, cluster) -> None:
        for s in _live(cluster):
            raft = s.raft
            with raft._lock:
                is_leader = raft.state == "leader"
                term = raft.current_term
            if not is_leader:
                continue
            prev = self._leaders_by_term.get(term)
            if prev is not None and prev != s.id:
                self._fail(
                    f"election safety: term {term} has two leaders "
                    f"({prev} and {s.id})")
            self._leaders_by_term[term] = s.id

    # -- 2: log matching ---------------------------------------------

    def check_log_matching(self, cluster) -> None:
        prefixes = [(s.id, _log_prefix(s)) for s in _live(cluster)]
        by_index: Dict[int, Tuple[str, int, str]] = {}
        for sid, (_first, _commit, rows) in prefixes:
            for idx, term, dig in rows:
                seen = by_index.get(idx)
                if seen is None:
                    by_index[idx] = (sid, term, dig)
                elif (term, dig) != seen[1:]:
                    self._fail(
                        f"log matching: committed index {idx} diverges — "
                        f"{seen[0]} has (term={seen[1]}, {seen[2]}), "
                        f"{sid} has (term={term}, {dig})")

    # -- 3: committed entries survive crashes ------------------------

    def check_committed_durability(self, cluster) -> None:
        """Record every committed (index, term, digest) seen so far and
        verify all previous records are still held (or snapshotted) by
        at least one live node, unchanged.

        Records come from committed prefixes; the retention check scans
        whole logs: raft only guarantees committed entries are present
        in a quorum's LOGS — commit_index itself is volatile knowledge
        every node re-derives after an election, so right after a
        leader crash no live node may *know* the commit point yet."""
        live = _live(cluster)
        full = {s.id: _log_prefix(s, committed_only=False) for s in live}
        maps = {sid: {idx: (term, dig) for idx, term, dig in rows}
                for sid, (_f, _c, rows) in full.items()}
        for sid, (_f, commit, rows) in full.items():
            for idx, term, dig in rows:
                if idx > commit:
                    continue  # record only what this node knows committed
                prev = self._committed.get(idx)
                if prev is not None and prev != (term, dig):
                    self._fail(
                        f"durability: committed index {idx} rewritten — "
                        f"recorded (term={prev[0]}, {prev[1]}), {sid} now "
                        f"has (term={term}, {dig})")
                self._committed[idx] = (term, dig)
        if not live:
            return
        for idx, (term, dig) in self._committed.items():
            held = False
            for sid, (first, _commit, _rows) in full.items():
                if idx < first:
                    held = True  # compacted into this node's snapshot
                    break
                if maps[sid].get(idx) == (term, dig):
                    held = True
                    break
            if not held:
                self._fail(
                    f"durability: committed index {idx} (term={term}, "
                    f"{dig}) vanished from every live node")

    # -- 4a: FSM convergence after heal ------------------------------

    def check_convergence(self, cluster, timeout: float = 15.0) -> None:
        """After a heal: all live nodes apply up to the max commit index
        and hold identical FSM contents."""
        deadline = time.monotonic() + timeout
        last_err = "no live nodes"
        while time.monotonic() < deadline:
            live = _live(cluster)
            if not live:
                break
            target = max(s.raft.commit_index for s in live)
            lagging = [s.id for s in live if s.raft.last_applied < target]
            if lagging:
                last_err = (f"replicas {lagging} applied < commit "
                            f"index {target}")
                time.sleep(0.05)
                continue
            dumps = {s.id: _dump_comparable(s) for s in live}
            ref_id = live[0].id
            ref = dumps[ref_id]
            diverged = [sid for sid, d in dumps.items() if d != ref]
            if not diverged:
                self.stats["checks"] += 1
                return
            last_err = f"FSM contents of {diverged} differ from {ref_id}"
            time.sleep(0.05)
        self._fail(f"convergence: {last_err} after {timeout:.0f}s")

    # -- 4b: allocs leave heartbeat-invalidated nodes ----------------

    def check_reschedule(self, server, timeout: float = 15.0) -> None:
        """Every alloc placed on a node the heartbeat manager marked
        down must eventually stop being live there (lost/stopped, with
        the scheduler free to place replacements elsewhere)."""
        from ..structs import enums
        deadline = time.monotonic() + timeout
        last_err = ""
        while time.monotonic() < deadline:
            snap = server.store.snapshot()
            down = [n.id for n in snap.nodes()
                    if n.status == enums.NODE_STATUS_DOWN]
            stranded = []
            for nid in down:
                for a in snap.allocs_by_node(nid):
                    if not a.terminal_status() and not a.server_terminal():
                        stranded.append((a.id[:8], nid))
            if not stranded:
                self.stats["checks"] += 1
                return
            last_err = f"live allocs still on down nodes: {stranded}"
            time.sleep(0.05)
        self._fail(f"reschedule: {last_err} after {timeout:.0f}s")

    # -- 5: alloc-set uniqueness -------------------------------------

    def check_alloc_uniqueness(self, cluster) -> None:
        """No duplicate placements: on every live node's FSM, at most
        one *live* (neither client- nor server-terminal) alloc exists
        per (namespace, job_id, alloc name). The batched plan-commit
        path re-applies ambiguous rounds through the idempotent per-plan
        fallback after a failover — upserts keyed by alloc id converge,
        so a duplicate under a FRESH id is exactly the bug class this
        catches (a round answered twice re-planning the same slot)."""
        for s in _live(cluster):
            snap = s.local_store.snapshot()
            by_slot: Dict[tuple, List[str]] = {}
            for a in snap.allocs():
                if a.terminal_status() or a.server_terminal():
                    continue
                by_slot.setdefault(
                    (a.namespace, a.job_id, a.name), []).append(a.id)
            dups = {slot: ids for slot, ids in by_slot.items()
                    if len(ids) > 1}
            if dups:
                worst = next(iter(dups.items()))
                self._fail(
                    f"alloc uniqueness: {len(dups)} slot(s) on {s.id} "
                    f"hold multiple live allocs, e.g. {worst[0]} -> "
                    f"{[i[:8] for i in worst[1]]}")
        self.stats["checks"] += 1

    # -- 8: node liveness (client-plane swarm) ------------------------

    def check_node_liveness(self, cluster, swarm=None,
                            ttl: float = None) -> None:
        """No missed-TTL false positives, on every live replica:

        (a) every expiry the heartbeat manager fired is attributable to
            a real silence — its attribution log shows >= ~one full TTL
            between arming and expiry (the failover grace window makes
            this hold across restore() too);
        (b) with a swarm attached: any swarm node marked down/
            disconnected went at least ~one TTL without a server-acked
            heartbeat before the mark (`status_updated_at - last_ok`);
        (c) no node is both down and heartbeating: a down-marked node
            whose heartbeats have been succeeding for > 2 TTLs since
            the mark should have flipped back to ready.

        Accepts a RaftCluster or a single (possibly replicated)
        server. Small epsilons absorb clock skew between the proposer's
        wall-clock stamp and the swarm's ack timestamps."""
        down_states = (enums.NODE_STATUS_DOWN,
                       enums.NODE_STATUS_DISCONNECTED)
        servers = (_live(cluster) if hasattr(cluster, "servers")
                   else [cluster])
        for s in servers:
            core = getattr(s, "server", s)
            store = getattr(s, "local_store", None) or core.store
            mgr = core.heartbeats
            t = ttl if ttl is not None else mgr.ttl
            for node_id, armed_at, expired_at in mgr.expiry_snapshot():
                silence = expired_at - armed_at
                if silence < t * 0.95 - 0.01:
                    self._fail(
                        f"node liveness: {getattr(s, 'id', 'server')} "
                        f"expired {node_id} after only {silence:.3f}s "
                        f"of a {t:.3f}s TTL")
            if swarm is None:
                continue
            now = time.time()
            for node in store.snapshot().nodes():
                sn = swarm.sim(node.id)
                if sn is None or node.status not in down_states:
                    continue
                last_ok = swarm.last_ok(node.id)
                silence = node.status_updated_at - last_ok
                if last_ok > 0 and silence < t * 0.9 - 0.1:
                    self._fail(
                        f"node liveness: {node.id} marked {node.status} "
                        f"on {getattr(s, 'id', 'server')} only "
                        f"{silence:.3f}s after a server-acked heartbeat "
                        f"(TTL {t:.3f}s) — missed-TTL false positive")
                if (last_ok - node.status_updated_at > 2 * t
                        and now - last_ok < t):
                    self._fail(
                        f"node liveness: {node.id} is {node.status} on "
                        f"{getattr(s, 'id', 'server')} yet has been "
                        f"heartbeating successfully for "
                        f"{last_ok - node.status_updated_at:.3f}s since "
                        f"the mark — down AND heartbeating")
        self.stats["checks"] += 1

    # -- 6: snapshot integrity (nomadown runtime prong) ---------------

    def check_snapshot_integrity(self, cluster=None) -> None:
        """When the nomadown ownership sanitizer is armed
        (NOMAD_TPU_SAN=1), sweep every fingerprinted store row for
        post-insert divergence — an aliased mutation rewrites MVCC
        history for all live snapshots and, through the FSM, diverges
        replicas; catch it here before it surfaces as a log-matching or
        convergence failure."""
        from ..analysis.ownership import GLOBAL as own

        if not own.active:
            return
        before = len(own.violations)
        own.verify_all()
        fresh = own.violations[before:]
        if fresh:
            extra = f" (+{len(fresh) - 1} more)" if len(fresh) > 1 else ""
            self._fail(f"snapshot integrity: {fresh[0].render()}{extra}")

    # -- 7: launch ledger (nomadjit runtime prong) --------------------

    def check_launch_ledger(self, cluster=None) -> None:
        """When the nomadjit launch ledger is armed (NOMAD_TPU_SAN=1),
        sweep it for warm-path compiles, extra host syncs, unsanctioned
        transfers, and leaked launch windows — a retrace or stray sync
        on the solve hot path bills milliseconds to every launch long
        before it surfaces as a failed perf gate."""
        from ..analysis.launch_ledger import GLOBAL as ledger

        if not ledger.active:
            return
        problems = ledger.verify_all()
        if problems:
            extra = (f" (+{len(problems) - 1} more)"
                     if len(problems) > 1 else "")
            self._fail(f"launch ledger: {problems[0]}{extra}")

    # -- 9: event completeness (nomadflow runtime prong) ---------------

    def check_event_completeness(self, cluster=None) -> None:
        """When the nomadflow shadow tracker is armed (NOMAD_TPU_SAN=1),
        force-compare every attached shadow replica against a fresh MVCC
        snapshot rebuild — a mutation that skipped its delta leaves every
        event consumer (alloc sync, the event stream API, the future
        device-resident incremental state) silently stale; catch the
        missing event here, at the commit that dropped it."""
        from ..analysis.shadow import GLOBAL as shadow

        if not shadow.active:
            return
        before = len(shadow.violations)
        shadow.verify_all()
        fresh = shadow.violations[before:]
        if fresh:
            extra = f" (+{len(fresh) - 1} more)" if len(fresh) > 1 else ""
            self._fail(f"event completeness: {fresh[0].render()}{extra}")

    # -- 11: incremental-state parity (nomadstate) ---------------------

    def check_state_parity(self, cluster=None) -> None:
        """Force a parity digest on every attached incremental-state
        feed (tensor/incremental.py): the delta-fed device-resident
        usage base must equal a fresh gen-bounded snapshot rebuild
        bit-exactly, flushed device twins included. Unlike the shadow
        prong the feeds attach in production, so this sweep runs
        whenever any feed exists."""
        from ..tensor.incremental import GLOBAL as state

        if not state.feeds:
            return
        before = len(state.violations)
        state.verify_all()
        fresh = state.violations[before:]
        if fresh:
            extra = f" (+{len(fresh) - 1} more)" if len(fresh) > 1 else ""
            self._fail(f"state parity: {fresh[0].render()}{extra}")

    # -- 10: overload tier ordering (nomadload) ------------------------

    def check_overload_ordering(self, cluster, window: float = 0.5
                                ) -> None:
        """Audit every live server's admission ledger (nomadload): the
        whole point of the overload plane is that liveness traffic
        survives at the expense of bulk traffic, never the reverse.

        (a) a tier-0 (liveness) request was never shed while the server
            was alive — tier-0 sheds are legal only on a stopping
            server (set_alive(False));
        (b) tier ordering: no tier-0 shed has a tier>=2 (submit/read)
            admit within ``window`` seconds of it — bulk work getting
            through while heartbeats bounce is priority inversion.

        Accepts a RaftCluster or a single (possibly replicated)
        server."""
        servers = (_live(cluster) if hasattr(cluster, "servers")
                   else [cluster])
        for s in servers:
            core = getattr(s, "server", s)
            adm = getattr(core, "loadctl", None)
            if adm is None:
                continue
            ledger = adm.ledger()
            t0_sheds = [(ts, src) for ts, tier, kind, src in ledger
                        if tier == 0 and kind == "shed"]
            if adm.snapshot()["alive"] and t0_sheds:
                ts, src = t0_sheds[0]
                self._fail(
                    f"overload ordering: {getattr(s, 'id', 'server')} "
                    f"shed {len(t0_sheds)} tier-0 request(s) while "
                    f"alive (first: source={src})")
            bulk_admits = [ts for ts, tier, kind, _src in ledger
                           if tier >= 2 and kind == "admit"]
            for ts, src in t0_sheds:
                near = [b for b in bulk_admits if abs(b - ts) <= window]
                if near:
                    self._fail(
                        f"overload ordering: "
                        f"{getattr(s, 'id', 'server')} shed a tier-0 "
                        f"request (source={src}) within {window:.1f}s "
                        f"of {len(near)} tier>=2 admit(s) — priority "
                        f"inversion")
        self.stats["checks"] += 1

    # -- aggregate ----------------------------------------------------

    def check_all(self, cluster) -> None:
        """The per-step safety sweep (history properties only; the
        liveness checks — convergence, reschedule — take timeouts and
        run where a scenario expects quiescence)."""
        self.check_snapshot_integrity(cluster)
        self.check_launch_ledger(cluster)
        self.check_event_completeness(cluster)
        self.check_state_parity(cluster)
        self.check_election_safety(cluster)
        self.check_log_matching(cluster)
        self.check_committed_durability(cluster)
        self.check_alloc_uniqueness(cluster)
        self.check_overload_ordering(cluster)
        self.stats["checks"] += 1

    def _fail(self, msg: str) -> None:
        self.stats["violations"] += 1
        log.error("invariant violated: %s", msg)
        # flight recorder: the last few hundred subsystem transitions
        # (broker deliveries, plan verdicts, raft role flips, solver
        # launches) are exactly the forensics a violation needs — dump
        # them with the failure instead of asking for a repro run
        from ..obs import RECORDER

        dump = RECORDER.dump_text(last=80)
        if dump:
            log.error("flight recorder (last 80 events):\n%s", dump)
        raise InvariantViolation(msg)
