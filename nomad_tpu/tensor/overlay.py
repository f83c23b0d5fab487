"""In-flight usage overlay for the PER-EVAL solve paths.

The bulk C2M path serializes racing workers on the solver service's
device-resident carry, so concurrent solves see each other's placements
before they commit (tensor/solver.py). The per-eval kernel paths
(spread/constraints/distinct-hosts — one fused launch per eval) had no
such visibility: two workers racing at the same snapshot both fill the
same best-fit nodes to capacity, the applier rejects the loser's whole
node lists, and the spread rung's rejection rate ran ABOVE stock
(round 4 weak #5: 0.0018 vs 0.0; stock's log2-N candidate subsampling
decorrelates workers by accident).

This overlay is the host-side twin of the service's ledger: each
per-eval solve registers its placements' per-node usage deltas as the
rows of its cluster they land on; every ClusterTensors usage gather
folds the open entries in (one indexed add an entry while the row
order is the one it was registered against, by node ID otherwise), so
the NEXT racing eval plans around them. Entries close through the
same plan post-apply hooks the service uses (confirmed usage is then in
the store; rejected nodes' deltas die with the entry), with a TTL
backstop for evals that die between solve and submit. Like the carry,
this is optimism-repair only — the serialized plan applier remains the
correctness gate.

Ports ride in the same entries. Usage is additive and read live, so an
entry's usage dies when its plan is applied (the committed usage takes
over). A port is a number, and the ports an evaluation sees committed
are those of ITS OWN SNAPSHOT (EvalContext.proposed_allocs): a plan
applied after that snapshot was taken is in neither the snapshot nor
the open entries, and the evaluation would hand its numbers out again
on the same half-filled node. So an entry that carries ports outlives
its commit: confirm() drops the ports of the nodes the applier
rejected, stamps the rest with the store's index (at or past the
commit's), and keeps them readable for evaluations whose snapshot is
older than that index, until the store's snapshot tracker says none
such is left (or ENTRY_TTL after the commit, whichever comes first).
ports_on() is the one reader; structs/network.py has the whole rule.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, Iterable, Optional, Sequence

import numpy as np

from ..obs import TRACER

ENTRY_TTL = 60.0


class InflightOverlay:
    def __init__(self):
        self._lock = threading.Lock()
        self._entries: Dict[int, dict] = {}  # token -> open entry
        self._token = 0
        # node id -> {token: the port numbers that entry holds there},
        # of open entries and of closed ones alike
        self._ports: Dict[str, Dict[int, Sequence[int]]] = {}
        # token -> (store index at the close, closed at, the store,
        # node ids): entries whose plan is applied and whose ports an
        # evaluation with an older snapshot still has to see
        self._closed: Dict[int, tuple] = {}
        self.stats = {"registered": 0, "confirmed": 0, "expired": 0,
                      "ports_retired": 0}

    def register(self, cluster, rows, deltas, plan, ports=None) -> None:
        """Record one eval's in-flight usage: `deltas[i]` (a resource
        vector) on row `rows[i]` of `cluster` (a ClusterTensors; each
        row once), and arrange for the plan outcome to close the entry
        (planner contract: hooks fire with the commit). The entry keeps
        its rows for the row order it was registered against, so a fold
        into that order is one indexed add. `ports` ({node id: the port
        numbers handed out there}) makes them readable through
        ports_on() by every evaluation of this process."""
        if not len(rows) and not ports:
            return
        now = time.time()
        with self._lock:
            self._token += 1
            token = self._token
            self._entries[token] = {
                "rows": rows, "deltas": deltas, "nodes": cluster.nodes,
                "node_index": cluster.node_index, "born": now,
                "plan": id(plan), "ports": ports,
                "store": getattr(cluster, "_store", None)}
            for node_id, values in (ports or {}).items():
                self._ports.setdefault(node_id, {})[token] = values
            self.stats["registered"] += 1
        if plan is not None:
            plan.post_apply_hooks.append(
                lambda result, _t=token: self.confirm(
                    _t, getattr(result, "rejected_nodes", None) or ()))
        else:
            # no plan to hook (harness edge): rely on the TTL
            pass

    def confirm(self, token: int, rejected_node_ids) -> None:
        """Plan applied: committed usage is now in the store, rejected
        nodes never landed — either way the entry's usage closes. Its
        ports on rejected nodes go with it; the others stay readable,
        stamped with the store's index, for snapshots older than that
        (the module's header). The index is read from the store, after
        the commit was published, and not from the plan's result: under
        raft that one counts log entries, a snapshot store generations."""
        now = time.time()
        with self._lock:
            entry = self._entries.pop(token, None)
            if entry is None:
                return
            self.stats["confirmed"] += 1
            if entry["ports"]:
                rejected = set(rejected_node_ids)
                self._forget_ports(token, rejected)
                kept = [n for n in entry["ports"] if n not in rejected]
                if kept:
                    store = entry["store"]
                    self._closed[token] = (
                        None if store is None else store.latest_index,
                        now, store, kept)
            self._retire(now)

    def _forget_ports(self, token: int, node_ids: Iterable[str]) -> None:
        for node_id in node_ids:
            held = self._ports.get(node_id)
            if held is not None and held.pop(token, None) is not None \
                    and not held:
                del self._ports[node_id]

    def retire(self) -> None:
        """Drop the closed entries nobody can need: every live snapshot
        of their store is at or past their index (so is every later
        one), or the TTL has run. Every confirm() does it."""
        with self._lock:
            self._retire(time.time())

    def _retire(self, now: float) -> None:
        floors: dict = {}
        for token, (index, at, store, node_ids) in list(self._closed.items()):
            if now - at <= ENTRY_TTL:
                if index is None:
                    continue
                floor = floors.get(id(store))
                if floor is None:
                    floor = floors[id(store)] = store._tracker.min_live(
                        store.latest_index)
                if floor < index:
                    continue
            del self._closed[token]
            self._forget_ports(token, node_ids)
            self.stats["ports_retired"] += 1

    def ports_on(self, node_ids: Iterable[str],
                 snapshot_index: Optional[int]) -> Dict[str, set]:
        """{node id: port numbers} that entries hold on `node_ids` and
        an evaluation whose snapshot is at `snapshot_index` cannot see
        committed: those of every open entry (the evaluation's own
        among them: a port is a number, not a sum, and its own
        placements that are not rows of its plan yet must not be handed
        out twice either) and of every closed one stamped past that
        index. Nodes without any are left out."""
        out: Dict[str, set] = {}
        with self._lock:
            if not self._ports:
                return out
            closed = self._closed
            for node_id in node_ids:
                held = self._ports.get(node_id)
                if not held:
                    continue
                for token, values in held.items():
                    stamp = closed.get(token)
                    if (stamp is None or stamp[0] is None
                            or snapshot_index is None
                            or stamp[0] > snapshot_index):
                        out.setdefault(node_id, set()).update(values)
        return out

    def open_entries(self, exclude_plan=None) -> list:
        """The live (non-TTL-expired) entries not owned by
        `exclude_plan`, as fold() takes them. A usage gather reads them
        BEFORE it reads committed usage (the feed's drain, the store's
        matrix): an entry closes right after its commit is published,
        so one that was read open is either still outside the committed
        usage read next or, rarely, inside it as well (counted twice:
        that solve plans around nodes that are freer than they look);
        read in the other order, a commit that lands between the two
        reads is in neither, the solve fills nodes that are already
        full and the applier rejects its rows. Span `overlay.read`
        (arg `entries`), the wait for the overlay's lock included."""
        now = time.time()
        exclude = id(exclude_plan) if exclude_plan is not None else None
        with TRACER.span("overlay.read", device=True) as span:
            with self._lock:
                dead = [t for t, e in self._entries.items()
                        if now - e["born"] > ENTRY_TTL]
                for t in dead:
                    self._forget_ports(t, self._entries.pop(t)["ports"] or ())
                    self.stats["expired"] += 1
                # an entry of ports alone (the host scorer's) has no usage
                out = [e for e in self._entries.values()
                       if (e.get("plan") != exclude or exclude is None)
                       and len(e["rows"])]
            span.set(entries=len(out))
        return out

    def fold(self, used, node_index: Dict[str, int], entries) -> None:
        """Add the deltas of `entries`, as open_entries() returned them
        before committed usage was read, into a canonical-order usage
        matrix (in place). Called from the usage gathers. Span
        `overlay.fold` (args `entries`, `rows`: the rows added,
        `remapped`: entries of another row order, found by node id)."""
        d = used.shape[1]
        added = remapped = 0
        with TRACER.span("overlay.fold", device=True,
                         entries=len(entries)) as span:
            for e in entries:
                rows, deltas = e["rows"], e["deltas"][:, :d]
                if e["node_index"] is not node_index:
                    # another row order (a node joined or left since):
                    # find each node's row by id
                    nodes = e["nodes"]
                    at = np.array([node_index.get(nodes[r].id, -1)
                                   for r in rows], dtype=np.int64)
                    rows, deltas = at[at >= 0], deltas[at >= 0]
                    remapped += 1
                used[rows] += deltas
                added += len(rows)
            span.set(rows=added, remapped=remapped)


INFLIGHT = InflightOverlay()
