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

Ports and device instances ride in the same entries ("held" values:
ids handed out on a node, as opposed to usage, which is a sum). Usage is
additive and read live, so an entry's usage dies when its plan is
applied (the committed usage takes over). A port or an instance is an
id, and the ids an evaluation sees committed are those of ITS OWN
SNAPSHOT (EvalContext.proposed_allocs): a plan applied after that
snapshot was taken is in neither the snapshot nor the open entries, and
the evaluation would hand the same ids out again on the same
half-filled node. So an entry that holds ids outlives its commit:
confirm() drops the ids on the nodes the applier rejected, stamps the
rest with the store's index (at or past the commit's), and keeps them
readable for evaluations whose snapshot is older than that index, until
the store's snapshot tracker says none such is left (or ENTRY_TTL after
the commit, whichever comes first). One rule for every kind of id;
ports_on() and devices_on() are its readers. structs/network.py has the
rule for ports, scheduler/devices.py DeviceIndex for instances.
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
        # node id -> {token: what that entry holds there}, of open
        # entries and of closed ones alike: port numbers, and device
        # instances as {device group id: [instance ids]}
        self._ports: Dict[str, Dict[int, Sequence[int]]] = {}
        self._devices: Dict[str, Dict[int, Dict[str, Sequence[str]]]] = {}
        self._held = {"ports": self._ports, "devices": self._devices}
        # token -> (store index at the close, closed at, the store, what
        # it holds by kind and node): entries whose plan is applied and
        # whose ids an evaluation with an older snapshot still has to see
        self._closed: Dict[int, tuple] = {}
        self.stats = {"registered": 0, "confirmed": 0, "expired": 0,
                      "retired": 0}

    def register(self, cluster, rows, deltas, plan, ports=None,
                 devices=None) -> None:
        """Record one eval's in-flight usage: `deltas[i]` (a resource
        vector) on row `rows[i]` of `cluster` (a ClusterTensors; each
        row once), and arrange for the plan outcome to close the entry
        (planner contract: hooks fire with the commit). The entry keeps
        its rows for the row order it was registered against, so a fold
        into that order is one indexed add. `ports` ({node id: the port
        numbers handed out there}) and `devices` ({node id: {device
        group id: the instance ids handed out there}}) make them
        readable through ports_on() / devices_on() by every evaluation
        of this process."""
        held = {kind: by_node for kind, by_node in
                (("ports", ports), ("devices", devices)) if by_node}
        if not len(rows) and not held:
            return
        now = time.time()
        with self._lock:
            self._token += 1
            token = self._token
            self._entries[token] = {
                "rows": rows, "deltas": deltas, "nodes": cluster.nodes,
                "node_index": cluster.node_index, "born": now,
                "plan": id(plan), "held": held,
                "store": getattr(cluster, "_store", None)}
            for kind, by_node in held.items():
                table = self._held[kind]
                for node_id, values in by_node.items():
                    table.setdefault(node_id, {})[token] = values
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
            if entry["held"]:
                rejected = set(rejected_node_ids)
                self._forget(token, rejected)
                held = {kind: kept for kind, kept in (
                    (kind, {n: v for n, v in by_node.items()
                            if n not in rejected})
                    for kind, by_node in entry["held"].items()) if kept}
                if held:
                    store = entry["store"]
                    self._closed[token] = (
                        None if store is None else store.latest_index,
                        now, store, held)
            self._retire(now)

    def _forget(self, token: int, node_ids: Iterable[str]) -> None:
        for node_id in node_ids:
            for table in self._held.values():
                held = table.get(node_id)
                if held is not None and held.pop(token, None) is not None \
                        and not held:
                    del table[node_id]

    def retire(self) -> None:
        """Drop the closed entries nobody can need: every live snapshot
        of their store is at or past their index (so is every later
        one), or the TTL has run. Every confirm() does it."""
        with self._lock:
            self._retire(time.time())

    def _retire(self, now: float) -> None:
        floors: dict = {}
        for token, (index, at, store, held) in list(self._closed.items()):
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
            self._forget(token, _nodes_of(held))
            self.stats["retired"] += 1

    def ports_on(self, node_ids: Iterable[str],
                 snapshot_index: Optional[int]) -> Dict[str, set]:
        """{node id: port numbers} that entries hold on `node_ids` and
        an evaluation whose snapshot is at `snapshot_index` cannot see
        committed (`_visible`). Nodes without any are left out."""
        out: Dict[str, set] = {}
        for node_id, values in self._visible(self._ports, node_ids,
                                             snapshot_index):
            out.setdefault(node_id, set()).update(values)
        return out

    def devices_on(self, node_ids: Iterable[str],
                   snapshot_index: Optional[int]) -> Dict[str, Dict[str, set]]:
        """{node id: {device group id: instance ids}} that entries hold
        on `node_ids` and an evaluation whose snapshot is at
        `snapshot_index` cannot see committed (`_visible`). Nodes
        without any are left out."""
        out: Dict[str, Dict[str, set]] = {}
        for node_id, groups in self._visible(self._devices, node_ids,
                                             snapshot_index):
            mine = out.setdefault(node_id, {})
            for gid, ids in groups.items():
                mine.setdefault(gid, set()).update(ids)
        return out

    def devices_held(self, snapshot_index: Optional[int],
                     exclude_plan=None) -> list:
        """[{node id: {device group id: instance ids}}], one map an
        entry, of the entries devices_on() reads (the same rule, taken
        entry by entry rather than node by node, and without a set a
        node): what the count columns' fold adds
        (cluster.fold_inflight_devices)."""
        exclude = id(exclude_plan) if exclude_plan is not None else None
        with self._lock:
            out = [e["held"]["devices"] for e in self._entries.values()
                   if "devices" in e["held"] and e["plan"] != exclude]
            out.extend(
                stamp[3]["devices"] for stamp in self._closed.values()
                if "devices" in stamp[3] and _unseen(stamp, snapshot_index))
        return out

    def _visible(self, table, node_ids, snapshot_index):
        """(node id, what one entry holds there) of every open entry
        (the evaluation's own among them: an id is not a sum, and its
        own placements that are not rows of its plan yet must not be
        handed out twice either) and of every closed one stamped past
        `snapshot_index`, on `node_ids`."""
        with self._lock:
            if not table:
                return []
            closed, out = self._closed, []
            for node_id in node_ids:
                held = table.get(node_id)
                if not held:
                    continue
                for token, values in held.items():
                    if _unseen(closed.get(token), snapshot_index):
                        out.append((node_id, values))
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
                    self._forget(t, _nodes_of(self._entries.pop(t)["held"]))
                    self.stats["expired"] += 1
                # an entry of ids alone (the host scorer's) has no usage
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


def _unseen(stamp, snapshot_index: Optional[int]) -> bool:
    """May an evaluation whose snapshot is at `snapshot_index` not see
    what the entry holds as committed? `stamp` is its `_closed` record
    (index, closed at, store, held), None while it is open: yes while
    open, and after its commit while the commit's index is unknown or
    past the snapshot."""
    return (stamp is None or stamp[0] is None or snapshot_index is None
            or stamp[0] > snapshot_index)


def _nodes_of(held: dict) -> set:
    """The node ids on which an entry's held ids ({kind: {node id:
    ids}}) lie, of any kind."""
    return {node_id for by_node in held.values() for node_id in by_node}


INFLIGHT = InflightOverlay()
