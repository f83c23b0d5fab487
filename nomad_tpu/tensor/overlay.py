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
"""

from __future__ import annotations

import threading
import time
from typing import Dict

import numpy as np

ENTRY_TTL = 60.0


class InflightOverlay:
    def __init__(self):
        self._lock = threading.Lock()
        self._entries: Dict[int, dict] = {}  # token -> entry
        self._token = 0
        self.stats = {"registered": 0, "confirmed": 0, "expired": 0}

    def register(self, cluster, rows, deltas, plan) -> None:
        """Record one eval's in-flight usage: `deltas[i]` (a resource
        vector) on row `rows[i]` of `cluster` (a ClusterTensors; each
        row once), and arrange for the plan outcome to close the entry
        (planner contract: hooks fire with the commit). The entry keeps
        its rows for the row order it was registered against, so a fold
        into that order is one indexed add."""
        if not len(rows):
            return
        now = time.time()
        with self._lock:
            self._token += 1
            token = self._token
            self._entries[token] = {
                "rows": rows, "deltas": deltas, "nodes": cluster.nodes,
                "node_index": cluster.node_index, "born": now,
                "plan": id(plan)}
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
        nodes never landed — either way the entry closes."""
        with self._lock:
            if self._entries.pop(token, None) is not None:
                self.stats["confirmed"] += 1

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
        full and the applier rejects its rows."""
        now = time.time()
        exclude = id(exclude_plan) if exclude_plan is not None else None
        with self._lock:
            dead = [t for t, e in self._entries.items()
                    if now - e["born"] > ENTRY_TTL]
            for t in dead:
                del self._entries[t]
                self.stats["expired"] += 1
            return [e for e in self._entries.values()
                    if e.get("plan") != exclude or exclude is None]

    def fold(self, used, node_index: Dict[str, int], entries) -> None:
        """Add the deltas of `entries`, as open_entries() returned them
        before committed usage was read, into a canonical-order usage
        matrix (in place). Called from the usage gathers."""
        d = used.shape[1]
        for e in entries:
            rows, deltas = e["rows"], e["deltas"][:, :d]
            if e["node_index"] is not node_index:
                # another row order (a node joined or left since): find
                # each node's row by id
                nodes = e["nodes"]
                at = np.array([node_index.get(nodes[r].id, -1)
                               for r in rows], dtype=np.int64)
                rows, deltas = at[at >= 0], deltas[at >= 0]
            used[rows] += deltas


INFLIGHT = InflightOverlay()
