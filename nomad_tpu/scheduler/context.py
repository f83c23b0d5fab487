"""Per-evaluation context (reference scheduler/context.go).

Carries the immutable state snapshot, the in-progress plan, parse caches
(regexp/version, reference context.go:15), the computed-class eligibility
memoizer (context.go:261 EvalEligibility), and per-placement metrics.
"""

from __future__ import annotations

import random
from typing import Dict, List, Optional

from ..structs import AllocMetric, Job, Node, Plan, TaskGroup
from ..structs import enums


class EvalEligibility:
    """Memoizes feasibility per computed node class so a 10k-node cluster
    with 20 classes does ~20 constraint evaluations, not 10k
    (reference context.go:261; escape semantics for unique-attr
    constraints per context.go:292-305)."""

    def __init__(self):
        self.job: Dict[str, bool] = {}       # class -> eligible at job level
        self.tg: Dict[str, Dict[str, bool]] = {}  # tg name -> class -> eligible
        self.job_escaped = False
        self.tg_escaped: Dict[str, bool] = {}

    def set_job(self, job: Job) -> None:
        from .feasible import is_class_escaped

        self.job_escaped = any(
            is_class_escaped(c.ltarget) or is_class_escaped(c.rtarget)
            for c in job.constraints
        )
        for tg in job.task_groups:
            constraints = list(tg.constraints)
            for t in tg.tasks:
                constraints.extend(t.constraints)
            self.tg_escaped[tg.name] = any(
                is_class_escaped(c.ltarget) or is_class_escaped(c.rtarget)
                for c in constraints
            )

    def job_status(self, klass: str) -> Optional[bool]:
        if self.job_escaped or not klass:
            return None
        return self.job.get(klass)

    def set_job_status(self, klass: str, eligible: bool) -> None:
        if not self.job_escaped and klass:
            self.job[klass] = eligible

    def tg_status(self, tg_name: str, klass: str) -> Optional[bool]:
        if self.tg_escaped.get(tg_name) or not klass:
            return None
        return self.tg.get(tg_name, {}).get(klass)

    def set_tg_status(self, tg_name: str, klass: str, eligible: bool) -> None:
        if not self.tg_escaped.get(tg_name) and klass:
            self.tg.setdefault(tg_name, {})[klass] = eligible


class EvalContext:
    """Reference scheduler/context.go EvalContext."""

    def __init__(self, snapshot, plan: Optional[Plan] = None, eval_id: str = "",
                 logger=None, on_event=None):
        self.snapshot = snapshot
        self.plan = plan
        self.eval_id = eval_id
        self.regex_cache: dict = {}
        self.version_cache: dict = {}
        self.eligibility = EvalEligibility()
        self.metrics: Optional[AllocMetric] = None
        self.logger = logger
        # domain-sanitizer sink, e.g. port collisions among committed
        # allocs (reference context.go:84 PortCollisionEvent via
        # SendEvent -> Server.listenWorkerEvents); the worker wires this
        # to the server's event broker
        self.on_event = on_event
        self._sent_events: set = set()
        self._tg_res: dict = {}
        self._tg_vec: dict = {}

    def tg_resources(self, tg: TaskGroup):
        """Per-eval memo of tg.combined_resources() — the combine walks
        every task and deep-copies networks, and the commit loop would
        otherwise pay it once per allocation."""
        r = self._tg_res.get(id(tg))
        if r is None:
            r = self._tg_res[id(tg)] = tg.combined_resources()
        return r

    def tg_vec(self, tg: TaskGroup):
        v = self._tg_vec.get(id(tg))
        if v is None:
            v = self._tg_vec[id(tg)] = self.tg_resources(tg).vec()
        return v

    def send_event(self, event: dict) -> None:
        key = repr(sorted(event.items()))
        if key in self._sent_events:
            return  # one emission per distinct event per eval
        self._sent_events.add(key)
        if self.logger:
            self.logger.warning("scheduler event: %s", event)
        if self.on_event is not None:
            self.on_event(dict(event, eval_id=self.eval_id))

    def new_metrics(self) -> AllocMetric:
        self.metrics = AllocMetric()
        return self.metrics

    def proposed_allocs(self, node_id: str) -> List:
        """The node's allocs as they would be if the in-progress plan
        committed: state minus evictions/preemptions plus placements
        (reference context.go:176 ProposedAllocs)."""
        existing = self.snapshot.allocs_by_node_terminal(node_id, False)
        if self.plan is None:
            return existing
        removed = set()
        for a in self.plan.node_update.get(node_id, ()):
            removed.add(a.id)
        for a in self.plan.node_preemptions.get(node_id, ()):
            removed.add(a.id)
        out = [a for a in existing if a.id not in removed]
        # placements may update an existing alloc in place (inplace update):
        placed_ids = {a.id for a in self.plan.node_allocation.get(node_id, ())}
        out = [a for a in out if a.id not in placed_ids]
        out.extend(self.plan.node_allocation.get(node_id, ()))
        if self.plan.alloc_blocks:
            out.extend(self.plan.block_allocs_for_node(node_id))
        return out

    def port_index(self, node: Node, proposed: Optional[List] = None):
        """Which ports are taken on `node` for this evaluation, as a
        NetworkIndex to assign from: the one answer the per-placement
        tier and the host scorer share (structs/network.py has the
        rule). `proposed` stands in for proposed_allocs(node.id) where
        the caller has it already, or has taken its victims out."""
        return self._port_index(node, proposed, self._inflight_ports([node]))

    def port_indexes(self, nodes: List[Node]) -> List:
        """port_index() of each of `nodes`, the in-flight overlay read
        once for all of them."""
        held = self._inflight_ports(nodes)
        return [self._port_index(node, None, held) for node in nodes]

    def _inflight_ports(self, nodes: List[Node]) -> Dict[str, set]:
        # read before the snapshot's rows: an entry leaves the overlay
        # only after its commit is published, so what is not read here
        # is in no snapshot that is older either
        from ..tensor.overlay import INFLIGHT

        return INFLIGHT.ports_on([n.id for n in nodes],
                                 getattr(self.snapshot, "index", None))

    def _port_index(self, node: Node, proposed: Optional[List], held):
        from ..structs.network import NetworkIndex

        idx = NetworkIndex(node)
        idx.add_allocs(self.proposed_allocs(node.id) if proposed is None
                       else proposed)
        idx.add_taken(held.get(node.id, ()))
        return idx

    def shuffled_nodes(self, nodes: List[Node], attempt: int = 0) -> List[Node]:
        """Deterministic shuffle seeded by eval id + retry attempt
        (reference scheduler/util.go:167 shuffleNodes, seeded by eval and
        plan-attempt index so retries explore different prefixes)."""
        rng = random.Random(f"{self.eval_id}:{attempt}")
        out = list(nodes)
        rng.shuffle(out)
        return out
