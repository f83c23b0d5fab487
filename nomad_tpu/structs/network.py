"""Port accounting and assignment — the NetworkIndex equivalent
(reference nomad/structs/network.go, 830 LoC NetworkIndex; consumed by
scheduler/rank.go:226-249 and structs/funcs.go:141 AllocsFit).

Design differences from the reference, TPU-first rationale:

- Exhaustion ("are there enough free dynamic port slots?") is a dense
  count that lives in the comparable-resources vector (resources.R_PORTS)
  so the device kernels see it as just another fit dimension — no
  per-node host loop at solve time.
- Exact port *numbers* (reserved-port collisions, dynamic assignment)
  are host-side and only touched for task groups that actually ask for
  ports, on the nodes their placements land on.
- Which numbers are taken on a node, for one evaluation, has ONE
  answer, EvalContext.port_index(node): the node's agent-reserved
  ports, the ports of ctx.proposed_allocs (the evaluation's snapshot
  less what its plan stops, plus its plan's own rows), and the ports
  the in-flight overlay holds there (tensor/overlay.py ports_on):
  those of every evaluation of this server that has chosen its ports
  and whose plan is not applied yet, and of every plan applied since
  this evaluation's snapshot was taken. The per-placement tier chooses
  a group's ports under its solve lock and registers them with the
  usage they belong to before it lets go (tensor/placer.py
  _assign_ports), and the host scorer (scheduler/rank.py) reads the
  same index, so two evaluations in flight on one server do not hand
  out the same number on a node: before, every one of them took the
  lowest free port of its own snapshot on the same half-filled nodes
  and the applier threw the rows away.
- The serialized plan applier still re-checks every row that carries a
  port (allocs_fit -> check_port_collisions) and rejects the node on a
  collision (counted: nomad.plan.port_collisions). Nothing above
  relaxes it; it is what guards the cases the overlay cannot see:
  evaluations on different servers, a snapshot that outlives the
  overlay's TTL, a host-scored remainder racing another one between
  its read and its registration.
- Dynamic assignment is deterministic (lowest free port first, for a
  given taken set) and the numbers ride in the plan, so a replayed plan
  or a replica applying the same log holds identical ports.
"""

from __future__ import annotations

from typing import (Collection, Iterable, List, Optional, Sequence, Set,
                    Tuple)

from .alloc import AllocatedPort


class NetworkIndex:
    """Used-port view of one node (reference network.go NetworkIndex)."""

    def __init__(self, node):
        res = node.resources
        self.min_dyn = res.min_dynamic_port
        self.max_dyn = res.max_dynamic_port
        self.used: Set[int] = set(node.reserved.reserved_ports)
        self.collision = False           # reference: SetAllocs collision flag
        self.colliding_ports: List[int] = []
        # no dynamic port below this one is free (used only grows)
        self._lowest = self.min_dyn
        # add_taken brought a port that no allocation counted here held
        self.inflight = False

    # -- building up usage --

    def add_ports(self, ports: Iterable[int]) -> None:
        for p in ports:
            if p in self.used:
                self.collision = True
                self.colliding_ports.append(p)
            self.used.add(p)

    def add_taken(self, ports: Collection[int]) -> None:
        """Ports taken on the node that are no allocation of the view
        yet (the in-flight overlay's). They may repeat ones already
        counted (an evaluation's own rows are in its plan and in its
        entry), which is no collision."""
        if not self.used.issuperset(ports):
            self.inflight = True
            self.used.update(ports)

    def add_allocs(self, allocs: Sequence) -> None:
        """Register ports of non-terminal allocs (reference network.go
        SetAllocs: client-terminal allocs free their ports)."""
        for a in allocs:
            if not a.should_count_for_usage():
                continue
            self.add_ports(p.value for p in a.allocated_ports)

    # -- assignment (reference network.go AssignPorts) --

    def assign_ports(self, ask) -> Tuple[List[AllocatedPort], str]:
        """Assign the resource ask's reserved + dynamic ports against this
        index. Returns (ports, "") on success or ([], reason) on failure;
        on success the assigned ports are recorded as used."""
        out: List[AllocatedPort] = []
        taken: Set[int] = set()

        for label, port in ask.reserved_port_asks():
            if port in self.used or port in taken:
                return [], f"reserved port collision {label}={port}"
            taken.add(port)
            out.append(AllocatedPort(label=label, value=port))

        for net in ask.networks:
            for label in net.dynamic_ports:
                port = self._next_free(taken)
                if port is None:
                    return [], "dynamic port selection failed"
                taken.add(port)
                out.append(AllocatedPort(label=label, value=port))

        self.used |= taken
        return out, ""

    def _next_free(self, taken: Set[int]) -> Optional[int]:
        p = self._lowest
        while p <= self.max_dyn and p in self.used:
            p += 1
        self._lowest = p
        while p <= self.max_dyn:
            if p not in self.used and p not in taken:
                return p
            p += 1
        return None


def check_port_collisions(node, allocs: Sequence) -> List[int]:
    """Collisions among the given allocs' assigned ports on this node
    (the AllocsFit port check, reference funcs.go:155-170). Returns the
    colliding port numbers (empty = fine)."""
    idx = NetworkIndex(node)
    idx.add_allocs(allocs)
    return idx.colliding_ports
