"""FSM: replicated commands -> state-store mutations
(reference nomad/fsm.go:228 applying ~60 raft message types).

A command is ("op", args) where op names a StateStore mutation method.
Payloads are deep-copied before apply so replicas never share mutable
objects, and because every replica applies the identical command
sequence, store generation numbers (indexes) agree across the cluster.

RaftStore presents the StateStore surface: reads hit the local store,
mutations propose through the raft node and block until committed and
applied locally — the write path every core.Server subsystem already
uses, so replication slots in without touching them.
"""

from __future__ import annotations

import copy
import time
from typing import Any, List

MUTATIONS = {
    "upsert_node", "upsert_nodes", "update_node_status",
    "update_nodes_status", "update_node_eligibility",
    "update_node_drain", "delete_node",
    "upsert_job", "delete_job", "update_job_status",
    "upsert_evals", "delete_evals",
    "upsert_allocs", "update_allocs_from_client",
    "update_alloc_desired_transitions",
    "upsert_plan_results", "upsert_plan_results_batch",
    "upsert_deployment", "update_deployment_status", "delete_deployment",
    "upsert_acl_policy", "delete_acl_policy",
    "upsert_acl_token", "delete_acl_token",
    "upsert_acl_role", "delete_acl_role",
    "upsert_auth_method", "delete_auth_method",
    "upsert_binding_rule", "delete_binding_rule",
    "gc_expired_acl_tokens", "upsert_region", "delete_region",
    "set_scheduler_configuration",
    "upsert_one_time_token", "delete_one_time_token",
    "take_one_time_token", "gc_one_time_tokens",
    "append_scaling_event",
    "upsert_variable", "delete_variable",
    "upsert_volume", "delete_volume", "reap_volume_claims",
    "upsert_node_pool", "delete_node_pool",
    "upsert_namespace", "delete_namespace",
    "upsert_service_registrations", "delete_service_registrations",
    "delete_services_by_alloc",
    "gc_terminal_allocs", "compact", "restore_dump",
}


def _refuse_wallclock() -> float:
    raise RuntimeError(
        "wall-clock read during a replicated apply: a timestamped command "
        "reached the store without an explicit ts — the proposer must stamp "
        "it (RaftStore fills ts for every TIMESTAMPED op)")


class FSM:
    def __init__(self, store):
        self.store = store
        # A replica applying the shared log must never stamp local time:
        # replace the store's ts-fallback clock with a guard so any
        # mutator that would read wall clock fails loudly instead of
        # silently diverging from its peers.
        store._clock = _refuse_wallclock

    def apply(self, command: tuple) -> Any:
        op, args, kwargs = command
        if op == "noop":
            return None  # leader barrier entry (raft/node.py _become_leader_locked)
        if op not in MUTATIONS:
            raise ValueError(f"unknown FSM op {op!r}")
        if op in TIMESTAMPED and kwargs.get("ts") is None:
            # catch the divergence at the boundary, with the op name,
            # rather than via the _clock guard deep in a mutator
            raise ValueError(
                f"replicated {op!r} command carries no ts: replicas "
                "would each stamp their own apply time and diverge")
        fn = getattr(self.store, op)
        # each replica must own its objects
        args = copy.deepcopy(args)
        kwargs = copy.deepcopy(kwargs)
        return fn(*args, **kwargs)


# Mutations that stamp wall-clock times must receive the time from the
# proposer inside the replicated command: a follower replaying the log at
# catch-up time would otherwise stamp replay-time and diverge from the
# leader on time-gated decisions (gc_terminal_allocs cutoffs). The
# reference embeds times in the raft request structs for the same reason.
TIMESTAMPED = {
    "gc_expired_acl_tokens", "gc_one_time_tokens",
    "take_one_time_token",
    "upsert_evals", "upsert_allocs", "update_allocs_from_client",
    "upsert_plan_results", "upsert_plan_results_batch", "update_node_status",
    "update_nodes_status",
    "update_alloc_desired_transitions",
}


class RaftStore:
    """StateStore facade: local reads, replicated writes."""

    def __init__(self, store, raft_node):
        self._store = store
        self._raft = raft_node

    def __getattr__(self, name: str):
        if name in MUTATIONS:
            def propose(*args, **kwargs):
                if name in TIMESTAMPED and kwargs.get("ts") is None:
                    kwargs["ts"] = time.time()
                return self._raft.apply((name, args, kwargs))

            return propose
        return getattr(self._store, name)

    # A raft-backed store can start a mutation without waiting for its
    # commit (propose_async/wait_applied); a plain StateStore cannot.
    # The plan applier's commit thread probes this to decide whether
    # commit rounds may overlap.
    can_propose_async = True

    def propose_async(self, name: str, *args, **kwargs):
        """Start a replicated mutation without waiting for its commit:
        returns a proposal handle for wait_applied. Timestamp stamping
        matches the synchronous propose path (the ts must be fixed at
        propose time, not apply time — see TIMESTAMPED). Because
        proposal order at the raft node is log order, a single proposer
        pipelining rounds through this API keeps FSM apply order equal
        to its propose order."""
        if name not in MUTATIONS:
            raise AttributeError(f"{name} is not a replicated mutation")
        if name in TIMESTAMPED and kwargs.get("ts") is None:
            kwargs["ts"] = time.time()
        return self._raft.apply_async((name, args, kwargs))

    def wait_applied(self, prop, timeout: float = 30.0):
        """Block until a propose_async proposal is committed and
        applied locally; returns the FSM result (the raft index for
        store mutations)."""
        return self._raft.apply_wait(prop, timeout)

    # explicit read-path passthroughs used as attributes (not calls)
    @property
    def latest_index(self) -> int:
        return self._store.latest_index
