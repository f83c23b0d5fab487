"""Tier-1 gate for the nomadflow prong (ANALYSIS.md "nomadflow").

Four contracts:
- each static flow rule flags its flow_bad.py shapes (exact detail
  sets) and stays silent on the disciplined flow_clean.py counterparts;
- the repo itself carries ZERO flow-rule findings and none are
  baselined — findings are fixed in code, never allowlisted;
- the shadow-state differential sanitizer replays every delta kind the
  store emits (rows, columnar blocks, promotions, GC, client updates,
  restore→resync) into a replica whose fingerprint — usage columns
  included — is bit-exact against a fresh MVCC snapshot rebuild, and a
  seeded dropped/stale/phantom delta trips the compare;
- the ``event_flow`` modelcheck scenario holds at a pinned seed, and
  replaying it with a delta kind suppressed (the docstring's promise)
  proves the compare actually bites under an adversarial schedule.
"""

from pathlib import Path

import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.analysis import load_baseline, run_analysis
from nomad_tpu.analysis.rules_flow import FLOW_RULES
from nomad_tpu.analysis.shadow import ShadowTracker, usage_columns
from nomad_tpu.core.events import EventBroker
from nomad_tpu.core.metrics import REGISTRY
from nomad_tpu.state import StateStore
from nomad_tpu.state.persist import dump_store, restore_store
from nomad_tpu.structs import enums
from nomad_tpu.structs.alloc import AllocBlock, Allocation
from nomad_tpu.structs.evaluation import Evaluation

REPO = Path(__file__).resolve().parent.parent
FIXTURES = REPO / "tests" / "fixtures" / "analysis"
POSITIVE = FIXTURES / "positive"
NEGATIVE = FIXTURES / "negative"


def _details(findings):
    return sorted(f.detail for f in findings)


def _run(path, rules):
    return run_analysis(paths=[path], rules=list(rules), root=path.parent)


# --- static rules: per-rule positive/negative fixtures -------------------

def test_mutation_without_delta_fixture():
    found = _run(POSITIVE / "flow_bad.py", ["flow-mutation-without-delta"])
    assert _details(found) == \
        ["delete_node:_nodes", "upsert_evals:_evals"]
    # the interprocedural finding points at the WRITE in the helper but
    # is attributed to the FSM-reachable mutator root
    helper = next(f for f in found if f.detail == "upsert_evals:_evals")
    assert helper.context.endswith(":Store.upsert_evals")


def test_publish_before_commit_fixture():
    found = _run(POSITIVE / "flow_bad.py", ["flow-publish-before-commit"])
    assert _details(found) == \
        ["listeners-before-index", "publish-before:upsert_node"]


def test_payload_narrowing_fixture():
    found = _run(POSITIVE / "flow_bad.py", ["flow-delta-payload-narrowing"])
    assert _details(found) == \
        ["narrowed:Node:status", "narrowed:Node:weight"]


def test_resync_gap_fixture():
    found = _run(POSITIVE / "flow_bad.py", ["flow-resync-gap-unhandled"])
    assert _details(found) == ["gap-unchecked", "gap-unhandled"]
    unchecked = next(f for f in found if f.detail == "gap-unchecked")
    assert unchecked.context.endswith(":drain_unchecked")


def test_unkeyed_delta_fixture():
    found = _run(POSITIVE / "flow_bad.py", ["flow-unkeyed-delta"])
    assert _details(found) == ["index-0:Event", "index-0:_publish_shard"]


def test_clean_fixture_is_silent_under_every_flow_rule():
    assert _run(NEGATIVE / "flow_clean.py", FLOW_RULES) == []


# --- repo sweep: fixed in code, never baselined --------------------------

def test_repo_is_clean_under_flow_rules():
    findings = run_analysis(rules=list(FLOW_RULES))
    assert findings == [], [f.render() for f in findings]


def test_no_flow_findings_are_baselined():
    assert not [k for k in load_baseline() if k[0] in FLOW_RULES]


def test_san_ok_comment_suppresses(tmp_path):
    bad = ("def bootstrap(ring, topic, payload):\n"
           "    ring.append(Event(0, 0, topic, 'seed', '', payload))"
           "  # san-ok: pre-first-commit seed event\n")
    p = tmp_path / "ringy.py"
    p.write_text(bad)
    assert _run(p, ["flow-unkeyed-delta"]) == []
    p.write_text(bad.replace("  # san-ok: pre-first-commit seed event",
                             ""))
    flagged = _run(p, ["flow-unkeyed-delta"])
    assert [f.detail for f in flagged] == ["index-0:Event"]


# --- usage columns: the shared fingerprint reduction ---------------------

def test_usage_columns_order_invariant_and_excludes_terminal():
    vec = lambda *vals: np.asarray(vals, np.float64).tobytes()  # noqa: E731
    entries = {
        "a1": (1, "running", "run", "n1", vec(1.0, 2.0)),
        "a2": (2, "pending", "run", "n1", vec(0.5, 0.25)),
        "a3": (3, "complete", "run", "n2", vec(9.0, 9.0)),   # terminal
        "a4": (4, "running", "run", "n2", vec(4.0, 0.0)),
    }
    u = usage_columns(entries)
    assert set(u) == {"n1", "n2"}
    assert np.frombuffer(u["n1"], np.float64).tolist() == [1.5, 2.25]
    assert np.frombuffer(u["n2"], np.float64).tolist() == [4.0, 0.0]
    # insertion order must not perturb a single float bit
    reordered = dict(reversed(list(entries.items())))
    assert usage_columns(reordered) == u
    assert usage_columns({}) == {}


# --- shadow replica: runtime differential --------------------------------

@pytest.fixture
def tracked():
    """A private installed tracker over a fresh (store, broker) pair —
    stacks over the GLOBAL one when NOMAD_TPU_SAN=1. every=1: compare
    on every single commit."""
    store = StateStore()
    broker = EventBroker(store)
    tracker = ShadowTracker(every=1)
    tracker.install()
    rep = tracker.attach(store, broker)
    try:
        yield store, broker, tracker, rep
    finally:
        tracker.uninstall()


def _alloc(aid, nid, fill):
    a = Allocation(id=aid, node_id=nid, job_id="fj", eval_id="fe")
    a.allocated_vec = np.full_like(a.allocated_vec, float(fill))
    return a


def test_shadow_replays_rows_updates_and_deletes(tracked):
    store, _, tracker, rep = tracked
    for i in range(3):
        store.upsert_node(mock.node())
    store.upsert_evals([Evaluation(id=f"fe{i}", job_id="fj")
                        for i in range(4)])
    store.upsert_allocs([_alloc(f"fa{i}", "fn0", i + 1)
                         for i in range(5)])
    store.update_allocs_from_client([Allocation(
        id="fa2", client_status=enums.ALLOC_CLIENT_COMPLETE)])
    store.delete_evals(["fe1", "fe3"])
    store.gc_terminal_allocs(before_index=store._index + 1)
    assert rep.force_compare() is None
    assert tracker.violations == []
    # with every=1 each commit compared; the replay kept exact pace
    assert rep.commits >= 6 and rep.compares >= rep.commits
    assert "fa2" not in rep.allocs          # orphan terminal row GCed
    assert REGISTRY.get("nomad.events.delta_lag") == 0.0


def test_shadow_expands_blocks_and_honors_promotion(tracked):
    store, _, tracker, rep = tracked
    nodes = []
    for _ in range(4):
        n = mock.node()
        n.compute_class()
        nodes.append(n)
        store.upsert_node(n)
    job = mock.batch_job()
    job.task_groups[0].count = 8
    store.upsert_job(job)
    vec = np.zeros_like(mock.alloc(job, nodes[0]).allocated_vec)
    vec[0] = 50.0
    vec[1] = 32.0
    block = AllocBlock(
        id="blk-sh", eval_id="ev-sh", namespace=job.namespace,
        job_id=job.id, job=job, job_version=job.version,
        task_group=job.task_groups[0].name,
        name_indices=np.arange(8, dtype=np.int64),
        node_ids=[nodes[0].id, nodes[1].id],
        node_names=[nodes[0].name, nodes[1].name],
        counts=np.array([4, 4], dtype=np.int64),
        allocated_vec=vec,
    )
    store.upsert_plan_results([], alloc_blocks=[block], job=job)
    assert rep.force_compare() is None
    assert len(rep.allocs) == 8             # columnar payload expanded
    # promote one position into a real row via a client update: the
    # row event must override the block expansion, once
    target = store.snapshot().allocs_by_job(job.id)[0]
    store.update_allocs_from_client([Allocation(
        id=target.id, client_status=enums.ALLOC_CLIENT_COMPLETE)])
    assert target.id in rep._promoted
    assert rep.force_compare() is None
    assert tracker.violations == []


def test_shadow_resyncs_through_restore(tracked):
    store, _, tracker, rep = tracked
    store.upsert_node(mock.node())
    store.upsert_allocs([_alloc("fa0", "fn0", 2)])
    before = rep.resyncs
    # operator restore truncates every ring: the contract answer is a
    # full snapshot rebuild, never incremental patching
    restore_store(store, dump_store(store))
    store.upsert_node(mock.node())
    assert rep.resyncs > before
    assert rep.force_compare() is None
    assert tracker.violations == []


def test_shadow_catches_dropped_delta(tracked):
    store, _, tracker, rep = tracked
    n1, n2 = mock.node(), mock.node()
    store.upsert_node(n1)
    store.upsert_node(n2)
    rep.nodes.pop(n2.id)                    # the seeded missed delta
    msg = rep.force_compare()
    assert msg is not None and "never delivered" in msg
    assert [v.kind for v in tracker.violations] == ["shadow-divergence"]
    with pytest.raises(AssertionError, match="nomadflow violations"):
        tracker.check()


def test_shadow_catches_stale_and_phantom_entries(tracked):
    store, _, tracker, rep = tracked
    ev = Evaluation(id="fe0", job_id="fj")
    store.upsert_evals([ev])
    rep.evals["fe0"] = (0, "zombie")        # reordered overwrite
    rep.evals["ghost"] = (1, "pending")     # delta for a row never stored
    msg = rep.force_compare()
    assert msg is not None
    assert "stale" in msg and "absent from the store" in msg
    report = tracker.report()
    assert "1 violation(s)" in report and "shadow-divergence" in report


def test_inactive_tracker_attach_is_a_noop():
    store = StateStore()
    broker = EventBroker(store)
    tracker = ShadowTracker()
    assert tracker.attach(store, broker) is None
    store.upsert_node(mock.node())          # nothing listening, no trip
    assert tracker.verify_all() == []
    assert tracker.stats()["replicas"] == 0


# --- the modelcheck scenario ---------------------------------------------

def test_event_flow_scenario_holds():
    from nomad_tpu.analysis import modelcheck as mc
    r = mc.run_scenario("event_flow", seed=0)
    assert r.ok, r.error


def test_event_flow_scenario_catches_suppressed_delta_kind(monkeypatch):
    """The pinned negative replay the scenario docstring promises:
    suppress one delta kind (alloc-upsert) in the replica's replay and
    the fingerprint compare must report the divergence. Pinned to a
    seed whose schedule runs the restore leg before the alloc writer,
    so the resync cannot mask the dropped deltas."""
    from nomad_tpu.analysis import modelcheck as mc
    from nomad_tpu.analysis import shadow

    real_apply = shadow.ShadowReplica._apply

    def dropping(self, e):
        if e.type == "alloc-upsert":
            return
        real_apply(self, e)

    monkeypatch.setattr(shadow.ShadowReplica, "_apply", dropping)
    r = mc.run_scenario("event_flow", seed=0)
    assert not r.ok
    assert "diverged" in str(r.error) or "never delivered" in str(r.error)
