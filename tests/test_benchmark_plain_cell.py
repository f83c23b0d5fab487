"""The contract of the configuration `grid-10k-plain` and of its deploy
kind, without running a cell: the files the benchmark finds by name
are there and say what `grid-10k` says of the same source, the liveness
check of `deploy/single_agent_live.py` refuses a blocked job with room
and accepts one without, and (`benchmark/tests` is not part of tier-1)
the evenness check reads a block's placements."""

import json
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from benchmark import check, layers
from benchmark.deploy import single_agent_live
from benchmark.harness import load_cell, metrics_of
from nomad_tpu import mock
from nomad_tpu.state.store import StateStore
from nomad_tpu.structs import enums
from nomad_tpu.structs.alloc import AllocBlock

ROOT = Path(__file__).resolve().parents[1]
CELL = "grid.plain.300"
GRID = json.loads((ROOT / "benchmark/configs/grid-10k.json").read_text())


def test_the_cell_loads_with_its_own_configuration_and_deploy_kind():
    bench, cell, config, traffic = load_cell(CELL, toy=False)
    assert cell == {"name": CELL, "config": "grid-10k-plain",
                    "traffic": "plain.300", "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200
    assert config["name"] == "grid-10k-plain"
    assert config["deploy"] == "single_agent_live"
    assert hasattr(single_agent_live, "deploy")
    assert config["window"]["rounds"] in (12, 16) and config["window"]["why"]
    # the traffic is upstream's job without the spread stanza
    (cls,) = traffic["classes"]
    assert traffic["jobs"] * cls["count"]["cycle"][0] == 15000
    assert "spread" not in cls and cls["ports"] == 0
    assert [cls["cpu"][0], cls["mem"][0]] == [
        config["task_ask"]["cpu"], config["task_ask"]["mem"]]
    # one toy round takes 70-85% of the toy fleet, so the second drains
    # only on what the purge freed
    _, _, toy_config, toy = load_cell(CELL, toy=True)
    share = (toy["jobs"] * toy["classes"][0]["count"]["cycle"][0]
             / toy_config["toy"]["allocations_that_fit"])
    assert 0.70 <= share <= 0.85 and toy_config["toy"]["window"] == {
        "rounds": 2}


@pytest.mark.parametrize("key", ["upstream", "node_mix", "task_ask",
                                 "job_sizes", "allocations_that_fit",
                                 "agent", "nodes", "chips", "servers",
                                 "tolerated_errors_outside_window"])
def test_it_is_the_grid_letter_for_letter(key):
    config = load_cell(CELL, toy=False)[2]
    assert config[key] == GRID[key]


def test_the_source_names_the_arm_and_every_cut_has_its_reason():
    bench, _, config, _ = load_cell(CELL, toy=False)
    entry = next(c for c in bench["configs"] if c["name"] == config["name"])
    assert entry["source"] == config["source"] != GRID["source"]
    assert len(config["source"]) <= 200 and len(entry["why"]) <= 200
    assert "withSpread = false" in config["source"]
    assert entry["reduced"] == config["reduced"] == ["servers"]
    assert all(config["reduced_why"][key] for key in config["reduced"])
    # the grid's guarantees without the evenness (no job spreads), plus
    # the liveness line the deploy kind holds the run to
    kept = [g for g in GRID["guarantees"] if "even over the racks" not in g]
    assert [g for g in config["guarantees"] if g in kept] == kept
    (new,) = [g for g in config["guarantees"] if g not in kept]
    assert "with room for all of it is placed" in new
    assert "spread.weight" not in config["assumed"]
    assert set(GRID["assumed"]) - {"spread.weight"} < set(config["assumed"])


def test_every_metric_that_lists_the_cell_has_its_reader_file():
    bench = load_cell(CELL, toy=False)[0]
    names = [m["name"] for m in metrics_of(bench, "per_layer", CELL)]
    assert len(names) == len(set(names)) == 23  # 22 until PR 37
    for m in metrics_of(bench, "per_layer", CELL):
        spec = layers.load(m["name"])
        assert (spec["unit"], spec["layer"], spec["moves"]) == (
            m["unit"], m["layer"], m["moves"])
    own = [m["name"] for m in bench["per_layer"]
           if m.get("workloads") == [CELL]]
    assert own == ["solver.wait_ms", "solver.evals_per_launch",
                   "solver.resyncs", "solve_bulk_multi_ms",
                   "solve_bulk_multi_roofline", "solver.idle_ms",
                   "solver.stale_frees"]
    # nothing of the tier it bypasses, nothing of raft
    assert not [n for n in names if n.startswith(
        ("placer.", "solve_task_group_fused", "raft."))]
    # the parked service reads as a wait among the idle gaps
    from benchmark.xplane import span_roles

    assert "solver.idle" in span_roles()["wait"]


# -- the liveness check ------------------------------------------------------

def _store_with_a_job(count: int, placed: int, status: str):
    """A job of `count` with `placed` of them live in one AllocBlock
    (`store_with_a_spread_job`, below), and one evaluation of it in
    `status`."""
    server, spec = store_with_a_spread_job([placed] + [0] * 24, count=count)
    job = server.store.snapshot().job_by_id(spec["id"])
    server.store.upsert_evals([mock.eval_for(job, status=status)])
    return server.store, job


def _deployment(store, fits: int):
    dep = single_agent_live.deploy(
        {"nodes": 10, "allocations_that_fit": fits, "toy": {}}, 1, False)
    dep.server = SimpleNamespace(
        store=store, broker=SimpleNamespace(inflight=lambda: 0),
        plan_queue=SimpleNamespace(depth=lambda: 0))
    dep.pause_broker = lambda paused: None     # no agent to tell
    return dep


@pytest.mark.parametrize("placed, whole", [(12, 0), (30, 1)])
def test_a_blocked_job_with_room_ends_the_run(placed, whole):
    """Short of placements, or whole with an evaluation left blocked
    that nothing will ever unblock: neither is a job that was placed."""
    store, _ = _store_with_a_job(30, placed, enums.EVAL_STATUS_BLOCKED)
    dep = _deployment(store, fits=40)
    with pytest.raises(single_agent_live.NotLive) as err:
        dep.quiesce()
    text = str(err.value)
    assert f"1 blocked evaluation(s) of 1 live job(s), {whole} of " in text
    assert (f"miss {30 - placed} placement(s), with {placed} live "
            "allocation(s)") in text
    assert f"room for {40 - placed} more of the 40 that fit" in text


@pytest.mark.parametrize("count, placed, fits, status, warm", [
    (30, 12, 29, enums.EVAL_STATUS_BLOCKED, False),   # no room for it
    (30, 12, 40, enums.EVAL_STATUS_COMPLETE, False),  # nobody is blocked
    (30, 12, 40, enums.EVAL_STATUS_BLOCKED, True),    # the warm-up's own
])
def test_the_liveness_check_passes(count, placed, fits, status, warm):
    store, job = _store_with_a_job(count, placed, status)
    dep = _deployment(store, fits)
    if warm:
        dep.warm_ids = {job.id}
    assert dep.quiesce() is True


def test_a_stopped_job_is_not_held_to_it():
    store, job = _store_with_a_job(30, 12, enums.EVAL_STATUS_BLOCKED)
    store.delete_job(job.id, job.namespace, purge=False)
    assert _deployment(store, fits=40).quiesce() is True


# -- the evenness check reads blocks (benchmark/tests/test_check.py's) -------

def store_with_a_spread_job(per_node_block: list, rows_on: list = (),
                            count: int = None):
    """A store of 25 nodes on 5 racks (`meta.rack` = r<i % 5>) and one
    spread job placed as the program places it since PR 34: one
    AllocBlock with `per_node_block[i]` placements on node i, and one
    Allocation row on each node of `rows_on`; the job asks for `count`
    (what is placed, unless given). -> (a stand-in for the server, the
    job's spec)."""
    store = StateStore()
    nodes = sorted((mock.node() for _ in range(25)), key=lambda n: n.id)
    for i, node in enumerate(nodes):
        node.meta["rack"] = f"r{i % 5}"
    store.upsert_nodes(nodes)
    if count is None:
        count = sum(per_node_block) + len(rows_on)
    job = mock.job()
    job.task_groups[0].count = count
    res = job.task_groups[0].tasks[0].resources
    res.cpu, res.memory_mb, res.networks = 100, 64, []
    store.upsert_job(job)
    vec = mock.alloc(job, nodes[0]).allocated_vec
    on = [i for i, k in enumerate(per_node_block) if k]
    block = AllocBlock(
        id="blk", eval_id="ev", namespace=job.namespace, job_id=job.id,
        job=job, job_version=job.version,
        task_group=job.task_groups[0].name,
        name_indices=np.arange(sum(per_node_block), dtype=np.int64),
        node_ids=[nodes[i].id for i in on],
        node_names=[nodes[i].name for i in on],
        counts=np.array([per_node_block[i] for i in on], np.int64),
        allocated_vec=vec)
    rows = [mock.alloc(job, nodes[i]) for i in rows_on]
    store.upsert_plan_results(rows, alloc_blocks=[block], job=job)
    spec = {"id": job.id, "type": "service", "count": count, "cpu": 100,
            "mem": 64, "ports": 0,
            "spread": {"attribute": "${meta.rack}", "weight": 50}}
    return SimpleNamespace(store=store), spec


@pytest.mark.parametrize("rows_on", [[], [10, 11]])
def test_the_evenness_check_counts_a_blocks_placements(rows_on):
    server, spec = store_with_a_spread_job([2] * 10 + [0] * 15, rows_on)
    snap = server.store.snapshot()
    ids = sorted(n.id for n in snap.nodes())
    counts, per_job = check.placements_per_node(
        snap, {spec["id"]}, {nid: i for i, nid in enumerate(ids)})
    want = [2] * 10 + [0] * 15
    for i in rows_on:
        want[i] += 1
    assert counts.tolist() == want
    assert sorted(per_job[spec["id"]]) == sorted(
        list(range(10)) * 2 + rows_on)
