"""The contract of the configuration `grid-10k-port` and of its deploy
kind, without running a cell (`benchmark/tests` is not part of tier-1;
its `test_port_cell.py` runs the cell at --toy size): the files the
benchmark finds by name are there and say what `grid-10k` says of the
same source, with the job's network kept; the plain reference
`benchmark/reference/ports.py` finds each kind of violation of the port
guarantee; and `deploy/single_agent_ports.py` refuses, on a doctored
store, a job short with room, a `failed` evaluation, and a port twice,
missing, surplus, reserved or out of range."""

import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from benchmark import layers
from benchmark.deploy import single_agent_ports
from benchmark.harness import load_cell, metrics_of
from benchmark.reference import ports as reference
from nomad_tpu import mock
from nomad_tpu.state.store import StateStore
from nomad_tpu.structs import enums
from nomad_tpu.structs.alloc import AllocatedPort
from nomad_tpu.structs.resources import NetworkResource

ROOT = Path(__file__).resolve().parents[1]
CELL = "grid.port.spread.300"
GRID = json.loads((ROOT / "benchmark/configs/grid-10k.json").read_text())
OWN = ["placer.ports_ms", "placer.rows_ms",
       "placer.port_nodes_inflight_pct", "applier.port_collisions"]


def test_the_cell_loads_with_its_own_configuration_and_deploy_kind():
    bench, cell, config, traffic = load_cell(CELL, toy=False)
    assert cell == {"name": CELL, "config": "grid-10k-port",
                    "traffic": "spread.port.300", "chips": 1,
                    "why": cell["why"]}
    assert len(cell["why"]) <= 200
    assert config["name"] == "grid-10k-port"
    assert config["deploy"] == "single_agent_ports"
    assert hasattr(single_agent_ports, "deploy")
    assert 1 <= config["window"]["rounds"] <= 8 and config["window"]["why"]
    # the traffic is grid.spread.300's with two dynamic ports a task
    spread = json.loads(
        (ROOT / "benchmark/traffic/spread.300.json").read_text())
    for name, mix in (("full", traffic), ("toy", traffic["toy"])):
        (cls,) = mix["classes"]
        assert cls["ports"] == 2 and cls["spread"], name
    for key in ("generator", "jobs", "stop_share", "submit_threads",
                "trace_seconds", "warm"):
        assert traffic[key] == spread[key]
    (cls,), (theirs,) = traffic["classes"], spread["classes"]
    assert {**cls, "ports": 0} == theirs
    assert traffic["jobs"] * cls["count"]["cycle"][0] == 15000
    assert config["task_ask"] == {
        "cpu": cls["cpu"][0], "mem": cls["mem"][0],
        "networks": {"mode": "host", "dynamic_ports": cls["ports"]}}
    # toy: two of the ask a node, and a round takes a part of the fleet
    _, _, toy_config, toy = load_cell(CELL, toy=True)
    assert toy_config["toy"]["allocations_that_fit"] == 2 * toy_config[
        "toy"]["nodes"]
    assert toy["jobs"] * toy["classes"][0]["count"]["cycle"][0] < 256


@pytest.mark.parametrize("key", ["upstream", "node_mix", "job_sizes",
                                 "allocations_that_fit", "agent", "nodes",
                                 "chips", "servers", "reduced_why",
                                 "tolerated_errors_outside_window"])
def test_it_is_the_grid_letter_for_letter(key):
    config = load_cell(CELL, toy=False)[2]
    assert config[key] == GRID[key]


def test_the_source_names_the_network_and_the_guarantees_are_written_out():
    bench, _, config, _ = load_cell(CELL, toy=False)
    entry = next(c for c in bench["configs"] if c["name"] == config["name"])
    sources = [c["source"] for c in bench["configs"]]
    assert entry["source"] == config["source"]
    assert sources.count(config["source"]) == 1
    assert len(config["source"]) <= 200 and len(entry["why"]) <= 200
    assert "network kept" in config["source"]
    assert "2 dynamic ports" in config["source"]
    assert entry["reduced"] == config["reduced"] == ["servers"]
    # the grid's guarantees, the port line written out in full, and the
    # placement line the deploy kind holds the run to
    kept = [g for g in GRID["guarantees"]
            if not g.startswith("no port number")]
    assert [g for g in config["guarantees"] if g in kept] == kept
    ports, placed = [g for g in config["guarantees"] if g not in kept]
    for words in ("exactly the ports it asked", "dynamic range",
                  "outside its reserved ports", "no value twice on a node",
                  "on every node", "at every round's end"):
        assert words in ports
    assert "with room for all of it is placed" in placed
    assert "ends `failed`" in placed
    assert set(GRID["assumed"]) < set(config["assumed"])
    assert "network is kept" in config["assumed"]["network"]
    assert {"port.labels", "window.rounds"} <= set(config["assumed"])
    assert "mock.Job" in config["network_kept"]["mock_job"]


def test_every_metric_that_lists_the_cell_has_its_reader_file():
    bench = load_cell(CELL, toy=False)[0]
    mine = metrics_of(bench, "per_layer", CELL)
    names = [m["name"] for m in mine]
    assert len(names) == len(set(names))
    for m in mine:
        spec = layers.load(m["name"])
        assert (spec["unit"], spec["layer"], spec["moves"]) == (
            m["unit"], m["layer"], m["moves"])
    # its own: listed for it and not for the grid's block cell (a
    # later cell with rows or ports may be listed beside it)
    own = [m["name"] for m in bench["per_layer"]
           if CELL in m.get("workloads", ())
           and "grid.spread.300" not in m["workloads"]]
    assert own == OWN and not any(
        (layers.HERE / f"{name}.py").exists() for name in OWN)
    # what grid.spread.300 reports this cell reports, less the one
    # reader that is bound to a window shorter than its trace and the
    # block arm's span, which a group of rows never opens
    theirs = {m["name"] for m in metrics_of(bench, "per_layer",
                                            "grid.spread.300")}
    assert theirs - set(names) <= {"placer.host_locked_pct",
                                   "placer.block_ms"}
    assert set(names) - theirs == set(OWN)
    # nothing of the solver service, nothing of raft
    assert not [n for n in names if n.startswith(
        ("solver.", "solve_bulk", "raft."))]


def test_the_new_readers_read_and_find_nothing_in_the_parent():
    obs = {"spans": {"durations": {"placer.ports": [0.001, 0.003, 0.002],
                                   "placer.rows": [0.004]}, "self": {}},
           "counters": {"applier": {"port_collisions": 0},
                        "registry": {"nomad.placer.port_nodes": 200,
                                     "nomad.placer.port_nodes_inflight": 50}}}
    got = {k: v["value"] for k, v in layers.read_all(OWN, obs).items()}
    assert got == {"placer.ports_ms": pytest.approx(2.0),
                   "placer.rows_ms": pytest.approx(4.0),
                   "placer.port_nodes_inflight_pct": pytest.approx(25.0),
                   "applier.port_collisions": 0.0}
    # a program without the spans and counters (the parent)
    parent = {"spans": {"durations": {}, "self": {}},
              "counters": {"applier": {"nodes_rejected": 3}, "registry": {}}}
    assert layers.read_all(OWN, parent) == {}


# -- the plain reference -----------------------------------------------------

NODES = {"n1": {"min": 20000, "max": 32000, "reserved": {22}},
         "n2": {"min": 20000, "max": 32000, "reserved": set()}}


def held(alloc_id, node, ports, dynamic=("p0", "p1"), static=()):
    return {"id": alloc_id, "node": node,
            "ports": [list(p) for p in ports], "dynamic": list(dynamic),
            "static": [list(s) for s in static]}


def test_the_reference_passes_a_sound_state_and_counts_what_it_read():
    allocs = [held("a", "n1", [("p0", 20000), ("p1", 20001)]),
              held("b", "n1", [("p0", 20002), ("p1", 20003)]),
              held("c", "n2", [("p0", 20000), ("p1", 20001)]),
              held("d", "n2", [], dynamic=()),
              held("e", "n2", [("web", 8080)], dynamic=(),
                   static=[("web", 8080)])]
    assert reference.violations(NODES, allocs) == []
    assert reference.census(NODES, allocs) == {
        "allocations": 5, "ports": 7, "asked": 7, "nodes": 2,
        "nodes_holding": 2}


@pytest.mark.parametrize("alloc, words", [
    (held("x", "n1", [("p0", 20000), ("p1", 20005)]), "port 20000 twice"),
    (held("x", "n1", [("p0", 20005)]), "missing the port it asked as p1"),
    (held("x", "n1", []), "missing the port it asked as p0"),
    (held("x", "n1", [("p0", 20005), ("p1", 20006), ("p2", 20007)]),
     "surplus port p2=20007"),
    (held("x", "n1", [("p0", 19999), ("p1", 20006)]),
     "outside the node's dynamic range"),
    (held("x", "n1", [("p0", 32001), ("p1", 20006)]),
     "outside the node's dynamic range"),
    (held("x", "n1", [("web", 22)], dynamic=(), static=[("web", 22)]),
     "is reserved on n1"),
    (held("x", "n1", [("web", 81)], dynamic=(), static=[("web", 80)]),
     "asked 80, holds 81"),
    (held("x", "n3", [("p0", 20005), ("p1", 20006)]),
     "a node the cluster does not have"),
])
def test_the_reference_finds_each_kind_of_violation(alloc, words):
    sound = held("a", "n1", [("p0", 20000), ("p1", 20001)])
    (line,) = reference.violations(NODES, [sound, alloc])[:1]
    assert words in line
    # ... and by that one limit alone: the other allocation is sound
    assert all("x" in l or "twice" in l
               for l in reference.violations(NODES, [sound, alloc]))


# -- the deploy kind on a doctored store -------------------------------------

def port_job(count: int):
    job = mock.job()
    tg = job.task_groups[0]
    tg.count = count
    tg.tasks[0].resources.networks = [
        NetworkResource(dynamic_ports=["p0", "p1"])]
    return job


def store_with_a_port_job(count: int, placed: int, status: str,
                          doctor=None):
    """Two nodes and a job of `count` with `placed` live allocations,
    each with the two ports it asked (20000 + 2i, 20001 + 2i on its
    node), and one evaluation of the job in `status`; `doctor(allocs)`
    edits the rows before they are committed."""
    store = StateStore()
    nodes = sorted((mock.node() for _ in range(2)), key=lambda n: n.id)
    nodes[0].reserved.reserved_ports = [20999]
    store.upsert_nodes(nodes)
    job = port_job(count)
    store.upsert_job(job)
    allocs = []
    for i in range(placed):
        a = mock.alloc(job, nodes[i % 2], index=i)
        k = 2 * (i // 2)
        a.allocated_ports = [AllocatedPort(label="p0", value=20000 + k),
                             AllocatedPort(label="p1", value=20001 + k)]
        allocs.append(a)
    if doctor is not None:
        doctor(allocs)
    store.upsert_plan_results(allocs, job=job)
    store.upsert_evals([mock.eval_for(job, status=status)])
    return store, job


def deployment(store, fits: int):
    dep = single_agent_ports.deploy(
        {"nodes": 2, "allocations_that_fit": fits, "toy": {}}, 1, False)
    dep.server = SimpleNamespace(
        store=store, broker=SimpleNamespace(inflight=lambda: 0),
        plan_queue=SimpleNamespace(depth=lambda: 0))
    dep.pause_broker = lambda paused: None     # no agent to tell
    return dep


def test_a_sound_round_passes_and_says_what_it_read(capsys):
    store, _ = store_with_a_port_job(8, 8, enums.EVAL_STATUS_COMPLETE)
    dep = deployment(store, fits=10)
    assert dep.quiesce() is True and dep.quiesce() is True
    out = capsys.readouterr().out.splitlines()
    assert out[0] == ("[ports] round=0 jobs=1 allocations=8 ports=16 "
                      "asked=16 nodes=2 nodes_holding=2 violations=0")
    assert out[1].startswith("[ports] round=1 ")


def test_a_job_short_with_room_ends_the_run():
    store, job = store_with_a_port_job(8, 5, enums.EVAL_STATUS_COMPLETE)
    with pytest.raises(single_agent_ports.NotHeld) as err:
        deployment(store, fits=10).quiesce()
    text = str(err.value)
    assert text.startswith("placement: 1 of 1 live job(s) are not whole")
    assert "miss 3 allocation(s) with 5 placed and room for 5 more" in text
    # without room for what it misses the job is held to nothing
    assert deployment(store, fits=7).quiesce() is True
    # nor is the warm-up's own job, nor one that was stopped
    dep = deployment(store, fits=10)
    dep.warm_ids = {job.id}
    assert dep.quiesce() is True
    store.delete_job(job.id, job.namespace, purge=False)
    assert deployment(store, fits=10).quiesce() is True


def test_a_failed_evaluation_ends_the_run():
    store, job = store_with_a_port_job(8, 8, enums.EVAL_STATUS_FAILED)
    dep = deployment(store, fits=10)
    with pytest.raises(single_agent_ports.NotHeld) as err:
        dep.quiesce()
    assert str(err.value).startswith(
        "attempts: 1 evaluation(s) of 1 live job(s) ended `failed`")
    # the run has failed: stop() will end it at once (exit code 1)
    assert dep.broken is True
    dep = deployment(store, fits=10)
    dep.warm_ids = {job.id}
    assert dep.quiesce() is True and dep.broken is False


def _twice(allocs):
    allocs[2].allocated_ports[0].value = allocs[0].allocated_ports[1].value


def _missing(allocs):
    del allocs[3].allocated_ports[1]


def _out_of_range(allocs):
    allocs[1].allocated_ports[0].value = 32001


def _reserved(allocs):
    allocs[0].allocated_ports[0].value = 20999


def _surplus(allocs):
    allocs[1].allocated_ports.append(AllocatedPort(label="p2", value=20500))


@pytest.mark.parametrize("doctor, words", [
    (_twice, "twice"), (_missing, "missing the port it asked as p1"),
    (_out_of_range, "outside the node's dynamic range"),
    (_reserved, "is reserved on"), (_surplus, "surplus port p2=20500")])
def test_a_broken_port_guarantee_ends_the_run(doctor, words, capsys):
    store, _ = store_with_a_port_job(8, 8, enums.EVAL_STATUS_COMPLETE,
                                     doctor)
    with pytest.raises(single_agent_ports.NotHeld) as err:
        deployment(store, fits=10).quiesce()
    text = str(err.value)
    assert text.startswith("ports: 1 violation(s) over 8 allocation(s) on "
                           "2 node(s)") and words in text
    assert "violations=1" in capsys.readouterr().out


def test_a_blocks_positions_are_read_too():
    """A block carries no port: placements of a port-asking group in a
    block are read, one by one, as missing theirs (what holds ROADMAP
    S1b (i) back until the benchmark's census reads a block's ports)."""
    import numpy as np

    from nomad_tpu.structs.alloc import AllocBlock

    store, job = store_with_a_port_job(6, 4, enums.EVAL_STATUS_COMPLETE)
    node = next(iter(store.snapshot().nodes()))
    block = AllocBlock(
        id="blk", eval_id="ev", namespace=job.namespace, job_id=job.id,
        job=job, job_version=job.version,
        task_group=job.task_groups[0].name,
        name_indices=np.arange(4, 6, dtype=np.int64),
        node_ids=[node.id], node_names=[node.name],
        counts=np.array([2], np.int64),
        allocated_vec=mock.alloc(job, node).allocated_vec)
    store.upsert_plan_results([], alloc_blocks=[block], job=job)
    with pytest.raises(single_agent_ports.NotHeld) as err:
        deployment(store, fits=10).quiesce()
    assert str(err.value).startswith(
        "ports: 4 violation(s) over 6 allocation(s)")
