"""`correct`'s checker says false to doctored end states, and the two
plain references agree with the host scheduler at toy size."""

import copy
import random

import numpy as np
import pytest

from benchmark import check
from benchmark.reference import (binpack_counts, reference_fitness,
                                 spread_greedy)
from benchmark.reference.fitness import bestfit, mean_fitness

RULES = {"sample_nodes": 4, "fitness_rel_tol": 5e-3,
         "spread_rel_tol": 0.25, "spread_abs_tol": 1}


def good_state() -> dict:
    cap = np.array([8000.0, 16384.0, 102400.0, 12001.0])
    return {
        "jobs": {"a": {"count": 10, "live": 10},
                 "b": {"count": 4, "live": 4}},
        "sample": [
            {"id": "n1", "cap": cap, "used": np.array([4000.0, 2048, 0, 2]),
             "ports": [20001, 20002]},
            {"id": "n2", "cap": cap, "used": np.array([8000.0, 4096, 0, 0]),
             "ports": []}],
        "not_ready": [],
        "spread": {"b": [1, 1, 1, 1, 0]},
        "fitness": 0.800, "reference_fitness": 0.802,
        "reference_unplaced": 0, "retraces": 0, "twin_failures": 0,
        "errors": [],
    }


def test_a_clean_state_is_correct():
    v = check.judge(good_state(), RULES)
    assert v["correct"] and not v["reasons"] and not v["failed_jobs"]
    # every number compared stands beside its limit, and is within it
    assert v["compared"] == {
        "jobs_off_count": [0, 0], "nodes_over_capacity": [0, 0],
        "ports_twice": [0, 0], "nodes_down": [0, 0],
        "spread_max_less_min": [1, 1 + 0.25 * 4 / 5],
        "fitness_under_reference": [pytest.approx(0.002 / 0.802), 5e-3],
        "retraces": [0, 0], "twin_failures": [0, 0], "errors": [0, 0]}


def over_capacity(s):
    s["sample"][1]["used"] = np.array([8050.0, 4096, 0, 0])


def port_twice(s):
    s["sample"][0]["ports"] = [20001, 20001]


def one_alloc_short(s):
    s["jobs"]["a"]["live"] = 9


def spread_broken(s):
    s["spread"]["b"] = [3, 1, 0, 0, 0]


def fitness_below(s):
    s["fitness"] = 0.79


def node_down(s):
    s["not_ready"] = ["n9"]


def retraced(s):
    s["retraces"] = 1


def error_logged(s):
    s["errors"] = ["log nomad_tpu.worker: eval failed"]


PAST = {over_capacity: "nodes_over_capacity", port_twice: "ports_twice",
        one_alloc_short: "jobs_off_count",
        spread_broken: "spread_max_less_min",
        fitness_below: "fitness_under_reference", node_down: "nodes_down",
        retraced: "retraces", error_logged: "errors"}


@pytest.mark.parametrize("doctor", [
    over_capacity, port_twice, one_alloc_short, spread_broken,
    fitness_below, node_down, retraced, error_logged])
def test_a_doctored_state_is_refused(doctor):
    state = copy.deepcopy(good_state())
    doctor(state)
    v = check.judge(state, RULES)
    assert not v["correct"] and v["reasons"]
    if doctor in (one_alloc_short, spread_broken):
        assert v["failed_jobs"]
    # exactly the doctored number is past its limit
    past = [n for n, (got, limit) in v["compared"].items() if got > limit]
    assert past == [PAST[doctor]]


def test_rounds_are_one_verdict_and_one_bad_round_fails_the_run():
    """A run of several rounds is correct only if each round is; a count
    held to 0 is the rounds' sum, a graded number the round nearest its
    limit."""
    clean = check.judge_rounds([good_state(), good_state()], RULES)
    assert clean["correct"] and not clean["reasons"]
    assert clean["compared"] == check.judge(good_state(), RULES)["compared"]
    short, uneven = good_state(), good_state()
    one_alloc_short(short)
    fitness_below(uneven)
    one_alloc_short(uneven)
    v = check.judge_rounds([good_state(), short, uneven], RULES)
    assert not v["correct"]
    assert [r.split(":")[0] for r in v["reasons"]] == [
        "round 1", "round 2", "round 2"]
    assert v["compared"]["jobs_off_count"] == [2, 0]
    assert v["compared"]["fitness_under_reference"] == check.judge(
        uneven, RULES)["compared"]["fitness_under_reference"]
    assert len(v["failed_jobs"]) == 2
    # one round is reported as it always was
    assert check.judge_rounds([short], RULES)["reasons"] == check.judge(
        short, RULES)["reasons"]


def toy_fleet(n=96, seed=0):
    rng = random.Random(seed)
    cap = np.array([[rng.choice([8000, 16000, 32000]),
                     rng.choice([16384, 32768, 65536])] for _ in range(n)],
                   np.float64)
    return cap, np.arange(n) % 8


def test_counts_reference_is_the_per_placement_greedy():
    """The counts form (fill the best node, rescore) against the K-step
    greedy it stands for, written out the slow way."""
    cap, _ = toy_fleet()
    used_a = np.zeros_like(cap)
    used_b = np.zeros_like(cap)
    ask = np.array([500.0, 256.0])
    fast = binpack_counts.place_job(cap, used_a, ask, 300)
    slow = np.zeros(len(cap), np.int64)
    for _ in range(300):
        ok = np.all(used_b + ask <= cap, axis=1)
        score = np.where(ok, bestfit(cap, used_b + ask), -np.inf)
        best = int(np.argmax(score))
        used_b[best] += ask
        slow[best] += 1
    assert fast.sum() == 300
    assert mean_fitness(cap, used_a, fast) == pytest.approx(
        mean_fitness(cap, used_b, slow), rel=1e-9)


def host_schedule(jobs, n_nodes, seed):
    """The same jobs through the host `binpack` scheduler (the serial
    test harness, no device) -> (cap, used, counts, per-rack counts)."""
    from nomad_tpu import mock
    from nomad_tpu.testing import Harness

    from benchmark.deploy.single_agent import shape_node
    from benchmark.jobs import build_job

    h = Harness()
    rng = random.Random(seed)
    mix = {"racks": 8, "zones": 4, "cpu_mhz": [8000, 16000, 32000],
           "memory_mb": [16384, 32768, 65536]}
    for i in range(n_nodes):
        node = mock.node()
        shape_node(node, i, rng, mix)
        h.store.upsert_node(node)
    built = [build_job(s) for s in jobs]
    for job in built:
        h.store.upsert_job(job)
        ev = mock.eval_for(job)
        h.store.upsert_evals([ev])
        h.process(ev)
    snap = h.store.snapshot()
    fleet = check.cluster_arrays(snap, "${attr.rack}")
    index_of = {nid: i for i, nid in enumerate(fleet["ids"])}
    counts, per_job = check.placements_per_node(
        snap, {j.id for j in built}, index_of)
    return fleet, counts, per_job


def test_references_agree_with_the_host_scheduler_at_toy_size():
    plain = [{"id": f"p{i}", "type": "batch", "count": 40, "cpu": 500,
              "mem": 256, "ports": 0, "spread": None} for i in range(3)]
    fleet, counts, _ = host_schedule(plain, 48, seed=1)
    assert counts.sum() == 120
    ref = reference_fitness(fleet["cap"], np.zeros_like(fleet["cap"]),
                            fleet["value_of"], len(fleet["values"]), plain)
    got = mean_fitness(fleet["cap"], fleet["used"], counts)
    # without a spread the host iterator scores 2 random nodes per batch
    # placement (upstream's limit iterator) where the reference scores
    # them all, so its packing differs from run to run (0.57 to 0.73
    # seen against the reference's 0.68): same placements made, same
    # capacity held, fitness in a band
    assert ref["unplaced"] == 0 and ref["counts"].sum() == 120
    assert (fleet["used"] <= fleet["cap"]).all()
    assert (ref["used"] <= fleet["cap"]).all()
    assert got == pytest.approx(ref["fitness"], rel=0.25)

    spread = [{"id": f"s{i}", "type": "service", "count": 24, "cpu": 500,
               "mem": 256, "ports": 1,
               "spread": {"attribute": "${attr.rack}", "weight": 50}}
              for i in range(2)]
    fleet, counts, per_job = host_schedule(spread, 48, seed=2)
    assert counts.sum() == 48
    ref = spread_greedy.run(fleet["cap"], np.zeros_like(fleet["cap"]),
                            fleet["value_of"], len(fleet["values"]), spread)
    for s, want in zip(spread, ref["per_value"]):
        rows = np.array(per_job[s["id"]], np.int64)
        got_racks = np.bincount(fleet["value_of"][rows],
                                minlength=len(fleet["values"]))
        # the evenness both reach: every rack within one of every other
        assert got_racks.max() - got_racks.min() <= 1
        assert want.max() - want.min() <= 1
        assert got_racks.sum() == want.sum() == 24
    # with a spread the host scores every node, as the reference does
    got = mean_fitness(fleet["cap"], fleet["used"], counts)
    want_fit = mean_fitness(fleet["cap"], ref["used"], ref["counts"])
    print("spread arm fitness: host", got, "reference", want_fit)
    assert got == pytest.approx(want_fit, rel=0.10)


def test_the_control_of_the_fitness_comparison_at_the_cells_own_size():
    """The reference put in the program's place with worst-fit scoring
    (upstream's spread algorithm) where the configuration states bin
    packing, on the grid's 10,000 nodes and the cell's 50 x 300 jobs:
    it reads 10.4% under the reference, and the cell's limit refuses it
    with room (PERF.md section 2 has the sound runs' readings)."""
    import json
    from pathlib import Path

    from benchmark import traffic as traffic_mod
    from benchmark.reference import spread_greedy

    here = Path(__file__).resolve().parents[1]
    traffic = json.loads((here / "traffic/spread.300.json").read_text())
    config = json.loads((here / "configs/grid-10k.json").read_text())
    n, racks = config["nodes"], config["node_mix"]["racks"]
    cap = np.tile(np.array([[float(config["node_mix"]["cpu_mhz"][0]),
                             float(config["node_mix"]["memory_mb"][0])]]),
                  (n, 1))
    value_of = np.arange(n) % racks
    jobs = traffic_mod.job_specs(traffic, 7, int(traffic["jobs"]), "c")
    ref = reference_fitness(cap, np.zeros_like(cap), value_of, racks, jobs)
    spread_greedy.bestfit = lambda c, u: 1.0 - bestfit(c, u)
    try:
        control = reference_fitness(cap, np.zeros_like(cap), value_of,
                                    racks, jobs)
    finally:
        spread_greedy.bestfit = bestfit
    assert ref["unplaced"] == control["unplaced"] == 0
    state = good_state()
    state["fitness"] = control["fitness"]
    state["reference_fitness"] = ref["fitness"]
    v = check.judge(state, traffic["check"])
    got, limit = v["compared"]["fitness_under_reference"]
    assert got == pytest.approx(0.1042, abs=2e-3) and limit == 0.02
    assert not v["correct"]
    # the widest sound reading on the chip, one drain in 289, passes
    state["fitness"] = ref["fitness"] * (1 - 0.00601)
    assert check.judge(state, traffic["check"])["correct"]
