"""The generator: job specs as a pure function of the traffic file and
the seed; the client's chain tracker."""

import json
from pathlib import Path

from benchmark import loadgen, traffic

HERE = Path(__file__).resolve().parents[1]


def mix(name):
    return json.loads((HERE / "traffic" / f"{name}.json").read_text())


def test_jobs_are_a_pure_function_of_the_seed():
    t = mix("spread.300")
    t["classes"][0]["cpu"] = [100, 1000]       # a drawn ask, to see the seed
    s = traffic.job_specs(t, 7, 40, "j")
    assert s == traffic.job_specs(t, 7, 40, "j")
    other = traffic.job_specs(t, 8, 40, "j")
    assert s != other
    # stratified: another seed is another order of the same work
    assert sorted(x["cpu"] for x in s) == sorted(x["cpu"] for x in other)
    assert sorted(x["count"] for x in s) == sorted(x["count"] for x in other)


def test_a_class_carries_its_datacenters_and_constraints():
    s = traffic.job_specs(mix("spread.1200"), 1, 4, "j")
    assert all(x["datacenters"] == ["dc-1", "dc-2"] and x["constraints"] == []
               and x["ports"] == 0 for x in s)
    assert all(x["spread"] == {"attribute": "${meta.rack}", "weight": 50}
               for x in s)
    c2m = traffic.job_specs(mix("backlog"), 1, 4, "j")
    assert all("datacenters" not in x and x["spread"] is None for x in c2m)


def test_grid_backlogs_fit_the_fleet():
    config = json.loads((HERE / "configs" / "grid-10k.json").read_text())
    for name, size, jobs in (("spread.300", 300, 50),
                             ("spread.1200", 1200, 12)):
        t = mix(name)
        specs = traffic.job_specs(t, 5, t["jobs"], "g")
        assert [s["count"] for s in specs] == [size] * jobs
        per_node = min(config["node_mix"]["cpu_mhz"][0] // specs[0]["cpu"],
                       config["node_mix"]["memory_mb"][0] // specs[0]["mem"])
        assert per_node * config["nodes"] == config["allocations_that_fit"]
        assert size * jobs <= 0.75 * config["allocations_that_fit"]


def test_warm_specs_cover_every_padded_length_once():
    # the scan compiles once a padded length: the job's own, and the
    # remainders of a partly rejected plan below it
    w = traffic.warm_specs(mix("spread.300"), "w")
    assert sorted(s["count"] for s in w["singles"]) == [32, 64, 128, 256, 300]
    w = traffic.warm_specs(mix("spread.1200"), "w")
    assert sorted(s["count"] for s in w["singles"]) == [
        32, 64, 128, 256, 512, 1024, 1200]
    assert all(s["spread"] and s["ports"] == 0 and s["cpu"] == 1
               for s in w["singles"]) and not w["burst"]
    c2m = mix("backlog")
    w = traffic.warm_specs(c2m, "w")
    # the count solve has one shape at width 1 and one at full width;
    # the scan takes the remainders of rejected rows
    assert sorted(s["count"] for s in w["singles"]) == [
        32, 64, 128, 255, 4000]
    assert len(w["burst"]) == 32 and all(s["count"] == 256
                                         for s in w["burst"])
    skipping = dict(c2m, warm=dict(c2m["warm"], host_cutover=32))
    assert min(s["count"] for s in traffic.warm_specs(skipping, "w")[
        "singles"]) == 64


def test_chain_tracker_waits_for_the_follow_up():
    tr = loadgen.ChainTracker({"j": 10})
    ev = {"id": "e1", "job_id": "j", "status": "pending", "modify_index": 1}
    assert not tr.feed(ev, 1.0)
    # out of plan attempts: failed, hands over to a blocked eval
    assert not tr.feed(dict(ev, status="failed", blocked_eval="e2",
                            modify_index=2), 2.0)
    assert not tr.feed({"id": "e2", "job_id": "j", "status": "blocked",
                        "modify_index": 3}, 3.0)
    assert "j" not in tr.done_at
    assert tr.feed({"id": "e2", "job_id": "j", "status": "complete",
                    "modify_index": 4}, 4.0)
    assert tr.done_at == {"j": 4.0} and tr.allocs_done() == 10
    # a job whose only eval failed with nothing after it ended badly
    tr2 = loadgen.ChainTracker({"k": 1})
    tr2.feed({"id": "x", "job_id": "k", "status": "failed",
              "modify_index": 1}, 1.0)
    assert tr2.ended_bad == {"k": "failed"} and not tr2.done_at
