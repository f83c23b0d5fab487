"""Job specs (benchmark/traffic.py) -> the program's Job structs and
their wire form. The only place a spec meets `nomad_tpu.mock`."""

from __future__ import annotations


def build_job(spec: dict):
    """The task of upstream's mock.Job (one group, one exec task) sized
    by the spec; a dynamic port and a spread stanza where it says so."""
    from nomad_tpu import mock
    from nomad_tpu.structs import Spread
    from nomad_tpu.structs.resources import NetworkResource

    job = mock.batch_job() if spec["type"] == "batch" else mock.job()
    job.id = job.name = spec["id"]
    tg = job.task_groups[0]
    tg.count = int(spec["count"])
    res = tg.tasks[0].resources
    res.cpu, res.memory_mb = int(spec["cpu"]), int(spec["mem"])
    if spec.get("datacenters"):
        job.datacenters = list(spec["datacenters"])
    if spec.get("constraints") is not None:
        job.constraints = list(spec["constraints"])
    if spec.get("ports"):
        res.networks = [NetworkResource(
            dynamic_ports=[f"p{i}" for i in range(int(spec["ports"]))])]
    if spec.get("spread"):
        tg.spreads = [Spread(attribute=spec["spread"]["attribute"],
                             weight=int(spec["spread"]["weight"]))]
    return job


def wire_body(spec: dict) -> dict:
    """The JSON body `POST /v1/jobs` takes for this spec."""
    from nomad_tpu.api.codec import to_dict

    return {"job": to_dict(build_job(spec))}
