"""Trace tooling CLI.

    python -m nomad_tpu.obs --export out.json [--addr URL]
    python -m nomad_tpu.obs --trace-smoke

``--export`` writes a Chrome ``trace_event`` JSON file (load it in
chrome://tracing or https://ui.perfetto.dev). With ``--addr`` it scrapes
a running agent's ``/v1/traces``; without, it boots a small in-process
demo cluster, runs a workload, and exports that trace.

``--trace-smoke`` is the scripts/check.sh gate: a live 3-node cluster
with tracing on, every committed eval must show a COMPLETE
enqueue→dequeue→schedule→plan-submit→verify→commit span chain (the
raft fsync/apply spans must exist for gap attribution), then the same
workload with ``NOMAD_TPU_TRACE`` semantics off must record ZERO spans
(the kill switch actually kills). Exit 0 ok / 2 fail."""

from __future__ import annotations

import argparse
import logging
import shutil
import sys
import tempfile
import time

from . import RECORDER, TRACER
from .export import (EVAL_CHAIN, chain_report, phase_breakdown,
                     render_chain, write_chrome_trace)
from .trace import R_NAME

log = logging.getLogger("nomad_tpu.obs")


def _run_workload(cluster, leader, jobs_n: int):
    """Register jobs_n single-alloc jobs, enqueue their evals, drain.
    Returns the list of enqueued evals (each its own trace root)."""
    from .. import mock

    jobs = []
    for _ in range(jobs_n):
        j = mock.job()
        j.task_groups[0].count = 1
        j.task_groups[0].tasks[0].resources.cpu = 100
        j.task_groups[0].tasks[0].resources.memory_mb = 64
        jobs.append(j)
        leader.store.upsert_job(j)
    evals = [mock.eval_for(j, create_time=time.time()) for j in jobs]
    leader.store.upsert_evals(evals)
    for ev in evals:
        leader.server.broker.enqueue(ev)

    deadline = time.time() + 120
    while True:
        if leader.server.wait_for_idle(timeout=10.0,
                                       include_delayed=False) \
                and leader.server.blocked.blocked_count() == 0:
            snap = leader.local_store.snapshot()
            placed = [a for a in snap.allocs()
                      if not a.terminal_status()
                      and not a.server_terminal()]
            if len(placed) >= jobs_n:
                return evals
        if time.time() > deadline:
            raise RuntimeError("workload did not drain")
        time.sleep(0.05)


def _demo_cluster(tmp: str, jobs_n: int = 60, nodes_n: int = 20,
                  workers: int = 2):
    """A small live 3-node cluster + drained workload; yields
    (cluster, leader, evals). Caller stops the cluster."""
    from .. import mock
    from ..core.server import ServerConfig
    from ..raft.cluster import RaftCluster

    def config_fn(_i: int) -> ServerConfig:
        return ServerConfig(
            num_workers=workers, eval_batch_size=4,
            heartbeat_ttl=3600.0, gc_interval=3600.0, nack_timeout=900.0,
            failed_eval_followup_delay=3600.0,
            failed_eval_unblock_interval=0.5)

    cluster = RaftCluster(3, config_fn=config_fn, data_dir=tmp)
    cluster.start()
    leader = cluster.wait_for_leader(timeout=15.0)
    if leader is None:
        cluster.stop()
        raise RuntimeError("no leader elected")
    for _ in range(nodes_n):
        leader.register_node(mock.node())
    evals = _run_workload(cluster, leader, jobs_n)
    return cluster, leader, evals


def export_trace(path: str, addr: str = "") -> int:
    if addr:
        import json
        import urllib.request

        with urllib.request.urlopen(
                addr.rstrip("/") + "/v1/traces?limit=0", timeout=10) as r:
            body = json.loads(r.read().decode())
        doc = body.get("trace", {"traceEvents": []})
        doc["otherData"] = {"phases": body.get("phases", {}),
                            "dropped": body.get("dropped", 0)}
        with open(path, "w") as f:
            json.dump(doc, f)
        print(f"wrote {len(doc['traceEvents'])} span(s) from {addr} "
              f"-> {path} ({doc['otherData']['dropped']} dropped from "
              "full rings)")
        return 0
    # demo mode: boot a cluster, run a workload, export its spans
    TRACER.set_enabled(True)
    TRACER.clear()
    tmp = tempfile.mkdtemp(prefix="nomad-obs-export-")
    try:
        cluster, _leader, _evals = _demo_cluster(tmp)
        try:
            spans, dropped = TRACER.spans(), TRACER.dropped
        finally:
            cluster.stop()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    write_chrome_trace(path, spans, dropped=dropped)
    print(f"wrote {len(spans)} span(s) from an in-process demo cluster "
          f"-> {path} ({dropped} dropped from full rings)")
    for name, row in phase_breakdown(spans).items():
        print(f"  {name:<22} n={row['count']:<5} p50={row['p50_ms']:8.3f}ms"
              f" p99={row['p99_ms']:8.3f}ms")
    return 0


def trace_smoke(jobs_n: int = 60) -> int:
    t0 = time.monotonic()
    TRACER.set_enabled(True)
    RECORDER.set_enabled(True)
    TRACER.clear()
    RECORDER.clear()
    tmp = tempfile.mkdtemp(prefix="nomad-obs-smoke-")
    try:
        cluster, leader, evals = _demo_cluster(tmp, jobs_n=jobs_n)
        try:
            spans = TRACER.spans()

            # 1) every committed eval's chain is complete
            incomplete = []
            for ev in evals:
                rep = chain_report(spans, ev.trace(), required=EVAL_CHAIN)
                if not rep["complete"]:
                    incomplete.append(rep)
            if incomplete:
                print("TRACE SMOKE: FAIL — incomplete span chain for "
                      f"{len(incomplete)}/{len(evals)} eval(s):")
                for rep in incomplete[:3]:
                    print(render_chain(rep))
                return 2

            # 2) the raft write path showed up (gap attribution fodder)
            names = {rec[R_NAME] for rec in spans}
            for must in ("raft.fsync", "raft.apply", "worker.snapshot",
                         "eval.persist"):
                if must not in names:
                    print(f"TRACE SMOKE: FAIL — no {must} span recorded")
                    return 2

            # 3) the recorder saw the control plane move
            if not RECORDER.events("broker") \
                    or not RECORDER.events("plan") \
                    or not RECORDER.events("raft"):
                print("TRACE SMOKE: FAIL — flight recorder missed a "
                      "subsystem (broker/plan/raft)")
                return 2

            # one sample chain for the human reading the CI log
            print(render_chain(chain_report(spans, evals[0].trace(),
                                            required=EVAL_CHAIN)))

            # 4) kill switch: same workload, tracing off, ZERO spans
            TRACER.set_enabled(False)
            RECORDER.set_enabled(False)
            TRACER.clear()
            RECORDER.clear()
            _run_workload(cluster, cluster.leader() or leader, 20)
            leftover = TRACER.spans()
            if leftover:
                print(f"TRACE SMOKE: FAIL — kill switch leaked "
                      f"{len(leftover)} span(s)")
                return 2
            if RECORDER.events():
                print("TRACE SMOKE: FAIL — kill switch leaked recorder "
                      "events")
                return 2
        finally:
            cluster.stop()
            TRACER.set_enabled(True)
            RECORDER.set_enabled(True)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    dt = time.monotonic() - t0
    print(f"TRACE SMOKE: ok — {len(evals)} eval(s) with complete "
          f"enqueue→commit span chains ({len(spans)} spans), kill "
          f"switch verified span-free, {dt:.1f}s")
    return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(prog="python -m nomad_tpu.obs")
    parser.add_argument("--export", metavar="PATH",
                        help="write a Chrome trace_event JSON file")
    parser.add_argument("--addr", default="",
                        help="scrape a running agent (e.g. "
                             "http://127.0.0.1:4646) instead of the "
                             "in-process demo")
    parser.add_argument("--trace-smoke", action="store_true",
                        help="live-cluster span-chain + kill-switch gate")
    parser.add_argument("-v", "--verbose", action="store_true")
    args = parser.parse_args(argv)
    logging.basicConfig(
        level=logging.INFO if args.verbose else logging.WARNING,
        format="%(asctime)s %(name)s %(levelname)s %(message)s")
    if args.trace_smoke or (args.export and not args.addr):
        # both run an in-process cluster that can schedule
        from ..tensor.backend import bootstrap

        bootstrap()
    if args.trace_smoke:
        return trace_smoke()
    if args.export:
        return export_trace(args.export, addr=args.addr)
    parser.print_help()
    return 0


if __name__ == "__main__":
    sys.exit(main())
