"""Chaos smoke: one scripted partition + crash scenario on a durable
3-node cluster, fixed seed, well under a minute.

    python -m nomad_tpu.chaos [--seed N]
    python -m nomad_tpu.chaos --raft-smoke
    python -m nomad_tpu.chaos --e2e-smoke
    python -m nomad_tpu.chaos --solve-smoke
    python -m nomad_tpu.chaos --snap-smoke
    python -m nomad_tpu.chaos --swarm-smoke
    python -m nomad_tpu.chaos --watch-smoke
    python -m nomad_tpu.chaos --flow-smoke
    python -m nomad_tpu.chaos --load-smoke
    python -m nomad_tpu.chaos --swarm-scale [N]

Exit 0 when every invariant holds; 2 on a violation (the CI gate in
scripts/check.sh). This is the smallest end-to-end proof that the
fault layer, the recovery paths, and the invariant sweep all work —
the full scenario matrix lives in tests/test_chaos.py.

`--raft-smoke` runs the group-commit write-path smoke instead: 3
durable raft nodes, 500 commands from 8 concurrent proposers, a leader
crash-restart in the middle — asserts zero acknowledged commits lost
(PERF.md "The replicated write path").

`--e2e-smoke` runs the full-pipeline smoke: 300 evals through
broker -> batched workers -> pipelined plan applier -> raft group
commit -> FSM on a durable 3-node cluster, with one leader restart
mid-stream — zero acked allocs lost, rejection <= 5% (the
scripts/check.sh --e2e-smoke gate; PERF.md "End-to-end pipeline").

`--solve-smoke` runs the global-batch solve smoke: bulk-sized jobs
through batched workers under "tpu-solve" on a live 3-node cluster —
asserts a whole worker batch reached the joint auction launch, the
selected packing score dominates the in-launch greedy counterfactual,
and every replica holds a unique alloc set (the scripts/check.sh
--solve-smoke gate; PERF.md "Global-batch solve").

`--snap-smoke` runs the snapshot/compaction smoke: the e2e pipeline on
a durable 3-node cluster with a low snapshot threshold (every replica
snapshots + compacts under load); one follower is crashed and wiped
after the leader compacts, and the restart must catch up via the
chunked install-snapshot path mid-traffic — zero acked-commit loss and
alloc-set uniqueness on every replica (the scripts/check.sh
--snap-smoke gate; ROBUSTNESS.md "Durability at scale").

`--swarm-smoke` runs the client-plane swarm smoke: 200 sim nodes
speaking the real register/heartbeat-batch/alloc-ack surface while a
churn loop flaps a rolling slice and THREE leaders crash in sequence —
no stable node is ever wrongly expired, silenced nodes expire only
after a real >= TTL silence and recover on their next beat, and every
replica passes check_node_liveness + alloc uniqueness (the
scripts/check.sh --swarm-smoke gate; ROBUSTNESS.md "Client plane").

`--swarm-scale [N]` runs the fleet-scale acceptance smoke: N (default
50,000) sim nodes heartbeating at the production TTL against a live
3-node cluster WHILE the e2e pipeline runs, one leader crash/failover
mid-stream — zero missed-TTL false positives on any replica.

`--flow-smoke` runs the event-completeness smoke: the e2e pipeline on
a 3-node cluster with the nomadflow shadow replicas force-armed — every
server's event stream is replayed into a reduced replica and
fingerprint-compared against MVCC snapshot rebuilds across a leader
crash/restart; any mutation whose delta never reached the stream fails
the run (the scripts/check.sh --flow-smoke gate; ANALYSIS.md
"nomadflow").

`--load-smoke` runs the overload smoke: a durable 3-node cluster under
a ~10x open-loop job-submit burst (seeded Poisson arrivals that do NOT
let up when the server slows) with a leader crash mid-burst — no
heartbeat is ever shed, heartbeat p99 stays bounded, zero missed-TTL
false positives, every acked submit survives the failover, and
invariant 10 (overload tier ordering) holds on every replica (the
scripts/check.sh --load-smoke gate; ROBUSTNESS.md "Overload
envelope").

`--watch-smoke` runs the read-path failover smoke: blocking queries +
event subscriptions parked on ALL 3 servers while the leader crashes —
survivors' parked queries complete with the post-failover result at a
higher index, fresh reads on the dead server fail fast with
X-Nomad-KnownLeader=false, and the X-Nomad-LastContact stale bound
holds across the transition (the scripts/check.sh --watch-smoke gate;
PERF.md "Read path at fan-out scale")."""

from __future__ import annotations

import argparse
import logging
import os
import sys
import tempfile
import threading
import time

from .. import mock
from ..raft.cluster import RaftCluster
from .invariants import InvariantViolation
from .runner import ScenarioRunner, seed_from_env

log = logging.getLogger("nomad_tpu.chaos")


def _live_entry(cluster):
    return next(s for s in cluster.servers.values() if not s.crashed)


def build_scenario(cluster) -> ScenarioRunner:
    r = ScenarioRunner(cluster, seed=seed_from_env())

    @r.step("elect + seed workload")
    def _seed(r):
        leader = r.wait_for_leader()
        entry = _live_entry(cluster)
        for _ in range(2):
            entry.register_node(mock.node())
        job = mock.job()
        job.task_groups[0].count = 2
        entry.register_job(job)
        leader.server.wait_for_idle(15.0)

    @r.step("cut the leader's outbound links (directed partition)")
    def _cut(r):
        leader = r.wait_for_leader()
        others = [sid for sid in cluster.servers if sid != leader.id]
        for sid in others:
            cluster.transport.partition_link(leader.id, sid)
        # followers miss heartbeats and elect among themselves; the old
        # leader still hears the higher term and steps down
        deadline = time.time() + 10
        while time.time() < deadline:
            fresh = cluster.leader()
            if fresh is not None and fresh.id != leader.id:
                return
            time.sleep(0.05)
        raise InvariantViolation("no replacement leader after directed cut")

    @r.step("write through the new leader, then heal")
    def _write_and_heal(r):
        entry = _live_entry(cluster)
        entry.register_node(mock.node())
        r.heal_and_converge()

    @r.step("crash the leader mid-write, restart, converge")
    def _crash_restart(r):
        leader = r.wait_for_leader()
        entry = next(s for s in cluster.servers.values()
                     if not s.crashed and s.id != leader.id)
        cluster.crash(leader.id)
        entry.register_node(mock.node())  # forwarded to the new leader
        cluster.restart(leader.id)
        r.heal_and_converge(timeout=20.0)

    return r


def raft_smoke(total: int = 500, proposers: int = 8) -> int:
    """Group-commit smoke: `total` commands through a 3-node durable
    cluster with a leader crash-restart in the middle. Every command
    the proposers saw acknowledged must be present on the post-crash
    leader AND replayed by the restarted node — zero lost commits."""
    import os
    import shutil
    import tempfile
    import threading

    from ..raft.durable import DurableLog
    from ..raft.node import NotLeaderError, RaftNode
    from ..raft.transport import InProcTransport

    t0 = time.monotonic()
    tmp = tempfile.mkdtemp(prefix="nomad-raft-smoke-")
    transport = InProcTransport()
    ids = ["a", "b", "c"]
    applied = {}

    def build(nid: str) -> RaftNode:
        d = os.path.join(tmp, nid)
        os.makedirs(d, exist_ok=True)
        mine = applied[nid] = []  # restart replays into a fresh list
        return RaftNode(nid, ids, transport,
                        lambda cmd, l=mine: l.append(cmd) or len(l),
                        log=DurableLog(d))

    nodes = {nid: build(nid) for nid in ids}
    for n in nodes.values():
        n.start()

    def current_leader(timeout: float = 10.0):
        deadline = time.time() + timeout
        while time.time() < deadline:
            for n in nodes.values():
                if n.is_leader():
                    return n
            time.sleep(0.01)
        return None

    try:
        if current_leader() is None:
            print("RAFT SMOKE: FAIL — no leader elected")
            return 2
        acked: set = set()
        acked_lock = threading.Lock()

        def propose(start: int) -> None:
            for i in range(start, total, proposers):
                cmd = ("smoke", (i,), {})
                # an errored apply is AMBIGUOUS (it may still commit);
                # retry until an unambiguous ack — duplicates are fine,
                # the assertion below is set inclusion
                while True:
                    leader = current_leader()
                    if leader is None:
                        time.sleep(0.02)
                        continue
                    try:
                        leader.apply(cmd, timeout=5.0)
                    except (NotLeaderError, TimeoutError):
                        time.sleep(0.01)
                        continue
                    with acked_lock:
                        acked.add(i)
                    break

        threads = [threading.Thread(target=propose, args=(i,), daemon=True)
                   for i in range(proposers)]
        for t in threads:
            t.start()

        # crash the leader mid-stream, then restart it over its data dir
        while True:
            with acked_lock:
                if len(acked) >= total // 2:
                    break
            time.sleep(0.005)
        victim = current_leader()
        if victim is not None:
            vid = victim.id
            transport.unregister(vid)
            victim.stop()
            victim.log.close()
            nodes[vid] = build(vid)
            nodes[vid].start()

        for t in threads:
            t.join(timeout=30.0)
        if any(t.is_alive() for t in threads):
            print("RAFT SMOKE: FAIL — proposers wedged")
            return 2

        # convergence: every node (including the restarted one) must
        # replay every acknowledged command
        deadline = time.time() + 15.0
        missing = {}
        while time.time() < deadline:
            missing = {
                nid: acked - {c[1][0] for c in lst if c[0] == "smoke"}
                for nid, lst in applied.items()}
            if not any(missing.values()):
                break
            time.sleep(0.05)
        if any(missing.values()):
            worst = {nid: len(m) for nid, m in missing.items() if m}
            print(f"RAFT SMOKE: FAIL — acked commits missing after "
                  f"crash/restart: {worst}")
            return 2
    finally:
        for n in nodes.values():
            n.stop()
        for n in nodes.values():
            if hasattr(n.log, "close"):
                n.log.close()
        shutil.rmtree(tmp, ignore_errors=True)
    dt = time.monotonic() - t0
    print(f"RAFT SMOKE: ok — {len(acked)}/{total} acked commits survived "
          f"a leader crash/restart on all 3 nodes, {dt:.1f}s")
    return 0


def e2e_smoke(jobs_n: int = 300, nodes_n: int = 75, workers: int = 4) -> int:
    """Full-pipeline smoke (scripts/check.sh --e2e-smoke): 300 evals
    through broker -> batched workers -> pipelined plan applier -> raft
    group commit -> FSM on a durable 3-node cluster, with one leader
    crash-restart mid-stream. Asserts: zero acked (committed-in-FSM)
    allocs lost across the failover, plan rejection rate <= 5%, every
    eval drained, and the alloc-uniqueness + safety invariants hold."""
    import os
    import shutil

    from ..core.server import ServerConfig
    from ..raft.cluster import RaftCluster
    from .invariants import InvariantChecker

    t0 = time.monotonic()

    def config_fn(_i: int) -> ServerConfig:
        return ServerConfig(
            num_workers=workers, eval_batch_size=8,
            heartbeat_ttl=3600.0, gc_interval=3600.0, nack_timeout=900.0,
            failed_eval_followup_delay=3600.0,
            failed_eval_unblock_interval=0.5)

    tmp = tempfile.mkdtemp(prefix="nomad-e2e-smoke-")
    checker = InvariantChecker()
    try:
        cluster = RaftCluster(3, config_fn=config_fn, data_dir=tmp)
        cluster.start()
        try:
            leader = cluster.wait_for_leader(timeout=15.0)
            if leader is None:
                print("E2E SMOKE: FAIL — no leader elected")
                return 2
            for _ in range(nodes_n):
                leader.register_node(mock.node())

            jobs = []
            for _ in range(jobs_n):
                j = mock.job()
                j.task_groups[0].count = 1
                # small tasks, low cluster utilization: the gate measures
                # pipeline safety across a failover, not placement
                # contention (the benchmark's cells own that axis)
                j.task_groups[0].tasks[0].resources.cpu = 100
                j.task_groups[0].tasks[0].resources.memory_mb = 64
                jobs.append(j)
                leader.store.upsert_job(j)
            evals = [mock.eval_for(j, create_time=time.time())
                     for j in jobs]
            leader.store.upsert_evals(evals)
            for ev in evals:
                leader.server.broker.enqueue(ev)

            # crash the leader once the pipeline is genuinely mid-batch:
            # some allocs committed, many evals still in flight
            deadline = time.time() + 60
            while time.time() < deadline:
                snap = leader.local_store.snapshot()
                committed = [a.id for a in snap.allocs()]
                if len(committed) >= jobs_n // 4:
                    break
                time.sleep(0.002)
            else:
                print("E2E SMOKE: FAIL — pipeline never reached the "
                      "crash window")
                return 2
            # everything in the crashed leader's applied FSM was
            # committed by a quorum => acked; none of it may vanish
            acked = set(committed)
            old_stats = dict(leader.server.plan_applier.stats)
            cluster.crash(leader.id)

            fresh = cluster.wait_for_leader(timeout=20.0)
            if fresh is None:
                print("E2E SMOKE: FAIL — no leader after the crash")
                return 2
            cluster.restart(leader.id)

            # drain: _restore_evals re-enqueued every still-pending
            # eval on the new leader; wait until all evals terminal
            # and nothing is parked in the blocked tracker
            deadline = time.time() + 180
            while True:
                fresh = cluster.leader() or fresh
                if fresh.server._running \
                        and fresh.server.wait_for_idle(
                            timeout=10.0, include_delayed=False) \
                        and fresh.server.blocked.blocked_count() == 0:
                    snap = fresh.local_store.snapshot()
                    placed = [a for a in snap.allocs()
                              if not a.terminal_status()
                              and not a.server_terminal()]
                    if len(placed) >= jobs_n:
                        break
                if time.time() > deadline:
                    print("E2E SMOKE: FAIL — pipeline did not drain "
                          "after the failover")
                    return 2
                time.sleep(0.1)

            checker.check_convergence(cluster, timeout=30.0)
            checker.check_all(cluster)

            snap = fresh.local_store.snapshot()
            lost = acked - {a.id for a in snap.allocs()}
            if lost:
                print(f"E2E SMOKE: FAIL — {len(lost)} acked alloc(s) "
                      f"lost across the failover: "
                      f"{sorted(i[:8] for i in lost)[:5]}")
                return 2

            # rejection across BOTH leaderships: optimistic-concurrency
            # rejects are retried by the submitter, so the rate is
            # rejected / (placed + rejected)
            stats = dict(fresh.server.plan_applier.stats)
            rejected = (stats.get("nodes_rejected", 0)
                        + old_stats.get("nodes_rejected", 0))
            rejection = rejected / max(len(placed) + rejected, 1)
            if rejection > 0.05:
                print(f"E2E SMOKE: FAIL — plan rejection rate "
                      f"{rejection:.1%} > 5%")
                return 2
        finally:
            cluster.stop()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    dt = time.monotonic() - t0
    print(f"E2E SMOKE: ok — {jobs_n} evals, {len(acked)} allocs acked "
          f"pre-crash all survived the leader restart, "
          f"rejection {rejection:.1%}, "
          f"{checker.stats['checks']} invariant sweeps, {dt:.1f}s")
    return 0


def load_smoke(nodes_n: int = 30, burst_s: float = 6.0,
               workers: int = 24) -> int:
    """Overload smoke (scripts/check.sh --load-smoke): a durable
    3-node cluster under a ~10x open-loop job-submit burst with a
    leader crash mid-burst (nomadload, ROBUSTNESS.md "Overload
    envelope"). Asserts:

    - tier-0 SLO: no heartbeat was ever shed, heartbeat p99 stayed
      bounded through the burst, and zero missed-TTL false positives
      (check_node_liveness attribution on every replica);
    - the admission plane engaged (submit sheds > 0 at 10x) AND let
      real work through (ok > 0);
    - zero acked-work loss: every register_job that RETURNED is in the
      FSM after the failover drains — a shed request was refused
      before any state changed, an acked one is quorum-durable;
    - invariant 10 (overload tier ordering) + the safety sweep on
      every replica."""
    import shutil

    from ..core.loadctl import RetryLater
    from ..core.server import ServerConfig
    from ..raft.cluster import RaftCluster
    from .invariants import InvariantChecker
    from .overload import run_open_loop

    t0 = time.monotonic()

    def config_fn(_i: int) -> ServerConfig:
        return ServerConfig(
            num_workers=2, eval_batch_size=8,
            heartbeat_ttl=10.0, gc_interval=3600.0, nack_timeout=900.0,
            failed_eval_followup_delay=3600.0,
            # the plane under test: watermarks low enough that a 10x
            # burst genuinely trips them on a laptop-scale cluster.
            # They must sit BELOW the
            # open-loop worker pool: submits block in propose, so queue
            # depth is bounded by the number of in-flight clients — a
            # soft mark above that can never be reached.
            loadctl_proposal_soft=8, loadctl_proposal_hard=24,
            loadctl_plan_soft=8, loadctl_plan_hard=24,
            loadctl_broker_soft=16, loadctl_broker_hard=48,
            loadctl_brownout_after=0.5)

    tmp = tempfile.mkdtemp(prefix="nomad-load-smoke-")
    checker = InvariantChecker()
    failures: list = []
    try:
        # high threshold: the burst commits ~10k entries, and default
        # compaction would route the restarted victim's recovery
        # through a chunked snapshot transfer that dominates the
        # convergence budget. The transfer has its own dedicated smoke
        # (--snap-smoke); this one audits the admission plane, so
        # recovery stays on the plain append path.
        cluster = RaftCluster(3, config_fn=config_fn, data_dir=tmp,
                              snapshot_threshold=1 << 17)
        cluster.start()
        try:
            leader = cluster.wait_for_leader(timeout=15.0)
            if leader is None:
                print("LOAD SMOKE: FAIL — no leader elected")
                return 2
            nodes = [mock.node() for _ in range(nodes_n)]
            for n in nodes:
                leader.register_node(n)

            lock = threading.Lock()
            acked_jobs: list = []

            def submit(i: int) -> None:
                j = mock.job()
                j.task_groups[0].count = 1
                j.task_groups[0].tasks[0].resources.cpu = 100
                j.task_groups[0].tasks[0].resources.memory_mb = 64
                entry = cluster.leader() or _live_entry(cluster)
                entry.register_job(j)
                with lock:
                    acked_jobs.append(j.id)

            # calibrate: closed-loop sequential submits for ~1 s give
            # the max-sustainable single-client rate; the burst offers
            # 10x that, open loop
            cal_t0 = time.monotonic()
            cal_n = 0
            while time.monotonic() - cal_t0 < 1.0:
                submit(-1)
                cal_n += 1
            base_rate = cal_n / (time.monotonic() - cal_t0)
            # cap the offered rate: the smoke proves shedding + SLOs,
            # not raw throughput, and the restarted victim must replay
            # whatever the burst committed inside the smoke budget
            burst_rate = min(500.0, max(100.0, 10.0 * base_rate))

            # tier-0 plane: heartbeats keep flowing through the burst;
            # a RetryLater here fails the smoke outright
            hb_stop = threading.Event()
            hb_lat: list = []
            hb_shed = [0]
            hb_err = [0]

            def heartbeats():
                k = 0
                while not hb_stop.is_set():
                    n = nodes[k % len(nodes)]
                    k += 1
                    h0 = time.monotonic()
                    try:
                        (cluster.leader()
                         or _live_entry(cluster)).heartbeat(n.id)
                    except RetryLater:
                        with lock:
                            hb_shed[0] += 1
                    except Exception:
                        # failover window: forwarding errors are
                        # liveness noise, not sheds
                        with lock:
                            hb_err[0] += 1
                    else:
                        with lock:
                            hb_lat.append(time.monotonic() - h0)
                    hb_stop.wait(0.1)

            hb_thread = threading.Thread(target=heartbeats, daemon=True)
            hb_thread.start()
            time.sleep(1.0)  # unloaded heartbeat baseline
            with lock:
                base_hb = sorted(hb_lat)
                base_p99 = base_hb[int(0.99 * (len(base_hb) - 1))] \
                    if base_hb else 0.05
                hb_lat.clear()

            victim = (cluster.leader() or leader).id

            def crash_mid_burst():
                time.sleep(burst_s / 2)
                cluster.crash(victim)

            crasher = threading.Thread(target=crash_mid_burst,
                                       daemon=True)
            crasher.start()
            res = run_open_loop(submit, rate=burst_rate,
                                duration=burst_s,
                                seed=seed_from_env(), workers=workers)
            crasher.join(timeout=burst_s + 10.0)

            fresh = cluster.wait_for_leader(timeout=20.0)
            if fresh is None:
                print("LOAD SMOKE: FAIL — no leader after the crash")
                return 2
            cluster.restart(victim)
            # let the admitted backlog drain before auditing the FSM
            deadline = time.time() + 120
            while time.time() < deadline:
                fresh = cluster.leader() or fresh
                if fresh.server._running and fresh.server.wait_for_idle(
                        timeout=10.0, include_delayed=False):
                    break
                time.sleep(0.1)
            hb_stop.set()
            hb_thread.join(timeout=10.0)

            with lock:
                burst_hb = sorted(hb_lat)
                burst_p99 = burst_hb[int(0.99 * (len(burst_hb) - 1))] \
                    if burst_hb else 0.0

            # -- assertions --
            if hb_shed[0]:
                failures.append(
                    f"tier-0 SLO: {hb_shed[0]} heartbeat(s) shed")
            # absolute floor: under full CPU saturation the tail is
            # GIL hand-off, not queueing the plane controls. With the
            # nomadown sanitizer armed every FSM write also pays the
            # fingerprint sweep, so the floor doubles — still 5x
            # inside the 10 s heartbeat TTL.
            hb_floor = 2.0 if os.environ.get("NOMAD_TPU_SAN") == "1" \
                else 1.0
            if burst_p99 > max(10.0 * base_p99, hb_floor):
                failures.append(
                    f"tier-0 SLO: heartbeat p99 {burst_p99 * 1e3:.0f}ms "
                    f"under burst vs {base_p99 * 1e3:.0f}ms unloaded")
            if res["ok"] == 0:
                failures.append("no submit was admitted during the burst")
            if res["shed"] == 0:
                failures.append(
                    f"admission plane never engaged at 10x "
                    f"(rate {burst_rate:.0f}/s, {res})")
            snap = fresh.local_store.snapshot()
            have = {j.id for j in snap.jobs()}
            lost = [j for j in acked_jobs if j not in have]
            if lost:
                failures.append(
                    f"{len(lost)} acked job(s) lost across the "
                    f"failover: {[i[:8] for i in lost[:5]]}")
            checker.check_convergence(cluster, timeout=90.0)
            checker.check_node_liveness(cluster)
            checker.check_all(cluster)  # includes overload ordering

            if failures:
                print("LOAD SMOKE: FAIL —")
                for f in failures[:20]:
                    print(f"  {f}")
                return 2
        finally:
            cluster.stop()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    dt = time.monotonic() - t0
    print(f"LOAD SMOKE: ok — {res['offered']} offered at "
          f"{burst_rate:.0f}/s (10x of {base_rate:.0f}/s), "
          f"{res['ok']} admitted / {res['shed']} shed / "
          f"{res['errors']} errors across a leader crash, "
          f"{len(acked_jobs)} acked jobs all survived, heartbeat p99 "
          f"{burst_p99 * 1e3:.0f}ms (unloaded {base_p99 * 1e3:.0f}ms), "
          f"0 tier-0 sheds, {checker.stats['checks']} invariant "
          f"sweeps, {dt:.1f}s")
    return 0


def flow_smoke(jobs_n: int = 120, nodes_n: int = 40,
               workers: int = 4) -> int:
    """Event-completeness smoke (scripts/check.sh --flow-smoke): the
    e2e pipeline on a durable 3-node cluster with the nomadflow shadow
    tracker force-armed, so every server construction auto-attaches a
    shadow replica that replays the Allocation/Node/Evaluation stream
    and fingerprint-compares against MVCC snapshot rebuilds. One leader
    crash/restart mid-stream (the restarted server resyncs through the
    restore-truncation path). Asserts: zero shadow divergences on ANY
    replica — including the crashed one's final pre-crash state — plus
    the standard safety sweep (which now includes invariant
    check_event_completeness)."""
    import shutil

    from ..analysis import shadow
    from ..core.server import ServerConfig
    from ..raft.cluster import RaftCluster
    from .invariants import InvariantChecker

    t0 = time.monotonic()

    def config_fn(_i: int) -> ServerConfig:
        return ServerConfig(
            num_workers=workers, eval_batch_size=8,
            heartbeat_ttl=3600.0, gc_interval=3600.0, nack_timeout=900.0,
            failed_eval_followup_delay=3600.0,
            failed_eval_unblock_interval=0.5)

    tmp = tempfile.mkdtemp(prefix="nomad-flow-smoke-")
    checker = InvariantChecker()
    was_active = shadow.GLOBAL.active
    shadow.install()   # arm BEFORE any server constructs its broker
    try:
        cluster = RaftCluster(3, config_fn=config_fn, data_dir=tmp)
        cluster.start()
        try:
            leader = cluster.wait_for_leader(timeout=15.0)
            if leader is None:
                print("FLOW SMOKE: FAIL — no leader elected")
                return 2
            for _ in range(nodes_n):
                leader.register_node(mock.node())
            jobs = []
            for _ in range(jobs_n):
                j = mock.job()
                j.task_groups[0].count = 1
                j.task_groups[0].tasks[0].resources.cpu = 100
                j.task_groups[0].tasks[0].resources.memory_mb = 64
                jobs.append(j)
                leader.store.upsert_job(j)
            evals = [mock.eval_for(j, create_time=time.time())
                     for j in jobs]
            leader.store.upsert_evals(evals)
            for ev in evals:
                leader.server.broker.enqueue(ev)

            # crash once genuinely mid-batch, same shape as e2e_smoke
            deadline = time.time() + 60
            while time.time() < deadline:
                snap = leader.local_store.snapshot()
                if len([a.id for a in snap.allocs()]) >= jobs_n // 4:
                    break
                time.sleep(0.002)
            else:
                print("FLOW SMOKE: FAIL — pipeline never reached the "
                      "crash window")
                return 2
            cluster.crash(leader.id)
            fresh = cluster.wait_for_leader(timeout=20.0)
            if fresh is None:
                print("FLOW SMOKE: FAIL — no leader after the crash")
                return 2
            cluster.restart(leader.id)

            deadline = time.time() + 180
            while True:
                fresh = cluster.leader() or fresh
                if fresh.server._running \
                        and fresh.server.wait_for_idle(
                            timeout=10.0, include_delayed=False) \
                        and fresh.server.blocked.blocked_count() == 0:
                    snap = fresh.local_store.snapshot()
                    placed = [a for a in snap.allocs()
                              if not a.terminal_status()
                              and not a.server_terminal()]
                    if len(placed) >= jobs_n:
                        break
                if time.time() > deadline:
                    print("FLOW SMOKE: FAIL — pipeline did not drain "
                          "after the failover")
                    return 2
                time.sleep(0.1)

            checker.check_convergence(cluster, timeout=30.0)
            checker.check_all(cluster)   # includes event completeness

            problems = shadow.GLOBAL.verify_all()
            stats = shadow.GLOBAL.stats()
            if problems:
                print(f"FLOW SMOKE: FAIL — {len(problems)} shadow "
                      f"divergence(s): {problems[0]}")
                return 2
            if stats["replicas"] < 4:   # 3 initial + the restart
                print(f"FLOW SMOKE: FAIL — only {stats['replicas']} "
                      f"shadow replicas attached; the server hook is "
                      f"not arming")
                return 2
            if stats["resyncs"] < stats["replicas"]:
                print("FLOW SMOKE: FAIL — a replica never took its "
                      "initial resync")
                return 2
        finally:
            cluster.stop()
    finally:
        if not was_active:
            shadow.uninstall()
        shadow.GLOBAL.replicas.clear()
        shutil.rmtree(tmp, ignore_errors=True)
    dt = time.monotonic() - t0
    print(f"FLOW SMOKE: ok — {jobs_n} evals across a leader restart, "
          f"{stats['replicas']} shadow replicas, {stats['commits']} "
          f"commits replayed, {stats['compares']} fingerprint compares, "
          f"{stats['resyncs']} resyncs, 0 divergences, "
          f"{checker.stats['checks']} invariant sweeps, {dt:.1f}s")
    return 0


def state_smoke(jobs_n: int = 120, nodes_n: int = 40,
                workers: int = 4) -> int:
    """Incremental-state smoke (scripts/check.sh --state-smoke): the
    e2e pipeline on a durable 3-node cluster with the nomadstate parity
    digests force-armed, so every tensor build the leader's workers run
    rides the device-resident O(Δ) base (tensor/incremental.py) and is
    periodically fingerprint-compared against gen-bounded snapshot
    rebuilds. One leader crash/restart mid-stream, then a forced
    event-ring truncation on the live leader followed by another
    scheduling round (the feed must take the resync path, never patch
    across the gap). Asserts: zero parity divergences on ANY feed —
    followers included (their epochs build from snapshot at verify
    time) — warm builds actually served off the fed base, and the
    truncation actually forced a resync."""
    import shutil

    from ..core.server import ServerConfig
    from ..raft.cluster import RaftCluster
    from ..structs import enums
    from ..structs.operator import SchedulerConfiguration
    from ..tensor import incremental
    from .invariants import InvariantChecker

    t0 = time.monotonic()

    def config_fn(_i: int) -> ServerConfig:
        return ServerConfig(
            num_workers=workers, eval_batch_size=8,
            # the tensor path is the whole point: every build must route
            # through ClusterTensors (and so the incremental feed)
            sched_config=SchedulerConfiguration(
                scheduler_algorithm=enums.SCHED_ALG_TPU_BINPACK),
            heartbeat_ttl=3600.0, gc_interval=3600.0, nack_timeout=900.0,
            failed_eval_followup_delay=3600.0,
            failed_eval_unblock_interval=0.5)

    def submit_round(node, n: int) -> None:
        jobs = []
        for _ in range(n):
            j = mock.job()
            j.task_groups[0].count = 1
            j.task_groups[0].tasks[0].resources.cpu = 100
            j.task_groups[0].tasks[0].resources.memory_mb = 64
            jobs.append(j)
            node.store.upsert_job(j)
        evals = [mock.eval_for(j, create_time=time.time()) for j in jobs]
        node.store.upsert_evals(evals)
        for ev in evals:
            node.server.broker.enqueue(ev)

    def wait_placed(cluster, fallback, want: int, timeout: float):
        deadline = time.time() + timeout
        fresh = fallback
        while True:
            fresh = cluster.leader() or fresh
            if fresh.server._running \
                    and fresh.server.wait_for_idle(
                        timeout=10.0, include_delayed=False) \
                    and fresh.server.blocked.blocked_count() == 0:
                snap = fresh.local_store.snapshot()
                placed = [a for a in snap.allocs()
                          if not a.terminal_status()
                          and not a.server_terminal()]
                if len(placed) >= want:
                    return fresh
            if time.time() > deadline:
                return None
            time.sleep(0.1)

    tmp = tempfile.mkdtemp(prefix="nomad-state-smoke-")
    checker = InvariantChecker()
    was_armed = incremental.GLOBAL.san_active
    incremental.install()   # arm the parity digests BEFORE any server
    try:
        cluster = RaftCluster(3, config_fn=config_fn, data_dir=tmp)
        cluster.start()
        try:
            leader = cluster.wait_for_leader(timeout=15.0)
            if leader is None:
                print("STATE SMOKE: FAIL — no leader elected")
                return 2
            for _ in range(nodes_n):
                leader.register_node(mock.node())
            submit_round(leader, jobs_n)

            # crash once genuinely mid-batch, same shape as flow_smoke
            deadline = time.time() + 60
            while time.time() < deadline:
                snap = leader.local_store.snapshot()
                if len([a.id for a in snap.allocs()]) >= jobs_n // 4:
                    break
                time.sleep(0.002)
            else:
                print("STATE SMOKE: FAIL — pipeline never reached the "
                      "crash window")
                return 2
            cluster.crash(leader.id)
            fresh = cluster.wait_for_leader(timeout=20.0)
            if fresh is None:
                print("STATE SMOKE: FAIL — no leader after the crash")
                return 2
            cluster.restart(leader.id)

            fresh = wait_placed(cluster, fresh, jobs_n, timeout=180.0)
            if fresh is None:
                print("STATE SMOKE: FAIL — pipeline did not drain "
                      "after the failover")
                return 2

            # force the gap contract: lap every subscription on the
            # live leader's broker, then schedule another round — the
            # feed must resync from snapshot, never patch across it
            resyncs_before = incremental.GLOBAL.stats()["resyncs"]
            fresh.server.events._truncate_all()
            submit_round(fresh, jobs_n // 4)
            fresh = wait_placed(cluster, fresh, jobs_n + jobs_n // 4,
                                timeout=120.0)
            if fresh is None:
                print("STATE SMOKE: FAIL — pipeline did not drain "
                      "after the forced truncation")
                return 2

            checker.check_convergence(cluster, timeout=30.0)
            checker.check_all(cluster)   # includes state parity (11)

            problems = incremental.GLOBAL.verify_all()
            stats = incremental.GLOBAL.stats()
            if problems:
                print(f"STATE SMOKE: FAIL — {len(problems)} parity "
                      f"divergence(s): {problems[0]}")
                return 2
            if stats["feeds"] < 4:      # 3 initial + the restart
                print(f"STATE SMOKE: FAIL — only {stats['feeds']} "
                      f"feeds attached; the server hook is not arming")
                return 2
            if stats["fast_hits"] == 0 or stats["deltas_applied"] == 0:
                print(f"STATE SMOKE: FAIL — no build ever rode the "
                      f"incremental base (fast_hits="
                      f"{stats['fast_hits']}, deltas_applied="
                      f"{stats['deltas_applied']}); the O(Δ) path is "
                      f"not engaging")
                return 2
            if stats["resyncs"] <= resyncs_before:
                print("STATE SMOKE: FAIL — the forced ring truncation "
                      "never drove a feed resync")
                return 2
            if stats["parity_checks"] == 0:
                print("STATE SMOKE: FAIL — no parity digest ever ran")
                return 2
        finally:
            cluster.stop()
    finally:
        if not was_armed:
            incremental.uninstall()
        incremental.GLOBAL.feeds.clear()
        shutil.rmtree(tmp, ignore_errors=True)
    dt = time.monotonic() - t0
    print(f"STATE SMOKE: ok — {jobs_n + jobs_n // 4} evals across a "
          f"leader restart + forced truncation, {stats['feeds']} feeds, "
          f"{stats['builds']} builds ({stats['fast_hits']} off the fed "
          f"base), {stats['deltas_applied']} deltas applied, "
          f"{stats['resyncs']} resyncs, {stats['parity_checks']} parity "
          f"digests, 0 divergences, {checker.stats['checks']} invariant "
          f"sweeps, {dt:.1f}s")
    return 0


def solve_smoke(nodes_n: int = 40, jobs_n: int = 4,
                count: int = 256) -> int:
    """Global-batch solve smoke (scripts/check.sh --solve-smoke): a
    live 3-node cluster with batched workers under "tpu-solve", jobs
    sized to engage the bulk tier (count >= scheduler/reconcile
    BULK_PLACE_MIN).
    Asserts: every placement lands, at least one whole worker batch
    went through the joint auction launch, the selected assignment's
    packing score is >= the in-launch greedy counterfactual (the
    portfolio guarantee, checked end to end), and the alloc-set
    uniqueness + safety invariants hold on every replica.

    A second leg exercises in-kernel preemption end to end: a
    low-priority filler eats the head room, then a high-priority batch
    job must preempt its way on. Asserts the whole preemption wave
    resolved through kernels.preempt_solve (host_preempted delta == 0,
    kernel_preempted > 0) and re-runs the full invariant sweep (alloc
    uniqueness on every replica) over the post-eviction state."""
    import shutil

    from ..core.server import ServerConfig
    from ..structs import enums
    from ..structs.operator import PreemptionConfig, SchedulerConfiguration
    from .invariants import InvariantChecker

    t0 = time.monotonic()

    def config_fn(_i: int) -> ServerConfig:
        return ServerConfig(
            num_workers=2, eval_batch_size=4,
            sched_config=SchedulerConfiguration(
                scheduler_algorithm=enums.SCHED_ALG_TPU_SOLVE,
                preemption_config=PreemptionConfig(
                    batch_scheduler_enabled=True,
                    service_scheduler_enabled=True)),
            heartbeat_ttl=3600.0, gc_interval=3600.0, nack_timeout=900.0,
            failed_eval_followup_delay=3600.0,
            failed_eval_unblock_interval=0.5)

    tmp = tempfile.mkdtemp(prefix="nomad-solve-smoke-")
    checker = InvariantChecker()
    try:
        cluster = RaftCluster(3, config_fn=config_fn, data_dir=tmp)
        cluster.start()
        try:
            leader = cluster.wait_for_leader(timeout=15.0)
            if leader is None:
                print("SOLVE SMOKE: FAIL — no leader elected")
                return 2
            for i in range(nodes_n):
                n = mock.node()
                n.resources.cpu = 16000
                n.resources.memory_mb = 32768
                n.compute_class()
                leader.register_node(n)

            from ..tensor.solver import get_service
            svc0 = dict(get_service().stats)

            jobs = []
            for i in range(jobs_n):
                j = mock.batch_job()
                tg = j.task_groups[0]
                tg.count = count
                tg.tasks[0].resources.cpu = (50, 80, 120, 60)[i % 4]
                tg.tasks[0].resources.memory_mb = (48, 96, 64, 128)[i % 4]
                jobs.append(j)
                leader.register_job(j)

            deadline = time.time() + 240
            while True:
                if leader.server.wait_for_idle(
                        timeout=10.0, include_delayed=False) \
                        and leader.server.blocked.blocked_count() == 0:
                    break
                if time.time() > deadline:
                    print("SOLVE SMOKE: FAIL — pipeline did not drain")
                    return 2
                time.sleep(0.1)

            checker.check_convergence(cluster, timeout=30.0)
            checker.check_all(cluster)

            snap = leader.local_store.snapshot()
            placed = [a for a in snap.allocs()
                      if not a.terminal_status() and not a.server_terminal()]
            want = jobs_n * count
            if len(placed) != want:
                print(f"SOLVE SMOKE: FAIL — {len(placed)}/{want} "
                      f"placements landed")
                return 2
            ids = {a.id for a in placed}
            if len(ids) != len(placed):
                print("SOLVE SMOKE: FAIL — duplicate alloc ids")
                return 2

            svc = get_service().stats
            launches = svc["joint_launches"] - svc0.get("joint_launches", 0)
            score_s = svc["joint_score"] - svc0.get("joint_score", 0.0)
            score_g = svc["greedy_score"] - svc0.get("greedy_score", 0.0)
            if launches < 1:
                print("SOLVE SMOKE: FAIL — no batch reached the joint "
                      "auction tier (joint_launches == 0)")
                return 2
            if score_s < score_g - 1e-3:
                print(f"SOLVE SMOKE: FAIL — selected packing score "
                      f"{score_s:.3f} below the greedy counterfactual "
                      f"{score_g:.3f}")
                return 2

            # -- preemption leg: filler (prio 20) eats the head room,
            # then a high-priority batch job preempts its way on. Every
            # row must resolve through the kernel's victim columns —
            # the exact host scanner staying cold IS the assertion.
            from ..tensor.placer import preempt_stats
            pstats0 = preempt_stats()

            def drain(label: str) -> bool:
                deadline = time.time() + 240
                while True:
                    if leader.server.wait_for_idle(
                            timeout=10.0, include_delayed=False) \
                            and leader.server.blocked.blocked_count() == 0:
                        return True
                    if time.time() > deadline:
                        print(f"SOLVE SMOKE: FAIL — {label} did not "
                              f"drain")
                        return False
                    time.sleep(0.1)

            filler = mock.batch_job()
            filler.priority = 20
            ftg = filler.task_groups[0]
            ftg.count = nodes_n
            ftg.tasks[0].resources.cpu = 8000
            ftg.tasks[0].resources.memory_mb = 13000
            leader.register_job(filler)
            if not drain("preemption filler"):
                return 2
            hi = mock.batch_job()
            hi.priority = 80
            htg = hi.task_groups[0]
            htg.count = count
            htg.tasks[0].resources.cpu = 1500
            htg.tasks[0].resources.memory_mb = 2000
            leader.register_job(hi)
            if not drain("preemption wave"):
                return 2

            pdelta = {key: val - pstats0[key]
                      for key, val in preempt_stats().items()}
            kpre, hpre = (pdelta["kernel_preempted"],
                          pdelta["host_preempted"])
            if kpre < 1:
                print("SOLVE SMOKE: FAIL — the preemption wave never "
                      "reached the kernel (kernel_preempted == 0)")
                return 2
            if hpre != 0:
                print(f"SOLVE SMOKE: FAIL — {hpre} preemption(s) "
                      f"routed through the exact host scanner on the "
                      f"bulk path (expected 0)")
                return 2
            snap = leader.local_store.snapshot()
            hi_placed = [a for a in snap.allocs_by_job(hi.id)
                         if not a.terminal_status()
                         and not a.server_terminal()]
            if len(hi_placed) != count:
                print(f"SOLVE SMOKE: FAIL — {len(hi_placed)}/{count} "
                      f"high-priority placements landed")
                return 2
            # post-eviction state: uniqueness + safety on every replica
            checker.check_convergence(cluster, timeout=30.0)
            checker.check_all(cluster)
        finally:
            cluster.stop()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    dt = time.monotonic() - t0
    print(f"SOLVE SMOKE: ok — {want} placements via {launches} joint "
          f"launch(es), selected score {score_s:.2f} >= greedy "
          f"{score_g:.2f}, preemption wave {len(hi_placed)} placements "
          f"({kpre} in-kernel, {hpre} host), "
          f"{checker.stats['checks']} invariant sweeps, {dt:.1f}s")
    return 0


def mesh_smoke(nodes_n: int = 40, jobs_n: int = 4,
               count: int = 256) -> int:
    """Multi-chip C2M smoke (scripts/check.sh --mesh-smoke): the live
    3-node cluster pipeline with the solver service running on the
    8-virtual-device mesh (check.sh exports
    XLA_FLAGS=--xla_force_host_platform_device_count=8 before jax
    imports). Batched workers under "tpu-solve" drive node-sharded
    joint launches end to end; asserts every placement lands, the
    sharded engine actually engaged (sharded launches > 0 at
    mesh_devices == 8, with live all-gather accounting and ZERO warm
    retraces), and the alloc-set uniqueness + safety invariants hold
    on every replica."""
    import os
    import shutil

    import jax

    from ..core.server import ServerConfig
    from ..structs import enums
    from ..structs.operator import SchedulerConfiguration
    from .invariants import InvariantChecker

    t0 = time.monotonic()
    if len(jax.devices()) < 2:
        print("MESH SMOKE: FAIL — single-device jax backend; export "
              "XLA_FLAGS=--xla_force_host_platform_device_count=8 "
              "before launching (scripts/check.sh --mesh-smoke does)")
        return 2
    os.environ["NOMAD_TPU_MESH_DEVICES"] = "8"

    def config_fn(_i: int) -> ServerConfig:
        return ServerConfig(
            num_workers=2, eval_batch_size=4,
            sched_config=SchedulerConfiguration(
                scheduler_algorithm=enums.SCHED_ALG_TPU_SOLVE),
            heartbeat_ttl=3600.0, gc_interval=3600.0, nack_timeout=900.0,
            failed_eval_followup_delay=3600.0,
            failed_eval_unblock_interval=0.5)

    tmp = tempfile.mkdtemp(prefix="nomad-mesh-smoke-")
    checker = InvariantChecker()
    try:
        cluster = RaftCluster(3, config_fn=config_fn, data_dir=tmp)
        cluster.start()
        try:
            leader = cluster.wait_for_leader(timeout=15.0)
            if leader is None:
                print("MESH SMOKE: FAIL — no leader elected")
                return 2
            for _ in range(nodes_n):
                n = mock.node()
                n.resources.cpu = 16000
                n.resources.memory_mb = 32768
                n.compute_class()
                leader.register_node(n)

            from ..tensor.solver import get_service
            svc0 = dict(get_service().stats)

            jobs = []
            for i in range(jobs_n):
                j = mock.batch_job()
                tg = j.task_groups[0]
                tg.count = count
                tg.tasks[0].resources.cpu = (50, 80, 120, 60)[i % 4]
                tg.tasks[0].resources.memory_mb = (48, 96, 64, 128)[i % 4]
                jobs.append(j)
                leader.register_job(j)

            deadline = time.time() + 240
            while True:
                if leader.server.wait_for_idle(
                        timeout=10.0, include_delayed=False) \
                        and leader.server.blocked.blocked_count() == 0:
                    break
                if time.time() > deadline:
                    print("MESH SMOKE: FAIL — pipeline did not drain")
                    return 2
                time.sleep(0.1)

            checker.check_convergence(cluster, timeout=30.0)
            checker.check_all(cluster)

            snap = leader.local_store.snapshot()
            placed = [a for a in snap.allocs()
                      if not a.terminal_status() and not a.server_terminal()]
            want = jobs_n * count
            if len(placed) != want:
                print(f"MESH SMOKE: FAIL — {len(placed)}/{want} "
                      f"placements landed")
                return 2
            if len({a.id for a in placed}) != len(placed):
                print("MESH SMOKE: FAIL — duplicate alloc ids")
                return 2

            svc = get_service().stats
            delta = {k: svc[k] - svc0.get(k, 0) for k in svc}
            if svc.get("mesh_devices", 0) != 8:
                print(f"MESH SMOKE: FAIL — solver mesh has "
                      f"{svc.get('mesh_devices', 0)} devices, wanted 8")
                return 2
            if delta.get("sharded", 0) < 1:
                print("MESH SMOKE: FAIL — no launch ran through the "
                      "node-sharded engine (sharded == 0)")
                return 2
            if delta.get("joint_launches", 0) < 1:
                print("MESH SMOKE: FAIL — no batch reached the joint "
                      "auction tier (joint_launches == 0)")
                return 2
            if delta.get("allgathers", 0) < 1:
                print("MESH SMOKE: FAIL — sharded launches ran but the "
                      "all-gather accounting stayed at 0")
                return 2
            if delta.get("retraces", 0) != 0:
                print(f"MESH SMOKE: FAIL — {delta['retraces']} warm "
                      f"retrace(s) under the no_retrace window")
                return 2
        finally:
            cluster.stop()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    dt = time.monotonic() - t0
    print(f"MESH SMOKE: ok — {want} placements via "
          f"{delta.get('sharded', 0)} sharded launch(es) "
          f"({delta.get('joint_launches', 0)} joint) on an 8-device "
          f"mesh, {delta.get('allgathers', 0)} all-gathers, "
          f"0 retraces, {checker.stats['checks']} invariant sweeps, "
          f"{dt:.1f}s")
    return 0


def snap_smoke(jobs_n: int = 200, nodes_n: int = 60, workers: int = 4,
               snapshot_threshold: int = 120) -> int:
    """Snapshot/compaction smoke (scripts/check.sh --snap-smoke): the
    e2e pipeline runs on a durable 3-node cluster with a snapshot
    threshold low enough that every replica snapshots + compacts under
    load. One follower is crashed and its data_dir wiped AFTER the
    leader has compacted past the wiped state, so the restart can only
    catch up via the chunked install-snapshot path — mid-traffic.
    Asserts: the wiped follower converges, zero acked-commit loss on
    every replica, alloc-set uniqueness on every replica, and the full
    invariant sweep passes."""
    import os
    import shutil

    from ..core.server import ServerConfig
    from ..raft.cluster import RaftCluster
    from .invariants import InvariantChecker

    t0 = time.monotonic()

    def config_fn(_i: int) -> ServerConfig:
        return ServerConfig(
            num_workers=workers, eval_batch_size=8,
            heartbeat_ttl=3600.0, gc_interval=3600.0, nack_timeout=900.0,
            failed_eval_followup_delay=3600.0,
            failed_eval_unblock_interval=0.5)

    tmp = tempfile.mkdtemp(prefix="nomad-snap-smoke-")
    checker = InvariantChecker()
    try:
        cluster = RaftCluster(3, config_fn=config_fn, data_dir=tmp,
                              snapshot_threshold=snapshot_threshold)
        cluster.start()
        try:
            leader = cluster.wait_for_leader(timeout=15.0)
            if leader is None:
                print("SNAP SMOKE: FAIL — no leader elected")
                return 2
            # shrink the transfer chunk so the install is genuinely
            # multi-frame at this store size
            for s in cluster.servers.values():
                s.raft.snapshot_chunk_bytes = 64 * 1024

            for _ in range(nodes_n):
                leader.register_node(mock.node())
            jobs = []
            for _ in range(jobs_n):
                j = mock.job()
                j.task_groups[0].count = 1
                j.task_groups[0].tasks[0].resources.cpu = 100
                j.task_groups[0].tasks[0].resources.memory_mb = 64
                jobs.append(j)
                leader.store.upsert_job(j)
            evals = [mock.eval_for(j, create_time=time.time())
                     for j in jobs]
            leader.store.upsert_evals(evals)
            for ev in evals:
                leader.server.broker.enqueue(ev)

            # wipe window: some allocs committed (acked), many evals
            # still in flight, and the leader has already compacted —
            # so the wiped follower's entries are physically gone
            deadline = time.time() + 90
            while time.time() < deadline:
                snap = leader.local_store.snapshot()
                committed = [a.id for a in snap.allocs()]
                if len(committed) >= jobs_n // 4 \
                        and leader.raft.log.base_index > 0:
                    break
                time.sleep(0.002)
            else:
                print("SNAP SMOKE: FAIL — pipeline never reached the "
                      "wipe window (committed allocs + a compaction)")
                return 2
            acked = set(committed)
            leader_base = leader.raft.log.base_index

            victim_id = next(i for i, s in cluster.servers.items()
                             if s is not leader)
            old = cluster.crash(victim_id)
            shutil.rmtree(os.path.join(old.data_dir, "raft"),
                          ignore_errors=True)
            victim = cluster.restart(victim_id)

            # drain with the wiped follower racing its chunked install
            # against live plan traffic
            deadline = time.time() + 180
            while True:
                if leader.server._running \
                        and leader.server.wait_for_idle(
                            timeout=10.0, include_delayed=False) \
                        and leader.server.blocked.blocked_count() == 0:
                    snap = leader.local_store.snapshot()
                    placed = [a for a in snap.allocs()
                              if not a.terminal_status()
                              and not a.server_terminal()]
                    if len(placed) >= jobs_n:
                        break
                if time.time() > deadline:
                    print("SNAP SMOKE: FAIL — pipeline did not drain "
                          "after the follower wipe")
                    return 2
                time.sleep(0.1)

            checker.check_convergence(cluster, timeout=60.0)
            checker.check_all(cluster)

            # the wiped follower can't have replayed entries <= the
            # leader's pre-wipe base from its (empty) log: a base past
            # that point proves the chunked install delivered it
            if victim.raft.log.base_index < leader_base:
                print(f"SNAP SMOKE: FAIL — wiped follower base "
                      f"{victim.raft.log.base_index} < leader's "
                      f"pre-wipe base {leader_base}; catch-up did not "
                      f"go through install-snapshot")
                return 2
            if victim.raft.snapshots.last_index <= 0:
                print("SNAP SMOKE: FAIL — wiped follower has no "
                      "persisted snapshot after catch-up")
                return 2

            for sid, s in cluster.servers.items():
                snap = s.local_store.snapshot()
                ids = [a.id for a in snap.allocs()]
                if len(ids) != len(set(ids)):
                    print(f"SNAP SMOKE: FAIL — duplicate alloc ids on "
                          f"{sid}")
                    return 2
                lost = acked - set(ids)
                if lost:
                    print(f"SNAP SMOKE: FAIL — {len(lost)} acked "
                          f"alloc(s) missing on {sid}: "
                          f"{sorted(i[:8] for i in lost)[:5]}")
                    return 2
        finally:
            cluster.stop()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    dt = time.monotonic() - t0
    print(f"SNAP SMOKE: ok — {jobs_n} evals, {len(acked)} allocs acked "
          f"pre-wipe all present on every replica, wiped follower "
          f"caught up via chunked install (base {leader_base} -> "
          f"{victim.raft.log.base_index}), "
          f"{checker.stats['checks']} invariant sweeps, {dt:.1f}s")
    return 0


def swarm_smoke(nodes_n: int = 200, ttl: float = 2.0,
                crashes: int = 3) -> int:
    """Client-plane flap-churn smoke (scripts/check.sh --swarm-smoke):
    200 sim nodes heartbeating through the batch endpoints while a
    churn loop registers/deregisters a rolling slice and THREE leaders
    crash in sequence. Asserts: no stable node is ever wrongly marked
    down (check_node_liveness on every replica), silenced nodes expire
    only after a real >= TTL silence and recover on their next beat,
    allocs pushed to sim nodes are acked without loss, and the
    alloc-uniqueness + safety invariants hold."""
    import shutil

    from ..core.server import ServerConfig
    from ..raft.cluster import RaftCluster
    from ..structs import enums as _enums
    from .invariants import InvariantChecker
    from .swarm import Swarm

    t0 = time.monotonic()

    def config_fn(_i: int) -> ServerConfig:
        return ServerConfig(
            num_workers=2, eval_batch_size=8,
            heartbeat_ttl=ttl, heartbeat_shards=4,
            heartbeat_expiry_rate=128.0,
            gc_interval=3600.0, nack_timeout=900.0,
            failed_eval_followup_delay=3600.0,
            failed_eval_unblock_interval=0.5)

    tmp = tempfile.mkdtemp(prefix="nomad-swarm-smoke-")
    checker = InvariantChecker()
    try:
        cluster = RaftCluster(3, config_fn=config_fn, data_dir=tmp)
        cluster.start()
        stop_churn = threading.Event()
        churn_thread = None
        swarm = None
        try:
            leader = cluster.wait_for_leader(timeout=15.0)
            if leader is None:
                print("SWARM SMOKE: FAIL — no leader elected")
                return 2

            def entry():
                return cluster.leader()

            swarm = Swarm(entry, nodes_n, ttl=ttl, interval=ttl / 4.0,
                          drivers=2, rpc_batch=64, ack=True)
            if swarm.register_all(chunk=50) != nodes_n:
                print("SWARM SMOKE: FAIL — fleet registration timed out")
                return 2

            # stable population: never churned, never silenced — these
            # must NEVER be marked down across all three failovers
            churn_pool = swarm.nodes[-60:]
            silence_pool = swarm.nodes[:20]
            stable = swarm.nodes[20:-60]

            # a real workload rides the sim nodes: its allocs must be
            # pushed out via delta sync and acked back without loss
            for _ in range(30):
                j = mock.job()
                j.task_groups[0].count = 2
                j.task_groups[0].tasks[0].resources.cpu = 50
                j.task_groups[0].tasks[0].resources.memory_mb = 32
                leader.register_job(j)

            swarm.start()

            def churn():
                i = 0
                while not stop_churn.is_set():
                    batch = churn_pool[i % 3::3]
                    swarm.deregister(batch)
                    if stop_churn.wait(0.3):
                        return
                    swarm.register_all(chunk=50, deadline_s=20.0,
                                       subset=batch)
                    if stop_churn.wait(0.3):
                        return
                    i += 1

            churn_thread = threading.Thread(target=churn, daemon=True,
                                            name="swarm-churn")
            churn_thread.start()

            for round_i in range(crashes):
                victim = cluster.wait_for_leader(timeout=15.0)
                if victim is None:
                    print("SWARM SMOKE: FAIL — lost the leader before "
                          f"crash round {round_i}")
                    return 2
                cluster.crash(victim.id)
                fresh = cluster.wait_for_leader(timeout=20.0)
                if fresh is None:
                    print("SWARM SMOKE: FAIL — no leader after crash "
                          f"round {round_i}")
                    return 2
                cluster.restart(victim.id)
                # let the fleet beat through the new leader's grace
                # window before sweeping
                time.sleep(ttl * 1.5)
                checker.check_all(cluster)
                checker.check_node_liveness(cluster, swarm=swarm, ttl=ttl)

            stop_churn.set()
            churn_thread.join(timeout=30.0)

            # no stable node may ever have been wrongly expired
            leader = cluster.wait_for_leader(timeout=15.0)
            deadline = time.time() + 60
            stable_ids = {sn.id for sn in stable}
            while True:
                snap = leader.local_store.snapshot()
                bad = [n.id for n in snap.nodes()
                       if n.id in stable_ids
                       and n.status != _enums.NODE_STATUS_READY]
                if not bad:
                    break
                if time.time() > deadline:
                    print(f"SWARM SMOKE: FAIL — {len(bad)} stable "
                          f"node(s) not ready after churn+crashes: "
                          f"{bad[:5]}")
                    return 2
                time.sleep(0.2)

            # silenced nodes must expire (real silence >= TTL)...
            swarm.silence(silence_pool)
            silence_ids = {sn.id for sn in silence_pool}
            deadline = time.time() + ttl * 10 + 30
            while True:
                snap = leader.local_store.snapshot()
                down = [n.id for n in snap.nodes()
                        if n.id in silence_ids
                        and n.status in (_enums.NODE_STATUS_DOWN,
                                         _enums.NODE_STATUS_DISCONNECTED)]
                if len(down) == len(silence_ids):
                    break
                if time.time() > deadline:
                    print(f"SWARM SMOKE: FAIL — only {len(down)}/"
                          f"{len(silence_ids)} silenced nodes expired")
                    return 2
                time.sleep(0.2)
            checker.check_node_liveness(cluster, swarm=swarm, ttl=ttl)

            # ...and recover to ready on their next successful beat
            swarm.unsilence(silence_pool)
            deadline = time.time() + 60
            while True:
                snap = leader.local_store.snapshot()
                ready = [n.id for n in snap.nodes()
                         if n.id in silence_ids
                         and n.status == _enums.NODE_STATUS_READY]
                if len(ready) == len(silence_ids):
                    break
                if time.time() > deadline:
                    print(f"SWARM SMOKE: FAIL — only {len(ready)}/"
                          f"{len(silence_ids)} silenced nodes recovered")
                    return 2
                time.sleep(0.2)

            # every live desired-run alloc on a registered sim node must
            # end up acked running — delta push + batched acks, no loss
            deadline = time.time() + 120
            while True:
                leader = cluster.wait_for_leader(timeout=15.0)
                snap = leader.local_store.snapshot()
                pending = [a.id for a in snap.allocs()
                           if a.node_id in swarm.ids()
                           and not a.terminal_status()
                           and not a.server_terminal()
                           and a.desired_status == _enums.ALLOC_DESIRED_RUN
                           and a.client_status != _enums.ALLOC_CLIENT_RUNNING]
                placed = [a for a in snap.allocs()
                          if not a.terminal_status()
                          and not a.server_terminal()]
                if not pending and placed:
                    break
                if time.time() > deadline:
                    print(f"SWARM SMOKE: FAIL — {len(pending)} alloc "
                          f"ack(s) still missing: {pending[:5]}")
                    return 2
                time.sleep(0.2)

            checker.check_convergence(cluster, timeout=30.0)
            checker.check_all(cluster)
            checker.check_node_liveness(cluster, swarm=swarm, ttl=ttl)
            beats = swarm.total_beats()
            acked = len(swarm.acked_ids)
            expiries = sum(
                s.server.heartbeats.stats["invalidated"]
                for s in cluster.servers.values() if not s.crashed)
        finally:
            stop_churn.set()
            if swarm is not None:
                swarm.stop()
            if churn_thread is not None:
                churn_thread.join(timeout=5.0)
            cluster.stop()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    dt = time.monotonic() - t0
    print(f"SWARM SMOKE: ok — {nodes_n} sim nodes, {beats} heartbeats, "
          f"{crashes} leader crashes, {len(silence_pool)} real expiries "
          f"(total {expiries}) all attributed, {acked} allocs acked, "
          f"{checker.stats['checks']} invariant sweeps, {dt:.1f}s")
    return 0


def swarm_scale_smoke(nodes_n: int = 50000, ttl: float = 10.0,
                      jobs_n: int = 150) -> int:
    """The ROADMAP acceptance run: 50K+ sim nodes heartbeating at the
    production TTL against a live 3-node cluster WHILE the e2e3 write
    pipeline runs, one leader crash/failover mid-stream, and ZERO
    missed-TTL false positives — verified by check_node_liveness on
    every replica. Heavy (minutes); run explicitly via
    `python -m nomad_tpu.chaos --swarm-scale [N]`."""
    import shutil

    from ..core.server import ServerConfig
    from ..raft.cluster import RaftCluster
    from ..structs import enums as _enums
    from .invariants import InvariantChecker
    from .swarm import Swarm

    t0 = time.monotonic()

    def config_fn(_i: int) -> ServerConfig:
        return ServerConfig(
            num_workers=4, eval_batch_size=8,
            heartbeat_ttl=ttl, heartbeat_shards=8,
            gc_interval=3600.0, nack_timeout=900.0,
            failed_eval_followup_delay=3600.0,
            failed_eval_unblock_interval=0.5)

    tmp = tempfile.mkdtemp(prefix="nomad-swarm-scale-")
    checker = InvariantChecker()
    try:
        cluster = RaftCluster(3, config_fn=config_fn, data_dir=tmp,
                              snapshot_threshold=8192)
        cluster.start()
        swarm = None
        try:
            leader = cluster.wait_for_leader(timeout=15.0)
            if leader is None:
                print("SWARM SCALE: FAIL — no leader elected")
                return 2

            def entry():
                return cluster.leader()

            swarm = Swarm(entry, nodes_n, ttl=ttl, interval=3.0,
                          drivers=8, rpc_batch=1024, ack=True)
            # drivers first, registration second: a real fleet ramps —
            # each node starts heartbeating the moment it registers. A
            # fleet-sized registration takes several TTLs, so arming
            # 50K timers and only then starting the beats would expire
            # (and revive) every early chunk purely as a harness
            # artifact.
            swarm.start()
            reg_t0 = time.monotonic()
            if swarm.register_all(chunk=1000, deadline_s=600.0) != nodes_n:
                print("SWARM SCALE: FAIL — fleet registration timed out")
                return 2
            reg_dt = time.monotonic() - reg_t0

            # registration load can move leadership; re-resolve, and
            # retry workload proposals through any further election
            def propose(fn):
                nonlocal leader
                deadline = time.time() + 60
                while True:
                    try:
                        return fn(leader)
                    except Exception:
                        if time.time() > deadline:
                            raise
                        time.sleep(0.25)
                        leader = (cluster.wait_for_leader(timeout=30.0)
                                  or leader)

            leader = cluster.wait_for_leader(timeout=30.0) or leader

            # e2e3 write pipeline in parallel with the heartbeat storm
            jobs = []
            for _ in range(jobs_n):
                j = mock.job()
                j.task_groups[0].count = 1
                j.task_groups[0].tasks[0].resources.cpu = 100
                j.task_groups[0].tasks[0].resources.memory_mb = 64
                jobs.append(j)
                propose(lambda srv: srv.store.upsert_job(j))
            evals = [mock.eval_for(j, create_time=time.time())
                     for j in jobs]
            propose(lambda srv: srv.store.upsert_evals(evals))
            for ev in evals:
                propose(lambda srv: srv.server.broker.enqueue(ev))

            deadline = time.time() + 120
            while time.time() < deadline:
                snap = leader.local_store.snapshot()
                if len([a for a in snap.allocs()]) >= jobs_n // 4:
                    break
                time.sleep(0.05)
            else:
                print("SWARM SCALE: FAIL — pipeline never reached the "
                      "crash window")
                return 2

            hb_before = swarm.total_beats()
            victim = cluster.wait_for_leader(timeout=15.0) or leader
            cluster.crash(victim.id)
            fresh = cluster.wait_for_leader(timeout=30.0)
            if fresh is None:
                print("SWARM SCALE: FAIL — no leader after the crash")
                return 2
            cluster.restart(victim.id)

            # beat through the new leader's grace window + one full TTL
            time.sleep(ttl * 2.0)

            checker.check_all(cluster)

            # ZERO missed-TTL false positives: no sim node may END UP
            # down on any live replica. If election churn stalled a
            # driver past the TTL, that expiry is a TRUE positive — but
            # it must be attributed (checker, below) and must heal via
            # the heartbeat revival path, so recovery gets a bounded
            # window before the hard zero-down assertion.
            sim_ids = set(swarm.ids())
            down_states = (_enums.NODE_STATUS_DOWN,
                           _enums.NODE_STATUS_DISCONNECTED)

            def down_on(s):
                snap = s.local_store.snapshot()
                return [n.id for n in snap.nodes()
                        if n.id in sim_ids and n.status in down_states]

            recover_deadline = time.time() + 60.0
            while time.time() < recover_deadline:
                if not any(down_on(s) for s in cluster.servers.values()
                           if not s.crashed):
                    break
                time.sleep(0.5)
            checker.check_node_liveness(cluster, swarm=swarm, ttl=ttl)
            for s in cluster.servers.values():
                if s.crashed:
                    continue
                wrong = down_on(s)
                if wrong:
                    print(f"SWARM SCALE: FAIL — {len(wrong)} node(s) "
                          f"still down on {s.id} after the recovery "
                          f"window: {wrong[:5]}")
                    return 2

            hb_after = swarm.total_beats()
            # every expiry that did fire was verified attributable to a
            # real >= TTL silence by check_node_liveness; surface count
            expiries = sum(
                s.server.heartbeats.stats["invalidated"]
                for s in cluster.servers.values() if not s.crashed)
            checker.check_convergence(cluster, timeout=60.0)
            snap = cluster.wait_for_leader(timeout=15.0).local_store.snapshot()
            placed = len([a for a in snap.allocs()
                          if not a.terminal_status()
                          and not a.server_terminal()])
        finally:
            if swarm is not None:
                swarm.stop()
            cluster.stop()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    dt = time.monotonic() - t0
    rate = (hb_after - hb_before) / max(dt, 1e-9)
    print(f"SWARM SCALE: ok — {nodes_n} sim nodes at TTL {ttl:.0f}s, "
          f"{swarm.total_beats()} heartbeats "
          f"({hb_after - hb_before} post-crash, ~{rate:.0f}/s overall), "
          f"{placed} live allocs placed by the concurrent pipeline, "
          f"registration {reg_dt:.1f}s, {expiries} attributed "
          f"expiries and ZERO missed-TTL false positives across the "
          f"failover, {checker.stats['checks']} invariant sweeps, "
          f"{dt:.1f}s")
    return 0


def watch_smoke(watchers_per_server: int = 12) -> int:
    """Leader-failover-mid-watch smoke (scripts/check.sh --watch-smoke):
    blocking queries + event subscriptions parked on ALL 3 servers of a
    live cluster while the leader crashes. Asserts: every parked query
    on a survivor completes with the post-failover result at a higher
    index; subscriptions on survivors deliver the post-failover event;
    fresh reads against the dead server fail fast with
    X-Nomad-KnownLeader=false; and the stale-read bound
    (X-Nomad-LastContact) holds on survivors across the transition."""
    import json
    import urllib.error
    import urllib.request

    from ..api.http import HTTPAgent
    from ..core.server import ServerConfig

    t0 = time.monotonic()
    cluster = RaftCluster(3, config_fn=lambda i: ServerConfig(
        num_workers=0, heartbeat_ttl=3600.0, gc_interval=3600.0))
    agents = {}
    failures: list = []
    try:
        cluster.start()
        leader = cluster.wait_for_leader(15.0)
        if leader is None:
            print("WATCH SMOKE: FAIL — no leader elected")
            return 2
        for sid, srv in cluster.servers.items():
            agents[sid] = HTTPAgent(srv.server, port=0, writer=srv).start()

        leader.register_node(mock.node())

        def get(sid, path, timeout=10.0):
            r = urllib.request.urlopen(f"{agents[sid].address}{path}",
                                       timeout=timeout)
            return json.loads(r.read()), r.headers

        # pre-crash: every server answers with staleness headers
        want = 0
        for sid in cluster.servers:
            nodes, hdrs = get(sid, "/v1/nodes")
            if len(nodes) != 1:
                failures.append(f"{sid}: pre-crash read saw {len(nodes)}")
            if hdrs["X-Nomad-KnownLeader"] != "true":
                failures.append(f"{sid}: pre-crash KnownLeader false")
            lc = int(hdrs["X-Nomad-LastContact"])
            if lc >= 2000:
                failures.append(f"{sid}: pre-crash LastContact {lc}ms")
            want = max(want, int(hdrs["X-Nomad-Index"]))

        # park blocking queries on all 3 servers + one event
        # subscription per server
        results: dict = {}
        lock = threading.Lock()

        def block(tag, sid, wait_s):
            try:
                data, hdrs = get(
                    sid, f"/v1/nodes?index={want}&wait={wait_s}",
                    timeout=wait_s + 20.0)
                out = ("ok", len(data), int(hdrs["X-Nomad-Index"]))
            except (urllib.error.URLError, OSError) as e:
                out = ("err", repr(e), None)
            with lock:
                results[tag] = out

        subs = {sid: srv.server.events.subscribe({"Node": ["*"]})
                for sid, srv in cluster.servers.items()}
        sub_got: dict = {}

        def watch_events(sid, timeout):
            evs = subs[sid].next_events(timeout=timeout)
            with lock:
                sub_got[sid] = [e.type for e in evs]

        victim = leader.id
        threads = []
        for sid in cluster.servers:
            # parked watchers on the (about to be) dead server can only
            # time out — keep their windows short so the smoke stays fast
            wait_s = 6.0 if sid == victim else 20.0
            for i in range(watchers_per_server):
                threads.append(threading.Thread(
                    target=block, args=(f"{sid}/{i}", sid, wait_s)))
            threads.append(threading.Thread(
                target=watch_events,
                args=(sid, 8.0 if sid == victim else 25.0)))
        for t in threads:
            t.start()
        deadline = time.time() + 10.0
        while time.time() < deadline:
            parked = sum(s.store.watches.parked()
                         for s in cluster.servers.values())
            if parked >= 3 * watchers_per_server:
                break
            time.sleep(0.05)
        else:
            failures.append(f"only {parked} queries parked")

        # crash the leader mid-watch, write through a survivor
        cluster.crash(victim)
        new_leader = cluster.wait_for_leader(15.0)
        if new_leader is None:
            print("WATCH SMOKE: FAIL — no post-crash leader")
            return 2
        _live_entry(cluster).register_node(mock.node())

        for t in threads:
            t.join(timeout=40.0)
        if any(t.is_alive() for t in threads):
            failures.append("watcher threads wedged")

        for tag, out in sorted(results.items()):
            sid = tag.split("/")[0]
            if sid == victim:
                continue  # below
            if out[0] != "ok" or out[1] != 2 or out[2] <= want:
                failures.append(f"survivor watcher {tag}: {out}")
        # dead-server watchers: a timed-out long-poll returning the old
        # state at the old index is a CONSISTENT bounded-stale answer;
        # a torn connection is a fail-fast. Both are allowed — seeing
        # the post-crash write from the dead server's store is not.
        for tag, out in sorted(results.items()):
            if not tag.startswith(victim):
                continue
            if out[0] == "ok" and out[1] != 1:
                failures.append(f"dead-server watcher {tag}: {out}")
        for sid in cluster.servers:
            if sid == victim:
                continue
            if sub_got.get(sid) != ["node-upsert"]:
                failures.append(
                    f"{sid}: subscription saw {sub_got.get(sid)}")

        # fresh reads post-failover: survivors answer with a fresh
        # stale bound; the dead server fails fast, KnownLeader=false
        for sid in cluster.servers:
            if sid == victim:
                continue
            nodes, hdrs = get(sid, "/v1/nodes")
            if len(nodes) != 2:
                failures.append(f"{sid}: post-crash read {len(nodes)}")
            if hdrs["X-Nomad-KnownLeader"] != "true":
                failures.append(f"{sid}: post-crash KnownLeader false")
            if int(hdrs["X-Nomad-LastContact"]) >= 2000:
                failures.append(
                    f"{sid}: post-crash LastContact "
                    f"{hdrs['X-Nomad-LastContact']}ms")
        t1 = time.monotonic()
        try:
            get(victim, "/v1/nodes", timeout=10.0)
            failures.append("dead server served a read-index GET")
        except urllib.error.HTTPError as e:
            if e.code != 503:
                failures.append(f"dead server replied {e.code}")
            if e.headers.get("X-Nomad-KnownLeader") != "false":
                failures.append("dead server claimed KnownLeader")
        except (urllib.error.URLError, OSError):
            pass  # connection-level death is fail-fast too
        if time.monotonic() - t1 > 5.0:
            failures.append("dead-server read was not fail-fast")

        if failures:
            print("WATCH SMOKE: FAIL —")
            for f in failures[:20]:
                print(f"  {f}")
            return 2
    finally:
        for sub in locals().get("subs", {}).values():
            sub.close()
        for a in agents.values():
            a.stop()
        cluster.stop()
    dt = time.monotonic() - t0
    print(f"WATCH SMOKE: ok — {3 * watchers_per_server} parked queries "
          f"+ 3 subscriptions across a leader crash: survivors woke "
          f"consistent, dead server failed fast, stale bounds held, "
          f"{dt:.1f}s")
    return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(prog="python -m nomad_tpu.chaos")
    parser.add_argument("--seed", type=int, default=None,
                        help="fault seed (default: NOMAD_TPU_CHAOS_SEED or 0)")
    parser.add_argument("--raft-smoke", action="store_true",
                        help="run the raft group-commit crash smoke "
                             "instead of the scenario smoke")
    parser.add_argument("--e2e-smoke", action="store_true",
                        help="run the full-pipeline smoke (300 evals, "
                             "3 nodes, leader restart mid-stream) "
                             "instead of the scenario smoke")
    parser.add_argument("--solve-smoke", action="store_true",
                        help="run the global-batch solve smoke "
                             "(batched workers under tpu-solve; joint "
                             "launch, score dominance, alloc "
                             "uniqueness) instead of the scenario smoke")
    parser.add_argument("--mesh-smoke", action="store_true",
                        help="run the multi-chip C2M smoke (live "
                             "3-node cluster with the solver on an "
                             "8-virtual-device mesh; sharded joint "
                             "launches, zero retraces, alloc "
                             "uniqueness on every replica) instead of "
                             "the scenario smoke — export XLA_FLAGS="
                             "--xla_force_host_platform_device_count=8 "
                             "first (scripts/check.sh --mesh-smoke "
                             "does)")
    parser.add_argument("--snap-smoke", action="store_true",
                        help="run the snapshot/compaction smoke (low "
                             "snapshot threshold under e2e load, one "
                             "follower wiped + restarted, catch-up via "
                             "chunked install-snapshot) instead of the "
                             "scenario smoke")
    parser.add_argument("--swarm-smoke", action="store_true",
                        help="run the client-plane swarm smoke (200 sim "
                             "nodes flap-churning while 3 leaders crash "
                             "in sequence; liveness + alloc-uniqueness "
                             "on every replica) instead of the scenario "
                             "smoke")
    parser.add_argument("--load-smoke", action="store_true",
                        help="run the overload smoke (3-node cluster, "
                             "10x open-loop submit burst, leader crash "
                             "mid-burst; tier-0 heartbeat SLO, zero "
                             "acked-work loss, overload tier ordering) "
                             "instead of the scenario smoke")
    parser.add_argument("--flow-smoke", action="store_true",
                        help="run the event-completeness smoke (e2e "
                             "pipeline with nomadflow shadow replicas "
                             "force-armed on every server across a "
                             "leader crash; zero shadow divergences) "
                             "instead of the scenario smoke")
    parser.add_argument("--state-smoke", action="store_true",
                        help="run the incremental-state smoke (e2e "
                             "pipeline riding the device-resident O(Δ) "
                             "usage base across a leader crash AND a "
                             "forced event-ring truncation; parity "
                             "clean on every feed) instead of the "
                             "scenario smoke")
    parser.add_argument("--watch-smoke", action="store_true",
                        help="run the read-path failover smoke (blocking "
                             "queries + event subscriptions parked on "
                             "all 3 servers across a leader crash; "
                             "stale-read bounds + fail-fast on the dead "
                             "server) instead of the scenario smoke")
    parser.add_argument("--swarm-scale", type=int, nargs="?",
                        const=50000, default=None, metavar="N",
                        help="run the fleet-scale acceptance smoke: N "
                             "(default 50000) sim nodes at production "
                             "TTL against a live 3-node cluster with "
                             "the e2e pipeline + a leader crash; zero "
                             "missed-TTL false positives (minutes)")
    parser.add_argument("-v", "--verbose", action="store_true")
    args = parser.parse_args(argv)
    logging.basicConfig(
        level=logging.INFO if args.verbose else logging.WARNING,
        format="%(asctime)s %(name)s %(levelname)s %(message)s")
    import os
    if args.seed is not None:
        os.environ["NOMAD_TPU_CHAOS_SEED"] = str(args.seed)
    from ..tensor.backend import bootstrap

    bootstrap()
    if args.raft_smoke:
        return raft_smoke()
    if args.e2e_smoke:
        return e2e_smoke()
    if args.solve_smoke:
        return solve_smoke()
    if args.mesh_smoke:
        return mesh_smoke()
    if args.snap_smoke:
        return snap_smoke()
    if args.swarm_smoke:
        return swarm_smoke()
    if args.load_smoke:
        return load_smoke()
    if args.flow_smoke:
        return flow_smoke()
    if args.state_smoke:
        return state_smoke()
    if args.watch_smoke:
        return watch_smoke()
    if args.swarm_scale is not None:
        return swarm_scale_smoke(nodes_n=args.swarm_scale)

    t0 = time.monotonic()
    with tempfile.TemporaryDirectory(prefix="nomad-chaos-") as tmp:
        cluster = RaftCluster(3, data_dir=tmp)
        cluster.start()
        try:
            runner = build_scenario(cluster)
            try:
                report = runner.run()
            except InvariantViolation as e:
                print(f"CHAOS SMOKE: FAIL — {e} "
                      f"(reproduce: NOMAD_TPU_CHAOS_SEED={runner.seed})")
                return 2
        finally:
            cluster.stop()
    dt = time.monotonic() - t0
    print(f"CHAOS SMOKE: ok — {len(report['steps'])} steps, "
          f"seed={report['seed']}, faults={report['faults']}, "
          f"{dt:.1f}s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
