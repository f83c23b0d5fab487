#!/usr/bin/env python3
"""chip_smoke.py: the served scheduling path, once, on the chip.

    python3 chip_smoke.py [--seed N]

One process drives the system's main path through the entry points a
user calls: the agent `python -m nomad_tpu agent --algorithm tpu-binpack`
starts (cli.Agent: Server + HTTPAgent), a fleet that joins through the
registration and heartbeat RPCs (chaos.swarm.Swarm), and jobs submitted
and read back over HTTP (api.client.ApiClient). Data comes from --seed.

Legs, at the size of the deployment this system names as its north star
(BASELINE.json "C2M": 2,000,000 allocations on 10,240 nodes), uncut:

  A c2m      500 batch jobs x 4,000 allocs drained by the count solve
             (solve_bulk_multi, resident statics, the incremental feed's
             device twin, double-buffered fetch)
  B service  on A's now half-full cluster, 4 service jobs x 256 with a
             rack spread and a dynamic port: the per-placement tier
             (solve_task_group_fused at K=256)
  C joint    the same agent switched to tpu-solve over HTTP, 8 batch
             jobs x 1,000: the joint auction (solve_batch at g=16)
  D preempt  a second agent with service preemption on: 1,024 nodes
             filled exactly by priority-20 allocs, then a 512-alloc
             priority-80 service (kernels.preempt_solve on the device)
  E sweep    every jitted entry the placer can reach that A-D did not
             launch, once at C2M width against a plain numpy reference

Each leg checks what came out by means that share nothing with the
solver: a recount of a fresh snapshot (allocations per job, usage per
node against capacity, port numbers per node), the system's own
counters, and reads over HTTP. Any failed check, any exception in a
thread that served a leg, any ERROR log record fails the run.

`__main__` runs only on a TPU and takes no switch that waives it; the
legs are plain functions of their sizes, and tests/test_chip_smoke.py
drives them at TOY size on the CPU. On a host with several chips the
solver service shards by itself, and the run also asserts that it did.

The last line of standard output is one JSON object with exactly the
keys "ok" and "device" ({"platform", "kind", "count"} as JAX reports
them). Off a TPU, or alone in a directory, nothing is printed there and
the exit code is 2; a failed leg prints its traceback, "ok": false, and
exits 1.
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import importlib.util
import json
import logging
import random
import statistics
import sys
import threading
import time
import traceback

import numpy as np

CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


@dataclasses.dataclass(frozen=True)
class Sizes:
    nodes: int            # A-C fleet (the ladder's node mix)
    jobs: int             # A: batch jobs
    per_job: int          # A: allocs per job
    service_jobs: int     # B
    service_count: int    # B: allocs per job, one scan step each
    joint_jobs: int       # C
    joint_count: int      # C
    preempt_nodes: int    # D: uniform nodes, filled exactly
    preempt_hi: int       # D: high-priority allocs that must preempt
    workers: int          # scheduler workers of the A-C agent
    http_sample: int      # A: jobs whose allocations are read over HTTP
    rtt_reps: int         # round-trip probe repetitions


# BASELINE.json "C2M", uncut
FULL = Sizes(nodes=10_240, jobs=500, per_job=4_000, service_jobs=4,
             service_count=256, joint_jobs=8, joint_count=1_000,
             preempt_nodes=1_024, preempt_hi=512, workers=24,
             http_sample=3, rtt_reps=200)
# same legs, same code paths (every count clears BULK_PLACE_MIN,
# HOST_CUTOVER and PREEMPT_DEVICE_MIN), at a size a CPU test can afford
TOY = Sizes(nodes=256, jobs=48, per_job=300, service_jobs=2,
            service_count=32, joint_jobs=8, joint_count=256,
            preempt_nodes=512, preempt_hi=512, workers=8,
            http_sample=2, rtt_reps=20)


class _ErrorLog(logging.Handler):
    """ERROR records, one line each, into a list."""

    def __init__(self, sink: list):
        super().__init__(level=logging.ERROR)
        self.sink = sink

    def emit(self, record: logging.LogRecord) -> None:
        msg = record.getMessage()
        exc = record.exc_info[1] if record.exc_info else None
        if exc is not None:
            msg += f" [{type(exc).__name__}: {exc}]"
        self.sink.append(f"log {record.name}: {msg}")


class Watch:
    """What can go wrong off the main thread, and what compiled.

    Collects uncaught thread exceptions, ERROR log records (a failed
    eval, a failed device-twin resync and a failed plan are all logged
    with their exception and then repaired, so the log is where they
    show), and every XLA compile with its kernel name, seconds and
    whether the persistent cache served it."""

    def __init__(self):
        import jax

        self.errors: list = []
        self.compiles: list = []      # (name, seconds, cache_hit)
        self._tls = threading.local()
        self._prev_hook = threading.excepthook
        threading.excepthook = self._on_thread_exc
        self._handler = _ErrorLog(self.errors)
        logging.getLogger().addHandler(self._handler)
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_thread_exc(self, args) -> None:
        name = args.thread.name if args.thread else "?"
        self.errors.append(
            f"thread {name}: {args.exc_type.__name__}: {args.exc_value}")
        self._prev_hook(args)

    def _on_event(self, event: str, **kwargs) -> None:
        # fires inside the compile span below, on the compiling thread
        if event == CACHE_HIT_EVENT:
            self._tls.hit = True

    def _on_duration(self, event: str, duration: float, **kwargs) -> None:
        from nomad_tpu.analysis.launch_ledger import COMPILE_EVENT

        if event != COMPILE_EVENT:
            return
        hit, self._tls.hit = getattr(self._tls, "hit", False), False
        name = str(kwargs.get("fun_name", "?"))
        if name.startswith("jit(") and name.endswith(")"):
            name = name[4:-1]
        self.compiles.append((name, duration, hit))

    def close(self) -> None:
        threading.excepthook = self._prev_hook
        logging.getLogger().removeHandler(self._handler)

    def mark(self) -> int:
        return len(self.compiles)

    def between(self, lo: int, hi: int = None) -> dict:
        """Compiles in [lo, hi), by kernel: count, seconds, cache hits."""
        out: dict = {}
        for name, secs, hit in self.compiles[lo:hi]:
            row = out.setdefault(name, {"n": 0, "s": 0.0, "hits": 0})
            row["n"] += 1
            row["s"] = round(row["s"] + secs, 3)
            row["hits"] += int(hit)
        return out


class Run:
    """Shared state of one smoke run: sizes, seed, the watch, and the
    line-per-observation output."""

    def __init__(self, sizes: Sizes, seed: int):
        self.sizes = sizes
        self.seed = seed
        self.watch = Watch()
        self.report: dict = {}

    def say(self, leg: str, **obs) -> None:
        self.report.setdefault(leg, {}).update(obs)
        print(f"[{leg}] " + " ".join(f"{k}={json.dumps(v)}"
                                     for k, v in obs.items()), flush=True)

    def counted(self, leg: str, cold_mark: int, warm_mark: int,
                ledger_mark: int, warmed: tuple = ()) -> None:
        """Close a leg's compile accounting. [cold_mark, warm_mark) was
        warm-up; after `warm_mark` none of the kernels the leg `warmed`
        (by the name XLA compiled them under) may compile again, and no
        launch window the code itself opened as warm may compile at all
        (the launch ledger records those as violations). What may still
        compile is a shape nobody could warm: a scatter bucket
        (incremental.SCATTER_FLOOR upward, once per delta-batch size) or
        the per-placement scan a partial-commit remainder falls to."""
        from nomad_tpu.analysis import launch_ledger

        cold = self.watch.between(cold_mark, warm_mark)
        window = self.watch.between(warm_mark)
        warm_compiles = [v.render() for v in
                         launch_ledger.GLOBAL.violations[ledger_mark:]]
        self.say(leg,
                 cold_compiles=sum(r["n"] for r in cold.values()),
                 cold_compile_s=round(sum(r["s"] for r in cold.values()), 3),
                 cold_by_kernel=cold,
                 window_new_shape_compiles=window,
                 warm_window_compiles=len(warm_compiles))
        assert not warm_compiles, warm_compiles
        late = [n for n in window if n in warmed]
        assert not late, f"{leg}: compiled after warm-up: {late}"

    def check_threads(self, leg: str) -> None:
        assert not self.watch.errors, \
            f"{leg}: errors off the main thread:\n  " + \
            "\n  ".join(self.watch.errors[:20])


# -- the deployment: agent, fleet, jobs ------------------------------------


def start_agent(algorithm: str, workers: int):
    """The agent exactly as `python -m nomad_tpu agent` builds it: the
    CLI's own parser and cli.Agent, single server, no local clients (the
    fleet below is the client plane), an ephemeral HTTP port."""
    from nomad_tpu import cli

    args = cli.build_parser().parse_args(
        ["agent", "--algorithm", algorithm, "--workers", str(workers),
         "--clients", "0", "--port", "0"])
    agent = cli.Agent(args)
    print(agent.start_line, flush=True)
    return agent


def join_fleet(server, count: int, prefix: str, shape):
    """`count` nodes joined through register_nodes and kept alive through
    heartbeat_batch at the agent's own TTL — a fleet nobody heartbeats
    is marked down mid-run."""
    from nomad_tpu.chaos.swarm import Swarm

    swarm = Swarm(lambda: server, count, ttl=server.config.heartbeat_ttl,
                  prefix=prefix)
    for i, sn in enumerate(swarm.nodes):
        shape(sn.node, i)
    swarm.start()       # drivers first: TTL timers arm at registration
    done = swarm.register_all()
    assert done == count, f"registered {done} of {count} nodes"
    return swarm


def submit(api, jobs) -> int:
    """Register jobs over HTTP. A 429 the client's own retry budget gave
    up on is a shed, not a failure: wait and offer the job again."""
    from nomad_tpu.api.client import ApiError

    sheds = 0
    for job in jobs:
        while True:
            try:
                api.register_job(job)
                break
            except ApiError as e:
                if e.status != 429:
                    raise
                sheds += 1
                time.sleep(0.5)
    return sheds


def configure(api, **fields) -> None:
    """The operator's scheduler-configuration API: read, change, PUT."""
    cfg = api.scheduler_configuration()
    for key, value in fields.items():
        if isinstance(value, dict):
            cfg[key].update(value)
        else:
            cfg[key] = value
    api.set_scheduler_configuration(cfg)
    got = api.scheduler_configuration()
    assert all(got[k] == cfg[k] for k in fields), (got, fields)


def submit_backlog(api, jobs) -> int:
    """Load jobs with the eval broker paused, then resume it: the drain
    starts from the whole backlog, so the solver service sees its full
    launch width whatever the relative speed of HTTP and the device."""
    configure(api, pause_eval_broker=True)
    try:
        return submit(api, jobs)
    finally:
        configure(api, pause_eval_broker=False)


def drain(server, jobs, timeout: float = 900.0) -> float:
    """Wait until the broker and the plan queue are empty AND the store
    holds no open evaluation of `jobs` -> seconds waited. The store is
    the criterion that has no gap: a blocked eval on its way back to the
    broker is in neither for a moment, but it is `blocked` in the store
    until it is `pending` there."""
    from nomad_tpu.structs import enums

    done = (enums.EVAL_STATUS_COMPLETE, enums.EVAL_STATUS_FAILED,
            enums.EVAL_STATUS_CANCELLED)
    want = {j.id for j in jobs}
    t0 = time.perf_counter()
    deadline = time.time() + timeout
    while True:
        idle = server.wait_for_idle(timeout=max(1.0, deadline - time.time()),
                                    include_delayed=False)
        still = [ev for ev in server.store.snapshot().evals()
                 if ev.job_id in want and ev.status not in done]
        if idle and not still:
            return time.perf_counter() - t0
        if time.time() > deadline:
            raise TimeoutError(
                f"not drained: idle={idle}, open evals "
                f"{[(ev.job_id, ev.status) for ev in still[:5]]}")
        time.sleep(0.05)


def census(server):
    """The plain reference: a recount of a fresh snapshot through the
    store's public readers. -> (live allocs per job, nodes over capacity,
    nodes with a port handed out twice, nodes not ready)."""
    snap = server.store.snapshot()
    per_job: collections.Counter = collections.Counter()
    used: dict = {}
    ports: dict = {}
    for a in snap.allocs():
        if a.terminal_status():
            continue
        per_job[a.job_id] += 1
        row = used.get(a.node_id)
        if row is None:
            row = used[a.node_id] = np.zeros_like(a.allocated_vec)
        row += a.allocated_vec
        for p in a.allocated_ports or ():
            ports.setdefault(a.node_id, []).append(p.value)
    over, not_ready = [], []
    for n in snap.nodes():
        if not n.ready():
            not_ready.append(n.id)
        row = used.get(n.id)
        if row is not None and (row > n.available_vec()).any():
            over.append(n.id)
    clash = [nid for nid, vals in ports.items() if len(set(vals)) != len(vals)]
    return per_job, over, clash, not_ready


def check_cluster(run: Run, leg: str, server, jobs, want_each: int) -> None:
    per_job, over, clash, not_ready = census(server)
    short = {j.id: per_job[j.id] for j in jobs if per_job[j.id] != want_each}
    run.say(leg, placed=sum(per_job[j.id] for j in jobs),
            nodes_over_capacity=len(over), port_collisions=len(clash),
            nodes_down=len(not_ready))
    assert not short, f"{leg}: jobs not fully placed: {dict(list(short.items())[:5])}"
    assert not over, f"{leg}: nodes over capacity: {over[:5]}"
    assert not clash, f"{leg}: port handed out twice on: {clash[:5]}"
    assert not not_ready, f"{leg}: nodes down: {not_ready[:5]}"


def evals_settled(api, jobs) -> int:
    """Over HTTP: every evaluation of these jobs is terminal and each
    job's newest one ended `complete`. An eval that ran out of plan
    attempts ends `failed` and hands over to a blocked eval, which is
    the scheduler working as designed -> how many did."""
    by_job: dict = {}
    for ev in api.list_evaluations():
        by_job.setdefault(ev["job_id"], []).append(ev)
    failed = 0
    for job in jobs:
        evs = sorted(by_job.get(job.id, ()), key=lambda e: e["create_index"])
        states = [e["status"] for e in evs]
        assert evs and set(states) <= {"complete", "failed"} \
            and states[-1] == "complete", f"{job.id}: evaluations {states}"
        failed += states.count("failed")
    return failed


def solver_delta(before: dict) -> dict:
    from nomad_tpu.tensor.solver import get_service

    after = dict(get_service().stats)
    return {k: after[k] - before.get(k, 0) for k in after}


def solver_stats() -> dict:
    from nomad_tpu.tensor.solver import get_service

    return dict(get_service().stats)


def ledger_mark() -> int:
    from nomad_tpu.analysis import launch_ledger

    return len(launch_ledger.GLOBAL.violations)


def ledger_tail():
    """The newest launch window the ledger holds (None when empty)."""
    from nomad_tpu.analysis import launch_ledger

    records = launch_ledger.GLOBAL.records
    return records[-1] if records else None


def launches_since(tail, name: str) -> int:
    """Launch windows opened for kernel `name` after `tail`. The ledger
    keeps a bounded ring: if `tail` has left it, every record is newer."""
    from nomad_tpu.analysis import launch_ledger

    n = 0
    for rec in reversed(launch_ledger.GLOBAL.records):
        if rec is tail:
            break
        n += rec.name == name
    return n


def batch_jobs(prefix: str, n: int, count: int, cpu: int, mem: int,
               priority: int = 50, batch: bool = True):
    from nomad_tpu import mock

    jobs = []
    for i in range(n):
        j = mock.service_job(count, cpu=cpu, mem=mem, batch=batch,
                             priority=priority)
        j.id = j.name = f"{prefix}-{i:04d}"
        jobs.append(j)
    return jobs


# -- legs --------------------------------------------------------------------


def leg_c2m(run: Run, agent, api) -> None:
    """A: the flagship drain."""
    from nomad_tpu.tensor import incremental
    from nomad_tpu.tensor.solver import BulkSolverService, get_service

    z, leg, server = run.sizes, "A c2m", agent.server
    t_leg = time.perf_counter()
    cold_mark, led = run.watch.mark(), ledger_mark()
    g_pad = BulkSolverService.G_PAD

    # warm both launch widths before counting: one job alone launches
    # g=1, a burst launches g=G_PAD (the first of either compiles)
    def warm_shapes():
        return {k[1] for k in get_service()._warm_shapes
                if str(k[0]).startswith("greedy")}

    warm = batch_jobs(f"warm-a{run.seed}", 1 + 2 * g_pad, 256, 1, 1)
    submit(api, warm[:1])
    drain(server, warm[:1])
    submit_backlog(api, warm[1:])
    drain(server, warm)
    assert {1, g_pad} <= warm_shapes(), \
        f"could not warm g=1 and g={g_pad}: {get_service()._warm_shapes}"

    warm_mark = run.watch.mark()
    svc0, feed0 = solver_stats(), incremental.GLOBAL.stats()
    jobs = batch_jobs(f"c2m-{run.seed}", z.jobs, z.per_job, 50, 32)
    t0 = time.perf_counter()
    sheds = submit_backlog(api, jobs)
    t_submitted = time.perf_counter() - t0
    wall = drain(server, jobs)

    svc = solver_delta(svc0)
    feed = {k: v - feed0[k] for k, v in incremental.GLOBAL.stats().items()}
    run.say(leg, nodes=z.nodes, jobs=z.jobs, allocs=z.jobs * z.per_job,
            submit_s=round(t_submitted, 3), drain_wall_s=round(wall, 3),
            sheds=sheds, launches=svc["launches"], solves=svc["solves"],
            pipelined=svc["pipelined"], resyncs=svc["resyncs"],
            retraces=svc["retraces"], twin_failures=svc["twin_failures"],
            rejections=svc["rejections"],
            sharded=svc["sharded"], mesh_devices=solver_stats()["mesh_devices"],
            feed_fast_hits=feed["fast_hits"], feed_resyncs=feed["resyncs"],
            feed_deltas=feed["deltas_applied"],
            plan_nodes_rejected=server.plan_applier.stats["nodes_rejected"])
    check_cluster(run, leg, server, jobs, z.per_job)
    run.say(leg, evals_out_of_attempts=evals_settled(api, jobs))
    rng = random.Random(run.seed)
    for job in rng.sample(jobs, z.http_sample):
        rows = api.job_allocations(job.id)
        assert len(rows) == z.per_job, (job.id, len(rows))
    assert svc["launches"] > 0 and svc["pipelined"] > 0, svc
    assert svc["retraces"] == 0 and svc["twin_failures"] == 0, svc
    assert feed["fast_hits"] > 0 and feed["deltas_applied"] > 0, feed
    if svc["resyncs"] > 1:
        # the first resync uploads the feed's twin, every later one
        # brings it up to date with one scatter launch
        compiled = run.watch.between(0)
        assert "state_scatter" in compiled \
            or "state_scatter_sharded" in compiled, sorted(compiled)
    # the single-device kernel, or the mesh twin's jitted `solve`
    run.counted(leg, cold_mark, warm_mark, led,
                warmed=("_solve_bulk_multi_impl", "solve"))
    run.check_threads(leg)
    run.say(leg, leg_wall_s=round(time.perf_counter() - t_leg, 3), ok=True)


def leg_service(run: Run, agent, api) -> None:
    """B: the service job users write, on the per-placement tier."""
    from nomad_tpu.core.metrics import REGISTRY
    from nomad_tpu.obs import TRACER
    from nomad_tpu.obs.trace import R_NAME
    from nomad_tpu.structs import Spread
    from nomad_tpu.structs.resources import NetworkResource

    z, leg, server = run.sizes, "B service", agent.server
    t_leg = time.perf_counter()
    cold_mark, led = run.watch.mark(), ledger_mark()

    def service_jobs(prefix, n):
        jobs = batch_jobs(prefix, n, z.service_count, 100, 64, batch=False)
        for j in jobs:
            tg = j.task_groups[0]
            tg.spreads = [Spread(attribute="${attr.rack}", weight=50)]
            tg.tasks[0].resources.networks = [
                NetworkResource(dynamic_ports=["http"])]
        return jobs

    warm = service_jobs(f"warm-b{run.seed}", 1)
    submit(api, warm)
    drain(server, warm)

    warm_mark = run.watch.mark()
    TRACER.clear()
    tail = ledger_tail()
    staged0 = REGISTRY.get("nomad.placer.staged_solves")
    jobs = service_jobs(f"svc-{run.seed}", z.service_jobs)
    submit(api, jobs)
    wall = drain(server, jobs)
    names = [rec[R_NAME] for rec in TRACER.spans()]
    solves, stages = names.count("worker.solve"), names.count("placer.stage")
    staged = int(REGISTRY.get("nomad.placer.staged_solves") - staged0)
    fused = launches_since(tail, "solve_task_group_fused")
    run.say(leg, jobs=z.service_jobs, allocs=z.service_jobs * z.service_count,
            drain_wall_s=round(wall, 3), worker_solve_spans=solves,
            fused_launches=fused, staged_solves=staged)
    check_cluster(run, leg, server, warm + jobs, z.service_count)
    evals_settled(api, jobs)
    # HOST_CUTOVER must not have eaten the launch: every job's group went
    # through a worker.solve span that opened a fused launch window, its
    # static arguments staged on the device before the lock (usage apart)
    assert solves >= z.service_jobs and fused >= z.service_jobs, \
        (solves, fused)
    assert staged == stages == solves, (staged, stages, solves)
    run.counted(leg, cold_mark, warm_mark, led,
                warmed=("solve_task_group_fused",))
    run.check_threads(leg)
    run.say(leg, leg_wall_s=round(time.perf_counter() - t_leg, 3), ok=True)


def leg_joint(run: Run, agent, api) -> None:
    """C: the operator switches the running agent to tpu-solve."""
    from nomad_tpu.structs import enums

    z, leg, server = run.sizes, "C joint", agent.server
    t_leg = time.perf_counter()
    cold_mark, led = run.watch.mark(), ledger_mark()
    configure(api, scheduler_algorithm=enums.SCHED_ALG_TPU_SOLVE)

    # a joint launch always takes the full G_PAD width: one job warms it
    warm = batch_jobs(f"warm-c{run.seed}", 1, 256, 1, 1)
    submit(api, warm)
    drain(server, warm)

    warm_mark = run.watch.mark()
    svc0 = solver_stats()
    jobs = batch_jobs(f"joint-{run.seed}", z.joint_jobs, z.joint_count, 60, 48)
    # as a backlog, so that a worker's dequeued batch solves jointly
    submit_backlog(api, jobs)
    wall = drain(server, jobs)
    svc = solver_delta(svc0)
    run.say(leg, jobs=z.joint_jobs, allocs=z.joint_jobs * z.joint_count,
            drain_wall_s=round(wall, 3), joint_launches=svc["joint_launches"],
            joint_solves=svc["joint_solves"], auction_won=svc["auction_won"],
            joint_score=round(svc["joint_score"], 3),
            greedy_score=round(svc["greedy_score"], 3),
            retraces=svc["retraces"], sharded=svc["sharded"])
    check_cluster(run, leg, server, jobs, z.joint_count)
    evals_settled(api, jobs)
    assert svc["joint_launches"] > 0 and svc["retraces"] == 0, svc
    assert svc["joint_score"] >= svc["greedy_score"], svc
    run.counted(leg, cold_mark, warm_mark, led,
                warmed=("solve_batch", "solve"))
    run.check_threads(leg)
    run.say(leg, leg_wall_s=round(time.perf_counter() - t_leg, 3), ok=True)


def leg_preempt(run: Run) -> None:
    """D: BASELINE.md config 4 (system + preemption, mixed priority)
    through a served agent of its own."""
    from nomad_tpu.api.client import ApiClient
    from nomad_tpu.structs import enums
    from nomad_tpu.tensor.cluster import _pad_pow2
    from nomad_tpu.tensor.placer import TPUPlacer, preempt_stats

    z, leg = run.sizes, "D preempt"
    t_leg = time.perf_counter()
    cold_mark, led = run.watch.mark(), ledger_mark()
    agent = start_agent(enums.SCHED_ALG_TPU_BINPACK, 4)
    swarm = None
    try:
        server, api = agent.server, ApiClient(address=agent.http.address)
        configure(api, preemption_config={"service_scheduler_enabled": True})

        def uniform(node, i):
            node.attributes["rack"] = f"r{i % 20}"
            node.resources.cpu = 16_000
            node.resources.memory_mb = 32_768
            node.compute_class()

        swarm = join_fleet(server, z.preempt_nodes, f"pre{run.seed}", uniform)
        # fill exactly: 2 x (7900 MHz, 14000 MB) per node leaves 200 MHz
        filler = batch_jobs(f"fill-{run.seed}", 1, 2 * z.preempt_nodes,
                            7_900, 14_000, priority=20, batch=False)
        submit(api, filler)
        drain(server, filler)
        check_cluster(run, leg, server, filler, 2 * z.preempt_nodes)

        p0 = preempt_stats()
        tail = ledger_tail()
        hi = batch_jobs(f"hi-{run.seed}", 1, z.preempt_hi, 2_500, 2_048,
                        priority=80, batch=False)
        submit(api, hi)
        # only `hi`: the evicted fillers' replacements have nowhere to
        # go and stay blocked
        wall = drain(server, hi)
        p = {k: v - p0[k] for k, v in preempt_stats().items()}
        on_device = launches_since(tail, "preempt_solve")
        evicted = sum(
            1 for a in server.store.snapshot().allocs_by_job(filler[0].id)
            if a.desired_status == enums.ALLOC_DESIRED_EVICT)
        run.say(leg, nodes=z.preempt_nodes, hi_allocs=z.preempt_hi,
                drain_wall_s=round(wall, 3), evicted=evicted,
                preempt_solve_launches=on_device, **p)
        check_cluster(run, leg, server, hi, z.preempt_hi)
        assert p["kernel_preempted"] > 0, p
        if (_pad_pow2(z.preempt_nodes) * _pad_pow2(z.preempt_hi, floor=1)
                >= TPUPlacer.PREEMPT_DEVICE_MIN):
            assert on_device > 0, "preempt_solve never launched on the device"
        assert swarm.stats["hb_failures"] == 0, swarm.stats
    finally:
        if swarm is not None:
            swarm.stop()
        agent.stop()
    run.counted(leg, cold_mark, run.watch.mark(), led)
    run.check_threads(leg)
    run.say(leg, leg_wall_s=round(time.perf_counter() - t_leg, 3), ok=True)


def leg_sweep(run: Run) -> None:
    """E: the placer-reachable jitted entries nothing above launched,
    each once at C2M width from seeded inputs, against plain numpy."""
    import jax

    from nomad_tpu.tensor import incremental
    from nomad_tpu.tensor.cluster import _pad_pow2
    from nomad_tpu.tensor.jit_guard import cache_size

    z, leg = run.sizes, "E sweep"
    t_leg = time.perf_counter()
    cold_mark = run.watch.mark()
    rng = np.random.default_rng(run.seed)
    n, n_pad, d, f32 = z.nodes, _pad_pow2(z.nodes), 4, np.float32
    avail = np.zeros((n_pad, d), f32)
    avail[:n, 0] = rng.choice([8000, 16000, 32000], n)
    avail[:n, 1] = rng.choice([16384, 32768, 65536], n)
    avail[:n, 2], avail[:n, 3] = 100 * 1024, 12001
    used = np.zeros((n_pad, d), f32)
    used[:n, 0] = (avail[:n, 0] * rng.uniform(0.2, 0.8, n)) // 50 * 50
    used[:n, 1] = (avail[:n, 1] * rng.uniform(0.1, 0.5, n)) // 32 * 32
    ask = np.array([50.0, 32.0, 0.0, 0.0], f32)

    def timed(fn):
        t0 = time.perf_counter()
        out = jax.device_get(fn())
        return out, time.perf_counter() - t0

    rows = rng.choice(n, 8, replace=False).astype(np.int32)
    delta = np.tile(ask, (8, 1)) * rng.integers(1, 9, (8, 1)).astype(f32)
    want_scatter = used.copy()
    np.add.at(want_scatter, rows, delta)

    def scatter(donate):
        def go():
            return incremental._scatter_fn(donate=donate)(
                *jax.device_put((used, rows, delta)))
        return go

    def check_scatter(name, out):
        # integral f32 adds are exact: the incremental-state tests demand
        # bit-equality, so does this
        assert np.array_equal(np.asarray(out), want_scatter), name
        return {"equal": True}

    entries = [
        ("state_fold", incremental._scatter_fn(donate=False),
         scatter(False), check_scatter),
        ("state_scatter", incremental._scatter_fn(donate=True),
         scatter(True), check_scatter),
    ]
    swept = {}
    for name, jitted, launch, check in entries:
        if cache_size(jitted):
            continue        # a leg above already launched it on this chip
        out, cold_s = timed(launch)
        check(name, out)
        out, warm_s = timed(launch)
        swept[name] = {"cold_s": round(cold_s, 3), "warm_s": round(warm_s, 5),
                       **check(name, out)}
    run.say(leg, n_pad=n_pad, swept=swept,
            skipped_already_launched=[e[0] for e in entries
                                      if e[0] not in swept])
    cold = run.watch.between(cold_mark)
    run.say(leg, cold_compiles=sum(r["n"] for r in cold.values()),
            cold_compile_s=round(sum(r["s"] for r in cold.values()), 3),
            cold_by_kernel=cold)
    run.check_threads(leg)
    run.say(leg, leg_wall_s=round(time.perf_counter() - t_leg, 3), ok=True)


def probe_round_trip(run: Run) -> None:
    """Warm round trip of the smallest launch there is — device_put of 8
    floats, one jitted add, device_get — which ROADMAP S3/D3 need before
    any path-selection constant can be re-derived."""
    import jax

    tiny = jax.jit(lambda x: x + 1.0)
    x = np.zeros(8, np.float32)
    jax.device_get(tiny(jax.device_put(x)))
    ts = []
    for _ in range(run.sizes.rtt_reps):
        t0 = time.perf_counter()
        jax.device_get(tiny(jax.device_put(x)))
        ts.append(time.perf_counter() - t0)
    q = statistics.quantiles(ts, n=10)
    run.say("probe", launch_round_trip_ms_median=statistics.median(ts) * 1e3,
            launch_round_trip_ms_p10=q[0] * 1e3,
            launch_round_trip_ms_p90=q[-1] * 1e3, reps=len(ts))


def check_mesh(run: Run, agent) -> None:
    """Several chips: the service must have sharded by itself, the
    resident arrays must really live on every chip (not all on device
    0), and the sharded engine must agree with the single-device one."""
    import jax

    import __graft_entry__ as graft
    from nomad_tpu.tensor.solver import get_service

    n_dev = len(jax.devices())
    svc = get_service()
    stats = dict(svc.stats)
    mesh_n = 1 << (n_dev.bit_length() - 1)
    assert stats["mesh_devices"] == mesh_n and stats["sharded"] > 0, stats
    shards = {}
    for static in agent.server.store._tensor_statics.values():
        for key, arr in static.device_arrays.items():
            kind = key[0] if isinstance(key, tuple) else key
            if kind in ("availsh", "msh", "ash"):   # solver.ensure_resident
                shards[kind] = len({s.device for s in arr.addressable_shards})
    assert shards and set(shards.values()) == {mesh_n}, shards
    # one graph per launch width and no more: the replicated device_puts
    # did not fork the cache against the warm-up's layout
    graphs = {"greedy": svc._mesh_solve._cache_size(),
              "joint": svc._mesh_solve_joint._cache_size()}
    assert graphs["greedy"] <= 2 and graphs["joint"] <= 1, graphs
    graft._dryrun_body(mesh_n)
    run.say("mesh", devices=n_dev, mesh_devices=mesh_n,
            sharded_launches=stats["sharded"], allgathers=stats["allgathers"],
            resident_shards=shards, graphs=graphs, parity="counts equal")


# -- the run -----------------------------------------------------------------


def run_smoke(sizes: Sizes, seed: int) -> dict:
    """All legs at `sizes` on whatever backend the bootstrap admits ->
    the report. Raises on the first failed check."""
    import jax
    import jaxlib

    from nomad_tpu import mock
    from nomad_tpu.analysis import launch_ledger
    from nomad_tpu.api.client import ApiClient
    from nomad_tpu.structs import enums
    from nomad_tpu.tensor.backend import bootstrap, cache_dir

    dev = bootstrap(enums.SCHED_ALG_TPU_BINPACK)
    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = None
    # the repo's own launch ledger: attributes every compile to the
    # launch window it fell in and records a warm window that compiled
    launch_ledger.install()
    run = Run(sizes, seed)
    run.say("env", platform=dev.platform, device_kind=dev.kind,
            device_count=dev.count, jax=jax.__version__,
            jaxlib=jaxlib.__version__, libtpu=libtpu_version,
            numpy=np.__version__, x64=bool(jax.config.jax_enable_x64),
            seed=seed, compile_cache=cache_dir(),
            sizes=dataclasses.asdict(sizes))
    t_all = time.perf_counter()
    try:
        agent = start_agent(enums.SCHED_ALG_TPU_BINPACK, sizes.workers)
        swarm = None
        try:
            api = ApiClient(address=agent.http.address)
            me = api.get("/v1/agent/self")[0]["stats"]
            assert me["device"] == dev.as_dict(), me["device"]
            t0 = time.perf_counter()
            rng = random.Random(seed)
            swarm = join_fleet(agent.server, sizes.nodes, f"c2m{seed}",
                               lambda node, i: mock.shape_node(node, i, rng))
            run.say("fleet", nodes=sizes.nodes,
                    join_s=round(time.perf_counter() - t0, 3))
            leg_c2m(run, agent, api)
            leg_service(run, agent, api)
            leg_joint(run, agent, api)
            run.say("fleet", heartbeats=swarm.stats["heartbeats"],
                    hb_failures=swarm.stats["hb_failures"])
            assert swarm.stats["hb_failures"] == 0, swarm.stats
            if dev.count > 1:
                check_mesh(run, agent)
        finally:
            if swarm is not None:
                swarm.stop()
            agent.stop()
        leg_preempt(run)
        leg_sweep(run)
        probe_round_trip(run)
        assert not launch_ledger.GLOBAL.violations, launch_ledger.GLOBAL.report()
        run.check_threads("run")
    finally:
        run.watch.close()
        launch_ledger.uninstall()
    hits = sum(1 for _, _, hit in run.watch.compiles if hit)
    run.say("run", wall_s=round(time.perf_counter() - t_all, 3),
            compiles=len(run.watch.compiles),
            compile_s=round(sum(s for _, s, _ in run.watch.compiles), 3),
            persistent_cache_hits=hits,
            not_served_from_cache_over_threshold=sorted(
                {n for n, s, hit in run.watch.compiles
                 if not hit and s >= 0.5}),
            claim=None)
    return run.report


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, default=0,
                        help="seeds the fleet's capacities, the sampled "
                             "jobs and the sweep's inputs")
    args = parser.parse_args(argv)

    # alone in a directory there is no program to drive: say so before
    # anything is printed on stdout
    missing = [m for m in ("nomad_tpu", "__graft_entry__")
               if importlib.util.find_spec(m) is None]
    if missing:
        print(f"chip_smoke: {', '.join(missing)} not found next to the "
              f"script; nothing was run", file=sys.stderr)
        return 2

    import jax

    first = jax.devices()[0]
    if first.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX resolved {first.platform} "
              f"({first.device_kind}); nothing was run", file=sys.stderr)
        return 2
    logging.basicConfig(level=logging.WARNING)
    try:
        run_smoke(FULL, args.seed)
        ok = True
    except Exception:
        traceback.print_exc()
        ok = False
    # the contract's result line: exactly these keys, last on stdout
    sys.stderr.flush()
    print(json.dumps({"ok": ok, "device": {
        "platform": first.platform, "kind": first.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
