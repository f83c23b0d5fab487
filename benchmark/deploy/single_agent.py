"""Deploy kind `single_agent`: one agent as the CLI builds it, a seeded
fleet joined over the registration RPCs, and the warm-up of the cell's
own shapes.

Copies of `chip_smoke.start_agent`, `join_fleet`, `submit`, `configure`,
`drain` and of `bench.shape_node` (PERF.md lists the originals for a
later PR to delete). Everything here is set-up: it runs before the
window opens and is what `setup_s` counts.
"""

from __future__ import annotations

import concurrent.futures
import random
import time

import numpy as np


def shape_node(node, i: int, rng: random.Random, mix: dict) -> None:
    """The configuration's node mix applied to node number `i`: the
    rack under `rack_key` ("attr.rack" or "meta.rack"), every list of
    `cycled_attributes` and of `datacenters` by node number, capacity
    drawn from the seed."""
    kind, _, key = mix.get("rack_key", "attr.rack").partition(".")
    table = node.meta if kind == "meta" else node.attributes
    table[key] = f"r{i % int(mix['racks'])}"
    for name, values in mix.get("cycled_attributes", {}).items():
        node.attributes[name] = values[i % len(values)]
    if mix.get("datacenters"):
        node.datacenter = mix["datacenters"][i % len(mix["datacenters"])]
    node.resources.cpu = rng.choice(mix["cpu_mhz"])
    node.resources.memory_mb = rng.choice(mix["memory_mb"])
    node.compute_class()


class Deployment:
    def __init__(self, config: dict, seed: int, toy: bool):
        self.config, self.seed, self.toy = config, seed, toy
        self.agent = self.api = self.swarm = None
        self.nodes = int(config["toy"]["nodes"] if toy else config["nodes"])
        self.timings: dict = {}

    # -- lifecycle -----------------------------------------------------

    def start(self) -> None:
        from nomad_tpu import cli
        from nomad_tpu.api.client import ApiClient
        from nomad_tpu.chaos.swarm import Swarm

        flags = dict(self.config["agent"])
        if self.toy:
            flags.update(self.config["toy"].get("agent", {}))
        argv = ["agent", "--port", "0"]
        for k, v in flags.items():
            argv += [f"--{k}", str(v)]
        t0 = time.perf_counter()
        self.agent = cli.Agent(cli.build_parser().parse_args(argv))
        print(self.agent.start_line, flush=True)
        self.device = self.agent.device
        self.server = self.agent.server
        self.address = self.agent.http.address
        self.api = ApiClient(address=self.address)
        self.timings["agent_s"] = time.perf_counter() - t0

        t0 = time.perf_counter()
        rng = random.Random(self.seed)
        mix = self.config["node_mix"]
        swarm = Swarm(lambda: self.server, self.nodes,
                      ttl=self.server.config.heartbeat_ttl,
                      prefix=f"n{self.seed}")
        for i, sn in enumerate(swarm.nodes):
            shape_node(sn.node, i, rng, mix)
        swarm.start()       # drivers first: TTL timers arm at registration
        self.swarm = swarm
        done = swarm.register_all()
        if done != self.nodes:
            raise RuntimeError(f"registered {done} of {self.nodes} nodes")
        self.timings["fleet_s"] = time.perf_counter() - t0

    def stop(self) -> None:
        if self.swarm is not None:
            self.swarm.stop()
        if self.agent is not None:
            self.agent.stop()

    # -- the operator's API -----------------------------------------------

    def configure(self, **fields) -> None:
        cfg = self.api.scheduler_configuration()
        cfg.update(fields)
        self.api.set_scheduler_configuration(cfg)

    def pause_broker(self, paused: bool) -> None:
        self.configure(pause_eval_broker=paused)

    def submit(self, jobs, threads: int = 1) -> int:
        """Register jobs over HTTP from `threads` threads, in order within
        a thread. A 429 the client gave up on is offered again."""
        from nomad_tpu.api.client import ApiClient, ApiError

        def one(chunk):
            api, sheds = ApiClient(address=self.address), 0
            for job in chunk:
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

        threads = max(1, min(threads, len(jobs)))
        chunks = [jobs[i::threads] for i in range(threads)]
        with concurrent.futures.ThreadPoolExecutor(threads) as pool:
            return sum(pool.map(one, chunks))

    def drain(self, job_ids, timeout: float = 600.0) -> float:
        """Wait until broker and plan queue are empty AND the store holds
        no open evaluation of `job_ids` (the criterion without a gap)."""
        want = set(job_ids)
        t0 = time.perf_counter()
        deadline = time.time() + timeout
        while True:
            idle = self.server.wait_for_idle(
                timeout=max(1.0, deadline - time.time()),
                include_delayed=False)
            snap = self.server.store.snapshot()
            still = [ev for j in want for ev in snap.evals_by_job(j)
                     if not ev.terminal_status()]
            if idle and not still:
                return time.perf_counter() - t0
            if time.time() > deadline:
                raise TimeoutError(
                    f"not drained: idle={idle}, open evals "
                    f"{[(ev.job_id, ev.status) for ev in still[:5]]}")
            time.sleep(0.02)

    def quiesce(self, timeout: float = 10.0) -> bool:
        """After the window: hold the broker and let what is in flight
        land, so the check reads a store nobody is writing to."""
        self.pause_broker(True)
        deadline = time.time() + timeout
        while time.time() < deadline:
            if (self.server.broker.inflight() == 0
                    and self.server.plan_queue.depth() == 0):
                return True
            time.sleep(0.02)
        return False

    # -- warm-up ------------------------------------------------------------

    def warm(self, warm_specs: dict, scatter_buckets: bool) -> dict:
        """Every shape the cell's traffic reaches, once, through the
        served path; then the delta-scatter buckets, which no job can be
        sized to hit, directly. -> the shapes the solver reports warm."""
        from benchmark.jobs import build_job

        t0 = time.perf_counter()
        scan = [build_job(s) for s in warm_specs["singles"]
                if not s["id"].endswith("-bulk")]
        bulk = [build_job(s) for s in warm_specs["singles"]
                if s["id"].endswith("-bulk")]
        burst = [build_job(s) for s in warm_specs["burst"]]
        # The per-placement shapes first, racing (each is a shape of its
        # own), and their jobs stopped again: the solver's usage carry
        # never hears of placements made outside it, so whatever they left
        # behind would be a stale carry on exactly the best-fit nodes the
        # window fills first, and every stale node is a rejected plan row.
        # Then the count solve alone at width 1, then the burst at full
        # width: those the carry knows. (A count solve racing the scan
        # jobs can lose both its plan attempts and sit on the 60 s
        # follow-up timer.)
        if scan:
            self.submit(scan, threads=4)
            self.drain([j.id for j in scan])
            for job in scan:
                self.api.deregister_job(job.id, purge=True)
            self.drain([j.id for j in scan])
        self.timings["warm_scan_s"] = time.perf_counter() - t0
        for job in bulk:
            self.submit([job])
            self.drain([job.id])
        if burst:
            self.pause_broker(True)
            try:
                self.submit(burst, threads=4)
            finally:
                self.pause_broker(False)
            self.drain([j.id for j in burst])
        self.timings["warm_bulk_s"] = (time.perf_counter() - t0
                                       - self.timings["warm_scan_s"])
        if scatter_buckets:
            self._warm_scatter()
        self.timings["warm_s"] = time.perf_counter() - t0
        return {"jobs": len(scan) + len(bulk) + len(burst)}

    def _warm_scatter(self) -> None:
        """The incremental feed pads a delta batch to a power of two from
        8 and scatters it in one launch; which buckets a run hits depends
        on how commits interleave, so every bucket up to the fleet's
        padded size is launched once here on zero deltas."""
        import jax

        from nomad_tpu.structs.resources import RESOURCE_DIMS
        from nomad_tpu.tensor.cluster import _pad_pow2
        from nomad_tpu.tensor.incremental import SCATTER_FLOOR, _scatter_fn

        n_pad, d = _pad_pow2(self.nodes), RESOURCE_DIMS
        bucket = SCATTER_FLOOR
        while bucket <= n_pad:
            idx = np.zeros(bucket, np.int32)
            delta = np.zeros((bucket, d), np.float32)
            for donate in (True, False):
                used = jax.device_put(np.zeros((n_pad, d), np.float32))
                out = _scatter_fn(donate=donate)(
                    used, *jax.device_put((idx, delta)))
                out.block_until_ready()
            bucket *= 2


def deploy(config: dict, seed: int, toy: bool) -> Deployment:
    return Deployment(config, seed, toy)
