"""Deploy kind `three_servers`: Nomad's documented production topology on
one machine. Three voting servers as the CLI builds them; every write is
acknowledged after a quorum (2 of 3) has it on disk, fsync on, and the
leader has applied it.

- The leader is `cli.Agent` in this process (it owns the chip), started
  first with `--peers` naming itself alone, so it is elected before the
  others exist.
- Two followers are child processes, `python -m nomad_tpu agent
  --workers 0 --clients 0 --join <leader>`: own interpreters, as own
  machines would have, no scheduler (upstream's `num_schedulers = 0`),
  no JAX, no device. Each start line has to say `device=none`, and the
  process's memory map is read to make sure no jaxlib or libtpu is in it.
- The fleet registers and heartbeats through the replicated endpoint
  (`Agent.replicated`), so every registration is a log entry the
  followers hold too.
- Each server has its own data directory (raft log, stable store,
  snapshots) under `benchmark/.work/<configuration>/<seed>.<pid>/`,
  removed when the deployment stops, which must lie on a real file system: on `tmpfs` / `ramfs` fsync is a no-op and the
  deployment refuses to start.

Every wait is bounded (`START_BUDGET_S` for the followers' start lines,
joins and first contact together): a program that cannot run this
deployment ends the run with an error, it does not hang it.

`quiesce()` adds, outside the window, the checks that hold the system to
the configuration's guarantees; a violation is an ERROR log record
starting `replica check:`, which the harness's watcher turns into
`correct: false`:

- replicas: both followers apply up to the leader's commit index at the
  pause (30 s at most), then every allocation of the run's jobs is read
  from each follower over HTTP (`?stale=true`) and its digest compared
  with the leader's store;
- durability: both followers are killed with SIGKILL (the leader's core
  collector, its one writer on a timer, is stopped first: from here on
  the leader has no quorum), then each of the three data directories is
  rebuilt with the plain reference (`reference/replay_log.py`) up to
  that commit index: the same digest;
- leadership: `nomad.raft.leader_changes` did not move since the window
  opened and the in-process server still leads.
"""

from __future__ import annotations

import json
import logging
import os
import random
import shutil
import signal
import socket
import subprocess
import sys
import time
import urllib.request
from pathlib import Path

from benchmark.deploy import single_agent
from benchmark.reference import replay_log

ROOT = Path(__file__).resolve().parents[2]
WORK = Path(__file__).resolve().parents[1] / ".work"
START_BUDGET_S = 60.0      # follower start lines + joins + first contact
CATCH_UP_S = 30.0          # followers reach the leader's commit index
VOLATILE_FS = ("tmpfs", "ramfs")
FOLLOWERS = ("server-1", "server-2")
LEADER = "server-0"

log = logging.getLogger("benchmark.replica")


def note(topic: str, **obs) -> None:
    print(f"[{topic}] " + " ".join(
        f"{k}={json.dumps(v, default=str)}" for k, v in obs.items()),
        flush=True)


def fs_type(path: Path) -> str:
    """File system type of the mount that holds `path` (/proc/mounts,
    longest mount point that is a prefix)."""
    path = str(path.resolve())
    best, kind = "", "unknown"
    with open("/proc/mounts") as f:
        for line in f:
            _dev, mount, fstype = line.split()[:3]
            mount = mount.replace("\\040", " ")
            if (path == mount or path.startswith(mount.rstrip("/") + "/")) \
                    and len(mount) > len(best):
                best, kind = mount, fstype
    return kind


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http_json(address: str, path: str, timeout: float = 30.0):
    with urllib.request.urlopen(address + path, timeout=timeout) as resp:
        return json.loads(resp.read())


class Follower:
    """One chipless server process and its files."""

    def __init__(self, server_id: str, data_dir: Path, flags: dict,
                 join: str):
        self.id, self.data_dir = server_id, data_dir
        self.rpc = f"127.0.0.1:{free_port()}"
        self.log_path = data_dir.parent / f"{server_id}.log"
        self.argv = [sys.executable, "-m", "nomad_tpu", "agent",
                     "--port", "0", "--server-id", server_id,
                     "--peers", f"{server_id}={self.rpc}",
                     "--join", join, "--data-dir", str(data_dir)]
        for k, v in flags.items():
            self.argv += [f"--{k}", str(v)]
        self.spawn()

    def spawn(self) -> None:
        """Start the process (again, after a kill: same id, same raft
        address, same data directory; `--join` of a member is a no-op)."""
        self.address = self.start_line = None
        env = dict(os.environ, PYTHONPATH=str(ROOT), PYTHONUNBUFFERED="1")
        self._out = open(self.log_path, "w")
        self.proc = subprocess.Popen(self.argv, cwd=str(ROOT), env=env,
                                     stdout=self._out,
                                     stderr=subprocess.STDOUT)

    def tail(self, n: int = 2000) -> str:
        try:
            return self.log_path.read_text()[-n:]
        except OSError:
            return ""

    def wait_started(self, deadline: float) -> None:
        """The start line, which the CLI prints once the server has
        joined; the process ending or the deadline passing is an error
        with the process's last output."""
        while True:
            for line in self.tail(20000).splitlines():
                if line.startswith("agent started: "):
                    self.start_line = line
                    self.address = line.split()[2]
                    return
            code = self.proc.poll()
            if code is not None:
                raise RuntimeError(f"follower {self.id} exited with code "
                                   f"{code} before its start line:\n"
                                   f"{self.tail()}")
            if time.time() > deadline:
                raise TimeoutError(f"follower {self.id} printed no start "
                                   f"line in time:\n{self.tail()}")
            time.sleep(0.05)

    def loaded_accelerator_libraries(self) -> list:
        """Shared objects of jaxlib or libtpu in the process's memory
        map: a server that never schedules must hold none."""
        try:
            with open(f"/proc/{self.proc.pid}/maps") as f:
                return sorted({ln.split()[-1] for ln in f
                               if "jaxlib" in ln or "libtpu" in ln})
        except OSError:
            return []

    def kill(self) -> None:
        """SIGKILL: no clean shutdown, what is on disk is what a crash
        leaves."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGKILL)
        try:
            self.proc.wait(10.0)
        except subprocess.TimeoutExpired:
            pass
        self._out.close()


class Deployment(single_agent.Deployment):
    def __init__(self, config: dict, seed: int, toy: bool):
        super().__init__(config, seed, toy)
        self.followers: list = []
        self.job_ids: list = []
        # one directory a run: cells of one configuration may run side
        # by side (the benchmark's tests do)
        self.workdir = WORK / config["name"] / f"{seed}.{os.getpid()}"
        self._changes_at_open = self._opened_at = None

    # -- lifecycle -----------------------------------------------------

    def start(self) -> None:
        from nomad_tpu import cli
        from nomad_tpu.api.client import ApiClient
        from nomad_tpu.chaos.swarm import Swarm

        shutil.rmtree(self.workdir, ignore_errors=True)
        dirs = {sid: self.workdir / sid for sid in (LEADER,) + FOLLOWERS}
        for d in dirs.values():
            d.mkdir(parents=True)
        kind = self.fs_type = fs_type(self.workdir)
        note("deploy", kind="three_servers", fs_type=kind,
             data_dirs=[str(d) for d in dirs.values()], fsync=True)
        if kind in VOLATILE_FS:
            raise RuntimeError(
                f"{self.workdir} is on {kind}: fsync is a no-op there, and "
                "the configuration states fsync before an entry counts")

        flags = dict(self.config["agent"])
        if self.toy:
            flags.update(self.config["toy"].get("agent", {}))
        rpc = f"127.0.0.1:{free_port()}"
        argv = ["agent", "--port", "0", "--server-id", LEADER,
                "--peers", f"{LEADER}={rpc}", "--data-dir", str(dirs[LEADER])]
        for k, v in flags.items():
            argv += [f"--{k}", str(v)]
        t0 = time.perf_counter()
        self.agent = cli.Agent(cli.build_parser().parse_args(argv))
        print(self.agent.start_line, flush=True)
        self.device = self.agent.device
        self.replicated = self.agent.replicated
        self.server = self.agent.server
        self.address = self.agent.http.address
        self.api = ApiClient(address=self.address)
        self.timings["agent_s"] = time.perf_counter() - t0

        t0 = time.perf_counter()
        deadline = time.time() + START_BUDGET_S
        self._wait(lambda: self.replicated.is_leader(), deadline,
                   "the in-process server was not elected")
        for sid in FOLLOWERS:
            f = Follower(sid, dirs[sid], {**flags, "workers": 0,
                                          "clients": 0}, join=rpc)
            self.followers.append(f)
            f.wait_started(deadline)
            print(f"{sid}: {f.start_line}", flush=True)
            if "device=none" not in f.start_line:
                raise RuntimeError(
                    f"follower {sid} resolved a backend: {f.start_line}")
        raft = self.replicated.raft
        self._wait(lambda: len(raft.servers) == 3 and all(
            raft._match_index.get(p, 0) >= raft.commit_index > 0
            for p in FOLLOWERS), deadline,
            "the followers did not catch up with the leader's log")
        libs = {f.id: f.loaded_accelerator_libraries()
                for f in self.followers}
        note("deploy", voters=sorted(raft.servers), leader=raft.leader_id,
             follower_accelerator_libraries=libs)
        if any(libs.values()):
            raise RuntimeError(f"a follower loaded an accelerator "
                               f"library: {libs}")
        self.timings["followers_s"] = time.perf_counter() - t0

        t0 = time.perf_counter()
        rng = random.Random(self.seed)
        mix = self.config["node_mix"]
        swarm = Swarm(lambda: self.replicated, self.nodes,
                      ttl=self.server.config.heartbeat_ttl,
                      prefix=f"n{self.seed}")
        for i, sn in enumerate(swarm.nodes):
            single_agent.shape_node(sn.node, i, rng, mix)
        swarm.start()       # drivers first: TTL timers arm at registration
        self.swarm = swarm
        done = swarm.register_all()
        if done != self.nodes:
            raise RuntimeError(f"registered {done} of {self.nodes} nodes")
        self.timings["fleet_s"] = time.perf_counter() - t0

    @staticmethod
    def _wait(cond, deadline: float, what: str) -> None:
        while not cond():
            if time.time() > deadline:
                raise TimeoutError(f"three_servers: {what} within "
                                   f"{START_BUDGET_S:.0f} s")
            time.sleep(0.02)

    def stop(self) -> None:
        if self.swarm is not None:
            self.swarm.stop()
        for f in self.followers:
            f.kill()
        if self.agent is not None:
            self.agent.stop()
        shutil.rmtree(self.workdir, ignore_errors=True)

    # -- the operator's API (what the checks need to know of it) ----------

    def submit(self, jobs, threads: int = 1) -> int:
        self.job_ids.extend(j.id for j in jobs)
        return super().submit(jobs, threads)

    def pause_broker(self, paused: bool) -> None:
        if not paused:
            # the generator resumes the broker to open the window
            self._changes_at_open = self._leader_changes()
            self._opened_at = time.time()
        super().pause_broker(paused)

    @staticmethod
    def _leader_changes() -> float:
        from nomad_tpu.core.metrics import REGISTRY

        return REGISTRY.get("nomad.raft.leader_changes")

    # -- the checks ---------------------------------------------------------

    def quiesce(self, timeout: float = 10.0) -> bool:
        quiet = super().quiesce(timeout)
        t0 = time.perf_counter()
        try:
            self._check_replicas()
        except Exception:
            # a follower that no longer answers, a log that no longer
            # parses: the run must not end with a number all the same
            log.exception("replica check: the check itself failed")
        self.timings["replica_check_s"] = time.perf_counter() - t0
        return quiet

    def _longest_spans(self, names: tuple) -> dict:
        from nomad_tpu.obs import TRACER
        from nomad_tpu.obs.trace import R_NAME, R_T0, R_T1

        longest = dict.fromkeys(names, 0.0)
        for r in TRACER.spans():
            if r[R_NAME] in longest and r[R_T0] >= (self._opened_at or 0.0):
                longest[r[R_NAME]] = max(longest[r[R_NAME]],
                                         round(r[R_T1] - r[R_T0], 3))
        return longest

    def _leader_rows(self, jobs: set) -> dict:
        snap = self.server.store.snapshot()
        return {a.id: (a.job_id, a.node_id, a.desired_status, a.client_status)
                for j in sorted(jobs) for a in snap.allocs_by_job(j)}

    def _check_replicas(self) -> None:
        jobs = set(self.job_ids)
        raft = self.replicated.raft
        # leadership
        moved = self._leader_changes() - (self._changes_at_open or 0.0)
        if self._changes_at_open is None or moved \
                or not self.replicated.is_leader():
            log.error("replica check: leadership moved: leader_changes "
                      "+%s since the window opened, leader now %r, "
                      "in-process server leads: %s", moved, raft.leader_id,
                      self.replicated.is_leader())
        for f in self.followers:
            if f.proc.poll() is not None:
                log.error("replica check: follower %s exited with code %s "
                          "during the run:\n%s", f.id, f.proc.poll(), f.tail())
                return
        # replicas: catch up, then stale reads
        commit = raft.commit_index
        rows = self._leader_rows(jobs)
        leader = replay_log.digest(rows)
        deadline = time.time() + CATCH_UP_S
        applied = {}
        for f in self.followers:
            while True:
                cfg = http_json(f.address, "/v1/operator/raft/configuration"
                                           "?stale=true")
                applied[f.id] = int(cfg["last_applied"])
                if applied[f.id] >= commit:
                    break
                if time.time() > deadline:
                    log.error("replica check: follower %s applied %d of the "
                              "leader's commit index %d after %.0f s",
                              f.id, applied[f.id], commit, CATCH_UP_S)
                    return
                time.sleep(0.05)
        read = {}
        for f in self.followers:
            rows = http_json(f.address, "/v1/allocations?stale=true", 120.0)
            read[f.id] = replay_log.digest({
                r["id"]: (r["job_id"], r["node_id"], r["desired_status"],
                          r["client_status"])
                for r in rows if r["job_id"] in jobs})
        # elections a follower put off because the silent leader's port
        # still answered (raft/node.py LEADER_STALL_GRACE): how often the
        # leader's interpreter stood still for over an election timeout
        deferred = {f.id: http_json(f.address, "/v1/metrics?stale=true").get(
            "nomad.raft.elections_deferred", 0) for f in self.followers}
        # what a run that stood still would want to know of itself: the
        # leader's sends that got no reply since it started, and its
        # longest fsync and append round since the window opened
        failed = dict(getattr(getattr(self.agent, "transport", None),
                              "failed_sends", {}))
        longest = self._longest_spans(("raft.fsync", "raft.replicate"))
        # durability: no clean shutdown, then what the disks hold. The
        # leader is without a quorum from here to the deployment's stop,
        # so its one periodic writer (the core collector proposes a log
        # entry a minute, which would time out and log an ERROR) is
        # stopped first, as the broker was paused; a pass under way
        # finishes while the followers still answer
        self.server.core_gc.stop()
        for f in self.followers:
            f.kill()
        dirs = [self.workdir / LEADER] + [f.data_dir for f in self.followers]
        disk = {}
        for d in dirs:
            state = replay_log.replay_dir(str(d), upto_index=commit)
            disk[d.name] = replay_log.digest(state.allocs, jobs)
            if state.applied < commit:
                log.error("replica check: the log in %s ends at index %d, "
                          "under the commit index %d", d, state.applied,
                          commit)
        note("replica", commit_index=commit, applied=applied,
             allocations=len(rows), jobs=len(jobs),
             leader=leader[:16],
             stale_read={k: v[:16] for k, v in read.items()},
             on_disk={k: v[:16] for k, v in disk.items()},
             fs_type=self.fs_type,
             leader_changes_in_window=moved, elections_deferred=deferred,
             failed_sends=failed, longest_s=longest)
        for where, got in [(f"stale read of {k}", v) for k, v in read.items()] \
                + [(f"data directory {k}", v) for k, v in disk.items()]:
            if got != leader:
                log.error("replica check: %s has digest %s where the "
                          "leader's store has %s (commit index %d)",
                          where, got[:16], leader[:16], commit)


def deploy(config: dict, seed: int, toy: bool) -> Deployment:
    return Deployment(config, seed, toy)
