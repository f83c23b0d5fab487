"""The replicated deployment (`grid-10k-r3`, PR 28): a server built with
`--workers 0` opens no device; three servers with fsync and quorum
commit drain a toy spread backlog and read the same from every replica
and from every data directory; the plain replay reference agrees with
the FSM on seeded command streams; leadership holds by the timers'
arithmetic and `nomad.raft.leader_changes` counts a step-down."""

import json
import logging
import random
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from nomad_tpu import mock  # noqa: E402
from nomad_tpu.structs import enums  # noqa: E402


# -- (a) a server that never schedules opens no device ------------------------


def _agent_args(*extra):
    from nomad_tpu import cli

    return cli.build_parser().parse_args(
        ["agent", "--port", "0", "--clients", "0", "--algorithm",
         "tpu-binpack", *extra])


def test_workers_zero_never_bootstraps_a_backend(monkeypatch):
    from nomad_tpu import cli
    from nomad_tpu.api.client import ApiClient
    from nomad_tpu.tensor import backend

    def boom(*a, **k):
        raise AssertionError("bootstrap called for a server with no workers")

    monkeypatch.setattr(backend, "bootstrap", boom)
    monkeypatch.setattr(backend, "device", boom)
    agent = cli.Agent(_agent_args("--workers", "0"))
    try:
        assert "device=none" in agent.start_line
        assert "workers=0" in agent.start_line
        assert agent.device is None and agent.server.workers == []
        stats = ApiClient(address=agent.http.address)._request(
            "GET", "/v1/agent/self")[0]["stats"]
        assert stats["device"]["platform"] == "none"
        assert stats["solver"] == {}
        # no scheduler can be started on it later: the dry run, the one
        # request that runs a scheduler outside a worker, is refused
        with pytest.raises(RuntimeError, match="runs no scheduler"):
            agent.server.plan_job(mock.job())
    finally:
        agent.stop()


def test_workers_zero_process_never_imports_jax():
    """In a process of its own (this one imported jax in conftest): a
    `--workers 0` agent serves a registration, a read and its own stats
    without jax ever entering `sys.modules`."""
    code = """
import sys
from nomad_tpu import cli, mock
from nomad_tpu.api.client import ApiClient
args = cli.build_parser().parse_args(["agent", "--port", "0", "--clients",
    "0", "--workers", "0", "--algorithm", "tpu-binpack"])
agent = cli.Agent(args)
print(agent.start_line)
api = ApiClient(address=agent.http.address)
agent.server.register_node(mock.node())
api.register_job(mock.job())
assert api.list_jobs()
assert api._request("GET", "/v1/agent/self")[0]["stats"]["device"][
    "platform"] == "none"
agent.stop()
print("jax_loaded=%s" % ("jax" in sys.modules or "jaxlib" in sys.modules))
"""
    proc = subprocess.run([sys.executable, "-c", code], cwd=str(ROOT),
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "device=none" in proc.stdout
    assert "jax_loaded=False" in proc.stdout


def test_help_says_what_workers_zero_means(capsys):
    from nomad_tpu import cli

    with pytest.raises(SystemExit):
        cli.build_parser().parse_args(["agent", "--help"])
    text = " ".join(capsys.readouterr().out.split())
    assert "device=none" in text and "num_schedulers = 0" in text


# -- (b), (d) three servers, fsync, quorum commit ------------------------------


def _toy_parts():
    from benchmark import traffic as traffic_mod
    from benchmark.harness import HERE, load_json
    from benchmark.jobs import build_job

    config = load_json(HERE / "configs" / "grid-10k-r3.json")
    traffic = load_json(HERE / "traffic" / "spread.300.json")
    traffic = {**traffic, **traffic["toy"]}
    specs = traffic_mod.job_specs(traffic, 11, int(traffic["jobs"]), "t-r3")
    return config, [build_job(s) for s in specs], specs


class _Records(logging.Handler):
    def __init__(self):
        super().__init__(level=logging.ERROR)
        self.messages = []

    def emit(self, record):
        self.messages.append(record.getMessage())


@pytest.fixture
def replica_errors():
    handler = _Records()
    logger = logging.getLogger("benchmark.replica")
    logger.addHandler(handler)
    yield handler.messages
    logger.removeHandler(handler)


def _deployment(tmp_path):
    from benchmark.deploy import three_servers

    config, jobs, specs = _toy_parts()
    dep = three_servers.deploy(config, 11, True)
    dep.workdir = tmp_path / "r3"
    return dep, jobs, specs


def test_three_servers_drain_and_every_replica_and_disk_agree(
        tmp_path, replica_errors, capsys):
    dep, jobs, specs = _deployment(tmp_path)
    try:
        dep.start()
        for f in dep.followers:
            assert "device=none" in f.start_line
            assert f.loaded_accelerator_libraries() == []
        assert sorted(dep.replicated.raft.servers) == [
            "server-0", "server-1", "server-2"]
        dep.pause_broker(True)
        dep.submit(jobs, threads=2)
        dep.pause_broker(False)
        dep.drain([j.id for j in jobs], timeout=120)
        snap = dep.server.store.snapshot()
        for s in specs:
            assert len(snap.allocs_by_job(s["id"])) == s["count"]
        assert dep.quiesce() is True
        # the check leaves the leader without a quorum: nothing on it may
        # propose a write on a timer any more (a core collector pass
        # would time out and log an ERROR), and a write times out
        assert dep.server.core_gc._stop.is_set()
        assert all(f.proc.poll() is not None for f in dep.followers)
        with pytest.raises(TimeoutError):
            dep.replicated.raft.apply(("noop", (), {}), timeout=0.3)
    finally:
        dep.stop()
    assert replica_errors == []
    out = capsys.readouterr().out
    line = [ln for ln in out.splitlines() if ln.startswith("[replica]")][-1]
    # three digests from the disks, two from stale reads, all the leader's
    fields = dict(kv.split("=", 1) for kv in line.split(" ")[1:]
                  if "=" in kv and not kv.startswith(("applied", "stale",
                                                      "on_disk")))
    leader = json.loads(fields["leader"])
    assert line.count(leader) == 6
    assert json.loads(fields["allocations"]) == sum(s["count"] for s in specs)
    assert '"tmpfs"' not in line
    # what a run that stood still would want to know of itself
    assert 'elections_deferred={"server-1": 0, "server-2": 0}' in line
    assert "failed_sends={}" in line
    assert 'longest_s={"raft.fsync": 0.' in line


def test_a_follower_killed_mid_drain_catches_up_after_restart(
        tmp_path, replica_errors):
    dep, jobs, specs = _deployment(tmp_path)
    try:
        dep.start()
        victim = dep.followers[1]
        dep.pause_broker(True)
        dep.submit(jobs, threads=2)
        victim.kill()                       # SIGKILL, backlog not drained
        dep.pause_broker(False)
        # the drain completes on the quorum that is left (2 of 3)
        dep.drain([j.id for j in jobs], timeout=120)
        snap = dep.server.store.snapshot()
        for s in specs:
            assert len(snap.allocs_by_job(s["id"])) == s["count"]
        behind = dep.replicated.raft.commit_index
        victim.spawn()
        victim.wait_started(time.time() + 60.0)
        assert "device=none" in victim.start_line
        # catch-up from its own data directory plus the leader's log
        assert dep.quiesce() is True
        assert dep.replicated.raft.commit_index >= behind
    finally:
        dep.stop()
    assert replica_errors == []


def test_tmpfs_is_refused(tmp_path, monkeypatch):
    from benchmark.deploy import three_servers

    dep, _, _ = _deployment(tmp_path)
    monkeypatch.setattr(three_servers, "fs_type", lambda path: "tmpfs")
    with pytest.raises(RuntimeError, match="fsync is a no-op"):
        dep.start()
    assert dep.agent is None and dep.followers == []


def test_fs_type_reads_the_mount_table():
    from benchmark.deploy import three_servers

    assert three_servers.fs_type(Path("/proc")) == "proc"
    assert three_servers.fs_type(ROOT) not in ("unknown", "proc")


# -- (c) the plain replay reference against the FSM ----------------------------


def _block(job, nodes, k, counts):
    from nomad_tpu.structs.alloc import AllocBlock
    from nomad_tpu.utils import generate_uuid

    vec = np.zeros_like(mock.alloc(job, nodes[0]).allocated_vec)
    vec[0], vec[1] = 50.0, 32.0
    return AllocBlock(
        id=generate_uuid(), eval_id=generate_uuid(), namespace=job.namespace,
        job_id=job.id, job=job, job_version=job.version,
        task_group=job.task_groups[0].name,
        name_indices=np.arange(k, k + sum(counts), dtype=np.int64),
        node_ids=[n.id for n in nodes[:len(counts)]],
        node_names=[n.name for n in nodes[:len(counts)]],
        counts=np.array(counts, dtype=np.int64), allocated_vec=vec)


def _command_stream(seed: int, n: int = 70):
    """(op, args, kwargs) commands as proposers make them: registrations,
    evaluation updates, row plans (with stops, and now and then a slot
    placed twice), AllocBlock plans, client updates, purges, collection."""
    import copy

    rng = random.Random(seed)
    nodes = [mock.node() for _ in range(6)]
    for nd in nodes:
        nd.compute_class()
    jobs = [mock.job(), mock.job(), mock.batch_job(), mock.system_job()]
    t = [1_000_000.0]

    def ts():
        t[0] += 1.0
        return t[0]

    out = [("upsert_nodes", (nodes,), {})]
    out += [("upsert_job", (j,), {}) for j in jobs]
    rows, evals, next_index = [], [], {j.id: 0 for j in jobs}
    live_jobs = list(jobs)

    def fresh(job, reuse_slot=False):
        if reuse_slot and next_index[job.id]:
            index = rng.randrange(next_index[job.id])
        else:
            index = next_index[job.id]
            next_index[job.id] += 1
        a = mock.alloc(job, rng.choice(nodes), index,
                       client_status=enums.ALLOC_CLIENT_PENDING)
        a.job = None
        return a

    for _ in range(n):
        kind = rng.choice(["evals", "plan", "plan", "plan", "block", "client",
                           "stop", "eval_update", "purge", "gc", "allocs",
                           "delete_evals", "transition"])
        job = rng.choice(live_jobs)
        if kind == "evals":
            ev = mock.eval_for(job)
            evals.append(ev)
            out.append(("upsert_evals", ([ev],), {"ts": ts()}))
        elif kind == "eval_update" and evals:
            ev = copy.copy(rng.choice(evals))
            ev.status = rng.choice(["complete", "failed", "blocked",
                                    "canceled"])
            out.append(("upsert_evals", ([ev],), {"ts": ts()}))
        elif kind == "plan":
            new = [fresh(job, reuse_slot=rng.random() < 0.15)
                   for _ in range(rng.randint(1, 5))]
            ev = mock.eval_for(job, status="complete")
            evals.append(ev)
            rows += new
            payload = {"result_allocs": new, "stopped_allocs": [],
                       "preempted_allocs": [], "deployment": None,
                       "deployment_updates": [], "evals": [ev],
                       "alloc_blocks": [], "job": job}
            if rng.random() < 0.5:
                out.append(("upsert_plan_results_batch", ([payload],),
                            {"ts": ts()}))
            else:
                out.append(("upsert_plan_results", (), {**payload,
                                                        "ts": ts()}))
        elif kind == "block":
            counts = [rng.randint(1, 3) for _ in range(rng.randint(1, 4))]
            b = _block(job, nodes, next_index[job.id], counts)
            next_index[job.id] += sum(counts)
            if rng.random() < 0.3 and len(counts) > 1:
                b = b.without_nodes([b.node_ids[0]])
            for p in range(b.size):
                if b.visible(p):
                    rows.append(b.alloc_at(p))
            out.append(("upsert_plan_results_batch", ([{
                "result_allocs": [], "evals": [], "alloc_blocks": [b],
                "job": job}],), {"ts": ts()}))
        elif kind == "stop" and rows:
            a = copy.copy(rng.choice(rows))
            a.job = None
            a.desired_status = rng.choice(["stop", "evict"])
            a.client_status = enums.ALLOC_CLIENT_PENDING
            key = rng.choice(["stopped_allocs", "preempted_allocs"])
            out.append(("upsert_plan_results_batch", ([{
                "result_allocs": [], key: [a], "evals": [],
                "job": None}],), {"ts": ts()}))
        elif kind == "client" and rows:
            from nomad_tpu.structs import Allocation

            ups = [Allocation(id=a.id, client_status=rng.choice(
                ["running", "complete", "failed", "lost", "unknown"]))
                for a in rng.sample(rows, min(3, len(rows)))]
            out.append(("update_allocs_from_client", (ups,), {"ts": ts()}))
        elif kind == "allocs" and rows:
            a = copy.copy(rng.choice(rows))
            a.desired_status = "stop"
            a.client_status = enums.ALLOC_CLIENT_PENDING
            out.append(("upsert_allocs", ([a],), {"ts": ts()}))
        elif kind == "purge" and len(live_jobs) > 2:
            live_jobs.remove(job)
            out.append(("delete_job", (job.id, job.namespace), {}))
        elif kind == "gc":
            out.append(("gc_terminal_allocs", (1 << 60,),
                        {"before_time": t[0] - rng.randint(0, 12)}))
        elif kind == "delete_evals" and evals:
            gone = rng.sample(evals, min(2, len(evals)))
            out.append(("delete_evals", ([e.id for e in gone],), {}))
        elif kind == "transition" and rows:
            from nomad_tpu.structs.alloc import DesiredTransition

            ev = mock.eval_for(job)
            evals.append(ev)
            out.append(("update_alloc_desired_transitions",
                        ({rng.choice(rows).id: DesiredTransition(
                            migrate=True)}, [ev]), {"ts": ts()}))
    return out


def _store_tables(store):
    snap = store.snapshot()
    allocs = {a.id: (a.job_id, a.node_id, a.desired_status, a.client_status)
              for a in snap.allocs()}
    return allocs, {e.id: e.status for e in snap.evals()}


def _replay_tables(data_dir, upto=None):
    from benchmark.reference import replay_log

    state = replay_log.replay_dir(str(data_dir), upto)
    return ({k: tuple(v) for k, v in state.allocs.items()}, state.evals,
            state)


@pytest.mark.parametrize("seed", [1, 2, 3, 4, 5, 6])
def test_replay_reference_agrees_with_the_fsm(tmp_path, seed):
    import copy

    from nomad_tpu.raft.durable import DurableLog
    from nomad_tpu.raft.fsm import FSM
    from nomad_tpu.state import StateStore

    raft_dir = tmp_path / "raft"
    raft_dir.mkdir()
    log = DurableLog(str(raft_dir))
    store = StateStore()
    fsm = FSM(store)
    commands = _command_stream(seed)
    log.append(1, ("noop", (), {}))
    for cmd in commands:
        # the FSM owns what it applies, the log what it was handed
        log.append_batch(1, [copy.deepcopy(cmd)])
        fsm.apply(copy.deepcopy(cmd))
    allocs, evals = _store_tables(store)
    got_allocs, got_evals, state = _replay_tables(tmp_path)
    assert state.applied == len(commands) + 1
    assert got_allocs == allocs
    assert got_evals == evals
    assert allocs and evals, "an empty stream proves nothing"
    # a prefix of the log is the state at that index
    half = len(commands) // 2
    store2 = StateStore()
    fsm2 = FSM(store2)
    for cmd in commands[:half]:
        fsm2.apply(copy.deepcopy(cmd))
    a2, e2, _ = _replay_tables(tmp_path, upto=half + 1)
    assert (a2, e2) == _store_tables(store2)


@pytest.mark.parametrize("seed", [7, 8, 9])
def test_replay_reference_reads_a_snapshot_and_the_tail(tmp_path, seed):
    import copy

    from nomad_tpu.raft.durable import DurableLog, SnapshotStore
    from nomad_tpu.raft.fsm import FSM
    from nomad_tpu.state import StateStore
    from nomad_tpu.state.persist import dump_store

    raft_dir = tmp_path / "raft"
    raft_dir.mkdir()
    log = DurableLog(str(raft_dir))
    store = StateStore()
    fsm = FSM(store)
    commands = _command_stream(seed, n=60)
    cut = 2 * len(commands) // 3
    for i, cmd in enumerate(commands, start=1):
        log.append_batch(1, [copy.deepcopy(cmd)])
        fsm.apply(copy.deepcopy(cmd))
        if i == cut:
            SnapshotStore(str(raft_dir)).save(
                i, 1, json.loads(json.dumps(dump_store(store))))
            log.compact(i, 1)
    assert log.first_index() == cut + 1
    got_allocs, got_evals, state = _replay_tables(tmp_path)
    assert state.applied == len(commands)
    assert (got_allocs, got_evals) == _store_tables(store)


def test_replay_reads_the_log_as_a_restart_would(tmp_path):
    """A torn tail ends the log; a rewritten index replaces the first
    write and what followed it."""
    from benchmark.reference import replay_log

    path = tmp_path / "log.jsonl"
    lines = [{"index": i, "term": 1, "command": ["noop", [], {}]}
             for i in (1, 2, 3)]
    lines.append({"index": 2, "term": 2, "command": ["noop", [], {}]})
    path.write_text("\n".join(json.dumps(x) for x in lines)
                    + '\n{"index": 3, "term": 2, "comm')
    got = replay_log.read_log(str(path))
    assert [(i, t) for i, t, _ in got] == [(1, 1), (2, 2)]
    assert replay_log.read_log(str(path), base_index=1)[0][0] == 2


# -- (f) leadership under a busy leader, by the timers' arithmetic -------------


def _bare_node(node_id="a"):
    from nomad_tpu.raft.node import RaftNode
    from nomad_tpu.raft.transport import InProcTransport

    # default timers, threads never started: handlers driven by hand
    return RaftNode(node_id, ["a", "b", "c"], InProcTransport(),
                    lambda cmd: None)


def test_timer_scale_is_upstreams_and_outlasts_a_busy_interpreter():
    from nomad_tpu.raft import node as rn

    assert rn.ELECTION_TIMEOUT == 1.0 and rn.HEARTBEAT_INTERVAL == 0.1
    n = _bare_node()
    assert n.election_timeout == rn.ELECTION_TIMEOUT
    assert n.heartbeat_interval == rn.HEARTBEAT_INTERVAL
    assert n.lease_duration == 0.5
    # the longest single host phase on record under load (PERF.md section
    # 5) is 0.141 s; ten heartbeats fit into the shortest election timeout
    assert rn.ELECTION_TIMEOUT >= 7 * 0.141
    assert rn.ELECTION_TIMEOUT >= 10 * rn.HEARTBEAT_INTERVAL
    now = time.time()
    for _ in range(200):
        left = n._new_deadline() - now
        assert rn.ELECTION_TIMEOUT <= left <= 2 * rn.ELECTION_TIMEOUT + 0.5


def test_a_heartbeat_buys_a_full_election_timeout_and_blocks_votes():
    from nomad_tpu.raft import node as rn

    f = _bare_node("b")
    beat = {"kind": "append_entries", "term": 1, "leader": "a",
            "prev_log_index": 0, "prev_log_term": 0, "entries": [],
            "leader_commit": 0}
    t0 = time.time()
    assert f.handle(beat)["success"] is True
    # no campaign before a whole election timeout has passed in silence
    assert f._deadline - t0 >= rn.ELECTION_TIMEOUT
    # a leader whose interpreter stalls for 0.9 s is still the leader to
    # this follower: a campaigner gets no vote and moves no term
    f._last_leader_contact = time.time() - 0.9
    reply = f.handle({"kind": "request_vote", "term": 5, "candidate": "c",
                      "last_log_index": 9, "last_log_term": 4})
    assert reply == {"term": 1, "granted": False}
    assert f.current_term == 1 and f.leader_id == "a"
    # after a silence longer than any election timeout the vote is free
    f._last_leader_contact = time.time() - 2 * rn.ELECTION_TIMEOUT - 0.5
    assert f.handle({"kind": "request_vote", "term": 5, "candidate": "c",
                     "last_log_index": 9, "last_log_term": 4})["granted"]


def test_leader_changes_counts_an_election_and_a_forced_step_down():
    from nomad_tpu.core.metrics import REGISTRY

    n = _bare_node()
    assert "nomad.raft.leader_changes" in REGISTRY.dump()
    before = REGISTRY.get("nomad.raft.leader_changes")
    with n._lock:
        n.current_term = 1
        n._become_leader_locked()
    assert n.is_leader()
    assert REGISTRY.get("nomad.raft.leader_changes") == before + 1
    # a reply from a higher term deposes the leader
    with n._lock:
        n._become_follower_locked(2)
    assert not n.is_leader() and n.current_term == 2
    assert REGISTRY.get("nomad.raft.leader_changes") == before + 2
    # a follower that moves to a new term was no leader: no change
    with n._lock:
        n._become_follower_locked(3)
    assert REGISTRY.get("nomad.raft.leader_changes") == before + 2


class _Probe:
    """A transport whose `peer_alive` answers what the test says."""

    def __init__(self, answer):
        self.answer, self.asked = answer, []

    def register(self, node_id, handler):
        pass

    def peer_alive(self, peer):
        self.asked.append(peer)
        return self.answer


def _silent_follower(answer, silent_for):
    """Follower "b" that last heard from leader "a" `silent_for` seconds
    ago and whose election deadline has passed; threads never started."""
    from nomad_tpu.raft.node import RaftNode

    f = RaftNode("b", ["a", "b", "c"], _Probe(answer), lambda cmd: None)
    f.leader_id = "a"
    f._last_leader_contact = time.time() - silent_for
    f._deadline = time.time() - 0.01
    return f


@pytest.mark.parametrize("silent_for", [1.2, 2.8, 4.4])
def test_a_stalled_leader_whose_port_answers_is_not_campaigned_against(
        silent_for):
    from nomad_tpu.core.metrics import REGISTRY
    from nomad_tpu.raft import node as rn

    f = _silent_follower(True, silent_for)
    before = REGISTRY.get("nomad.raft.elections_deferred")
    now = time.time()
    assert f._leader_stalled() is True
    assert f.transport.asked == ["a"]
    assert REGISTRY.get("nomad.raft.elections_deferred") == before + 1
    # the next look comes within half an election timeout, and never
    # after the grace has run out
    grace_ends = f._last_leader_contact \
        + rn.LEADER_STALL_GRACE * rn.ELECTION_TIMEOUT
    assert now < f._deadline <= min(time.time() + rn.ELECTION_TIMEOUT / 2,
                                    grace_ends)
    assert f.state == rn.FOLLOWER and f.current_term == 0


@pytest.mark.parametrize("answer, silent_for, why", [
    (False, 1.2, "connect refused or timed out: the process is gone"),
    (None, 1.2, "the transport cannot tell (a fault plan decides)"),
    (True, 5.1, "the grace has run out: alive or not, it is no leader"),
    (True, 60.0, "long silence"),
])
def test_a_dead_or_long_silent_leader_is_campaigned_against_at_once(
        answer, silent_for, why):
    from nomad_tpu.core.metrics import REGISTRY

    f = _silent_follower(answer, silent_for)
    before = REGISTRY.get("nomad.raft.elections_deferred")
    deadline = f._deadline
    assert f._leader_stalled() is False, why
    assert f._deadline == deadline
    assert REGISTRY.get("nomad.raft.elections_deferred") == before


def test_no_grace_without_a_known_leader_or_a_probe():
    # never heard from a leader (a fresh start): nothing to wait for
    f = _silent_follower(True, 1.2)
    f._last_leader_contact = 0.0
    assert f._leader_stalled() is False and f.transport.asked == []
    f = _silent_follower(True, 1.2)
    f.leader_id = None
    assert f._leader_stalled() is False and f.transport.asked == []
    # an in-process transport has no port to ask
    assert _bare_node("b")._leader_stalled() is False


def test_a_heartbeat_during_the_probe_keeps_its_full_deadline():
    f = _silent_follower(True, 1.2)
    fresh = time.time() + 1.7

    def heard_meanwhile(peer):
        f._deadline = fresh     # what _on_append_entries sets
        return True

    f.transport.peer_alive = heard_meanwhile
    assert f._leader_stalled() is True
    assert f._deadline == fresh


def test_socket_transport_asks_the_kernel_not_the_interpreter():
    import socket

    from nomad_tpu.raft.transport import SocketTransport

    # a port that is listened on and never accepted from: what a leader
    # whose interpreter stands still looks like from outside
    stalled = socket.socket()
    stalled.bind(("127.0.0.1", 0))
    stalled.listen(8)
    gone = socket.socket()
    gone.bind(("127.0.0.1", 0))
    gone_addr = "127.0.0.1:%d" % gone.getsockname()[1]
    gone.close()
    t = SocketTransport("b", "127.0.0.1:0", {
        "a": "127.0.0.1:%d" % stalled.getsockname()[1], "c": gone_addr})
    try:
        assert t.peer_alive("a") is True
        assert t.peer_alive("c") is False
        assert t.peer_alive("nobody") is False
        t.set_fault_plan(object())
        assert t.peer_alive("a") is None
    finally:
        stalled.close()


# -- the write path's spans and counters ---------------------------------------


def test_commit_path_spans_and_counters_on_a_durable_cluster(tmp_path):
    from nomad_tpu.core.metrics import REGISTRY
    from nomad_tpu.obs import TRACER
    from nomad_tpu.raft.cluster import RaftCluster

    names = ("nomad.raft.entries", "nomad.raft.fsyncs",
             "nomad.raft.append_bytes")
    with RaftCluster(3, data_dir=str(tmp_path)) as cluster:
        leader = cluster.wait_for_leader(15.0)
        assert leader is not None
        before = {k: REGISTRY.get(k) for k in names}
        t0 = time.time()
        job = mock.job()
        leader.store.upsert_job(job)
        leader.store.upsert_plan_results(
            [mock.alloc(job, mock.node(), i) for i in range(7)], job=job)
        spans = [r for r in TRACER.spans() if r[4] >= t0]
    delta = {k: REGISTRY.get(k) - before[k] for k in names}
    assert delta["nomad.raft.entries"] == 2
    assert 1 <= delta["nomad.raft.fsyncs"] <= 2
    by = {}
    for r in spans:
        by.setdefault(r[0], []).append(r)
    encodes = [r[7] for r in by["raft.encode"]]
    assert sorted(e["rows"] for e in encodes) == [1, 7]
    assert delta["nomad.raft.append_bytes"] == sum(e["bytes"] for e in encodes)
    commits = {r[7]["kind"]: r for r in by["raft.commit"]}
    assert set(commits) == {"upsert_job", "upsert_plan_results"}
    plan = commits["upsert_plan_results"]
    assert plan[7]["bytes"] == max(e["bytes"] for e in encodes)
    # enqueue -> resolved spans its own encode and fsync
    enc = max(by["raft.encode"], key=lambda r: r[7]["bytes"])
    assert plan[4] <= enc[4] and enc[5] <= plan[5]
    assert by["raft.fsync"] and by["raft.apply"] and by["raft.replicate"]
    assert REGISTRY.get("nomad.raft.follower_lag") >= 0


# -- a command is encoded once: the leader's text is what followers hold -------


def test_entry_decodes_its_text_on_first_use_only():
    from nomad_tpu.raft.durable import DurableLog
    from nomad_tpu.raft.log import Entry

    job = mock.job()
    text = DurableLog.encode_command(("upsert_job", (job,), {}))
    e = Entry(index=4, term=2, wire=text)
    assert e._command is None and not e.is_config()
    assert e._command is None, "is_config must not decode"
    op, args, kwargs = e.command
    assert op == "upsert_job" and args[0].id == job.id and kwargs == {}
    assert e.command is e.command
    cfg = Entry(index=5, term=2, wire=DurableLog.encode_command(
        ("config", ({"a": "", "b": ""},), {})))
    assert cfg.is_config() and cfg._command is None
    assert Entry(1, 1, ("config", ({},), {})).is_config()
    assert Entry(1, 1, ("noop", (), {})) == Entry(1, 1, ("noop", (), {}))


def test_followers_log_the_leaders_text_and_apply_the_same_state(tmp_path):
    from nomad_tpu.raft.cluster import RaftCluster

    def lines(server_id):
        path = tmp_path / server_id / "raft" / "log.jsonl"
        return {json.loads(ln)["index"]: ln
                for ln in path.read_text().splitlines()}

    with RaftCluster(3, data_dir=str(tmp_path)) as cluster:
        leader = cluster.wait_for_leader(15.0)
        job = mock.job()
        leader.store.upsert_job(job)
        node = mock.node()
        index = leader.store.upsert_plan_results(
            [mock.alloc(job, node, i) for i in range(5)], job=job)
        raft_index = leader.raft.last_applied
        for f in cluster.followers():
            f.raft.wait_applied(raft_index, timeout=10.0)
            assert len(f.local_store.snapshot().allocs_by_job(job.id)) == 5
            assert f.local_store.latest_index == index
            entry = f.raft.log.get(raft_index)
            assert entry.wire is not None and entry.wire == \
                leader.raft.log.get(raft_index).wire
        ids = [s.id for s in cluster.servers.values()]
    mine = lines(leader.id)
    assert '"upsert_plan_results"' in mine[raft_index]
    for sid in ids:
        got = lines(sid)
        # byte for byte what the leader's log writer encoded, once
        assert got[raft_index] == mine[raft_index]
        assert got[raft_index - 1] == mine[raft_index - 1]
