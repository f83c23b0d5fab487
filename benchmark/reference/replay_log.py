"""The plain reference for what a server's data directory holds: read
`<data_dir>/raft/snapshot.json` (if one was taken) and
`<data_dir>/raft/log.jsonl`, and rebuild, with dicts alone,

    allocs: {allocation id: (job id, node id, desired status, client status)}
    evals:  {evaluation id: status}

from the handful of command kinds that carry them: job registration and
purge, evaluation updates and deletes, allocation upserts, client
updates, plan results in row and in AllocBlock form, the terminal-alloc
collection, and a whole-state restore. Node registrations and every
other command move neither table and are passed over; a command this
file does not know that did move one shows as a digest that differs
from the live store's, which is the point of comparing.

Independent of `raft/fsm.py`, `state/store.py` and `state/persist.py`:
nothing of theirs is imported. A record's bytes are decoded with the
program's own `structs.wire.wire_decode` (the codec of the log's lines
and of the snapshot's rows), and the decoded objects are read as plain
records: `.id`, `.job_id`, `.node_id`, `.desired_status`,
`.client_status`, `.status`, and an AllocBlock's columns.

    python3 benchmark/reference/replay_log.py <data_dir> [upto_index]
"""

from __future__ import annotations

import hashlib
import json
import os

SERVER_TERMINAL = ("stop", "evict")
CLIENT_TERMINAL = ("complete", "failed", "lost")
BLOCK_SEP = "."
SLOT_PER_NODE = ("system", "sysbatch")


def _decode(wire):
    from nomad_tpu.structs.wire import wire_decode

    return wire_decode(wire)


def read_log(path: str, base_index: int = 0) -> list:
    """-> [(index, term, wire command)] in index order, as a restart
    would read the file: a line that does not parse ends the log (a torn
    tail), a line at or under `base_index` is covered by the snapshot,
    and a second write of an index (a follower's conflicting suffix
    rewritten) replaces the first and everything after it."""
    entries: list = []
    if not os.path.exists(path):
        return entries
    with open(path, "rb") as f:
        for raw in f:
            line = raw.decode("utf-8", errors="replace").strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
                index, term = int(rec["index"]), int(rec["term"])
                command = rec["command"]
            except (ValueError, KeyError, TypeError):
                break
            if index <= base_index:
                continue
            pos = index - base_index - 1
            if pos < len(entries):
                del entries[pos:]
            elif pos > len(entries):
                continue
            entries.append((index, term, command))
    return entries


class Replay:
    """The two tables and the few facts their rules need."""

    def __init__(self):
        self.allocs: dict = {}    # id -> [job, node, desired, client]
        self.evals: dict = {}     # id -> status
        self.jobs: dict = {}      # (namespace, job id) -> job type
        self._meta: dict = {}     # alloc id -> (namespace, name, canary, ts)
        self.applied = 0          # raft index of the last command applied
        self.commands = 0

    # -- rows ----------------------------------------------------------

    def _put_row(self, a, ts) -> None:
        client = a.client_status
        prev = self.allocs.get(a.id)
        if prev is not None and client == "pending" and prev[3]:
            # the client's side of a row belongs to the client: a
            # server-side rewrite that says "pending" leaves it alone
            client = prev[3]
        self.allocs[a.id] = [a.job_id, a.node_id, a.desired_status, client]
        self._meta[a.id] = (a.namespace, a.name, bool(a.canary), ts)

    def _live(self, aid: str) -> bool:
        row = self.allocs[aid]
        return row[2] not in SERVER_TERMINAL and row[3] not in CLIENT_TERMINAL

    def _slot(self, aid: str) -> tuple:
        ns, name, _, _ = self._meta[aid]
        job, node = self.allocs[aid][0], self.allocs[aid][1]
        per_node = self.jobs.get((ns, job)) in SLOT_PER_NODE
        return (ns, job, name, node if per_node else "")

    def _put_fresh(self, fresh: list, ts) -> None:
        """First inserts of a plan: a fresh placement whose slot
        (namespace, job, name) holds a live allocation under another id
        stops that one (two plans for one slot across a failover)."""
        for a in fresh:
            self._put_row(a, ts)
        ids = {a.id for a in fresh}
        slots = {self._slot(a.id) for a in fresh if not a.canary}
        if not slots:
            return
        job_keys = {(a.namespace, a.job_id) for a in fresh if not a.canary}
        for aid, row in self.allocs.items():
            if aid in ids or self._meta[aid][2]:
                continue
            if (self._meta[aid][0], row[0]) not in job_keys:
                continue
            if (self._live(aid) and row[3] != "unknown"
                    and self._slot(aid) in slots):
                row[2] = "stop"
                self._meta[aid] = self._meta[aid][:3] + (ts,)

    def _put_block(self, b, ts) -> None:
        """An AllocBlock's visible positions, one row each: position p
        of the block is allocation `<block id>.<p>` on the node whose
        row's count prefix covers p."""
        rejected = set(b.rejected_rows or ())
        dropped = set(b.dropped or ())
        p = 0
        for m, node in enumerate(b.node_ids):
            for _ in range(int(b.counts[m])):
                if m not in rejected and p not in dropped:
                    aid = f"{b.id}{BLOCK_SEP}{p}"
                    self.allocs[aid] = [b.job_id, node, "run", "pending"]
                    name = (f"{b.job_id}.{b.task_group}"
                            f"[{int(b.name_indices[p])}]")
                    self._meta[aid] = (b.namespace, name, False, ts)
                p += 1

    def _plan(self, payload: dict, ts) -> None:
        for a in payload.get("stopped_allocs") or ():
            self._put_row(a, ts)
        for a in payload.get("preempted_allocs") or ():
            self._put_row(a, ts)
        fresh = []
        for a in payload.get("result_allocs") or ():
            if a.id in self.allocs:
                self._put_row(a, ts)
            else:
                fresh.append(a)
        if fresh:
            self._put_fresh(fresh, ts)
        for b in payload.get("alloc_blocks") or ():
            self._put_block(b, ts)
        for ev in payload.get("evals") or ():
            self.evals[ev.id] = ev.status

    # -- commands ------------------------------------------------------

    def apply(self, op: str, args: list, kwargs: dict) -> None:
        self.commands += 1
        ts = kwargs.get("ts")
        if op == "upsert_job":
            job = args[0] if args else kwargs["job"]
            self.jobs[(job.namespace, job.id)] = job.type
        elif op == "delete_job":
            job_id = args[0] if args else kwargs["job_id"]
            ns = (args[1] if len(args) > 1
                  else kwargs.get("namespace", "default"))
            purge = args[2] if len(args) > 2 else kwargs.get("purge", True)
            if purge:
                self.jobs.pop((ns, job_id), None)
        elif op == "upsert_evals":
            for ev in (args[0] if args else kwargs["evals"]):
                self.evals[ev.id] = ev.status
        elif op == "delete_evals":
            for eid in (args[0] if args else kwargs["eval_ids"]):
                self.evals.pop(eid, None)
        elif op == "upsert_allocs":
            for a in (args[0] if args else kwargs["allocs"]):
                self._put_row(a, ts)
        elif op == "update_allocs_from_client":
            for upd in (args[0] if args else kwargs["updates"]):
                if upd.id in self.allocs:
                    self.allocs[upd.id][3] = upd.client_status
                    self._meta[upd.id] = self._meta[upd.id][:3] + (ts,)
        elif op == "update_alloc_desired_transitions":
            evals = args[1] if len(args) > 1 else kwargs.get("evals") or ()
            for ev in evals:
                self.evals[ev.id] = ev.status
        elif op == "upsert_plan_results_batch":
            for payload in (args[0] if args else kwargs["payloads"]):
                self._plan(payload, ts)
        elif op == "upsert_plan_results":
            names = ("result_allocs", "stopped_allocs", "preempted_allocs",
                     "deployment", "deployment_updates", "evals",
                     "alloc_blocks", "job")
            payload = dict(zip(names, args))
            payload.update(kwargs)
            self._plan(payload, ts)
        elif op == "gc_terminal_allocs":
            before_time = (args[1] if len(args) > 1
                           else kwargs.get("before_time", float("inf")))
            self._collect(before_time)
        elif op == "restore_dump":
            self.load_state(args[0] if args else kwargs["data"])

    def _collect(self, before_time: float) -> None:
        """Allocations with no purpose left and not written since
        `before_time`: those of a purged job once either side is done
        with them, the others once both sides are."""
        dead = []
        for aid, (job, _node, desired, client) in self.allocs.items():
            ns, _, _, ts = self._meta[aid]
            if (ts or 0) > before_time:
                continue
            server, cl = desired in SERVER_TERMINAL, client in CLIENT_TERMINAL
            if (server or cl) if (ns, job) not in self.jobs else (server
                                                                  and cl):
                dead.append(aid)
        for aid in dead:
            del self.allocs[aid], self._meta[aid]

    # -- a whole state (snapshot file, restore_dump) --------------------

    def load_state(self, data: dict) -> None:
        self.allocs.clear()
        self.evals.clear()
        self.jobs.clear()
        self._meta.clear()
        for wire in data.get("jobs") or ():
            job = _decode(wire)
            self.jobs[(job.namespace, job.id)] = job.type
        for wire in data.get("evals") or ():
            ev = _decode(wire)
            self.evals[ev.id] = ev.status
        for wire in data.get("alloc_blocks") or ():
            b = _decode(wire)
            self._put_block(b, b.modify_time)
        for wire in data.get("allocs") or ():      # format 1: rows
            a = _decode(wire)
            self._put_row(a, a.modify_time)
        sec = data.get("allocs_columnar") or {}    # format 2: columns
        cols = sec.get("cols") or {}
        for i in range(int(sec.get("n", 0))):
            aid = cols["id"][i]
            self.allocs[aid] = [cols["job_id"][i], cols["node_id"][i],
                                cols["desired_status"][i],
                                cols["client_status"][i]]
            self._meta[aid] = (cols["namespace"][i], cols["name"][i],
                               bool(cols["canary"][i]),
                               cols["modify_time"][i])


def replay_dir(data_dir: str, upto_index: int = None) -> Replay:
    """Rebuild the tables from one server's data directory, applying
    log entries up to `upto_index` (the commit index the caller vouches
    for; None: every entry the file holds)."""
    raft_dir = os.path.join(data_dir, "raft")
    state = Replay()
    base = 0
    snap_path = os.path.join(raft_dir, "snapshot.json")
    if os.path.exists(snap_path):
        with open(snap_path) as f:
            snap = json.load(f)
        base = int(snap["index"])
        state.load_state(snap["data"])
        state.applied = base
    for index, _term, wire in read_log(os.path.join(raft_dir, "log.jsonl"),
                                       base):
        if upto_index is not None and index > upto_index:
            break
        op, args, kwargs = _decode(wire)
        if op not in ("noop", "config"):
            state.apply(op, list(args), dict(kwargs))
        state.applied = index
    return state


def digest(allocs: dict, job_ids=None) -> str:
    """sha256 over the sorted (allocation id, job id, node id, desired
    status, client status) rows, of `job_ids` only where given."""
    h = hashlib.sha256()
    for aid in sorted(allocs):
        job, node, desired, client = allocs[aid]
        if job_ids is None or job in job_ids:
            h.update(f"{aid}\t{job}\t{node}\t{desired}\t{client}\n".encode())
    return h.hexdigest()


if __name__ == "__main__":
    import sys
    from pathlib import Path

    sys.path[0] = str(Path(__file__).resolve().parents[2])
    out = replay_dir(sys.argv[1],
                     int(sys.argv[2]) if len(sys.argv) > 2 else None)
    print(json.dumps({"applied": out.applied, "commands": out.commands,
                      "allocs": len(out.allocs), "evals": len(out.evals),
                      "digest": digest(out.allocs)}))
