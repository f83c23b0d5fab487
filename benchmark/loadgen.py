#!/usr/bin/env python3
"""The client side of a cell, in a process of its own.

    python3 benchmark/loadgen.py <plan.json>

Speaks only HTTP to the agent (GET /v1/event/stream topic Evaluation,
GET /v1/evaluations to resynchronise), imports nothing of the program
and never JAX, so it holds no chip and shares no interpreter lock with
the scheduler it measures. The parent loaded a backlog; this process
follows the event stream and reports when each job's evaluation chain
completed, printing a milestone line when jobs holding `stop_share` of
the backlog's allocations are complete and when all are.

Protocol: prints {"event": "ready"} once subscribed; reads `stop`
(prints the final {"event": "report", ...} line and exits) on stdin.
All times are time.time() of this host.
"""

from __future__ import annotations

import http.client
import json
import sys
import threading
import time
from urllib.parse import urlparse

TERMINAL = ("complete", "failed", "cancelled")


class ChainTracker:
    """Evaluation events -> when each job's chain completed.

    A job is done when every evaluation seen for it is terminal, its
    newest one is `complete`, and no terminal one hands over
    (`blocked_eval`, `next_eval`) to an evaluation that is not itself
    terminal: a `failed` eval that ran out of plan attempts and its
    blocked follow-up are one chain, and the client waits for its end."""

    def __init__(self, jobs: dict):
        self.jobs = jobs                      # job id -> alloc count
        self.evals: dict = {}                 # job id -> {eval id: row}
        self.done_at: dict = {}               # job id -> time
        self.ended_bad: dict = {}             # job id -> status
        self.lock = threading.Lock()
        self.events = 0

    def feed(self, ev: dict, now: float) -> bool:
        job_id = ev.get("job_id")
        if job_id not in self.jobs:
            return False
        with self.lock:
            self.events += 1
            rows = self.evals.setdefault(job_id, {})
            rows[ev["id"]] = (ev.get("status"), ev.get("blocked_eval") or "",
                              ev.get("next_eval") or "",
                              int(ev.get("modify_index") or 0))
            return self._settle(job_id, rows, now)

    def _settle(self, job_id: str, rows: dict, now: float) -> bool:
        newest, newest_idx = None, -1
        for eid, (status, blocked, nxt, idx) in rows.items():
            if status not in TERMINAL:
                self.done_at.pop(job_id, None)
                return False
            for ref in (blocked, nxt):
                if ref and rows.get(ref, ("",))[0] not in TERMINAL:
                    self.done_at.pop(job_id, None)
                    return False
            if idx >= newest_idx:
                newest, newest_idx = status, idx
        if newest == "complete":
            self.ended_bad.pop(job_id, None)
            if job_id not in self.done_at:
                self.done_at[job_id] = now
                return True
            return False
        self.ended_bad[job_id] = newest
        return False

    def allocs_done(self) -> int:
        with self.lock:
            return sum(self.jobs[j] for j in self.done_at)


class Client:
    def __init__(self, address: str):
        u = urlparse(address)
        self.host, self.port = u.hostname, u.port

    def conn(self, timeout: float = 35.0) -> http.client.HTTPConnection:
        return http.client.HTTPConnection(self.host, self.port,
                                          timeout=timeout)

    def get_json(self, path: str):
        c = self.conn()
        try:
            c.request("GET", path)
            r = c.getresponse()
            body = r.read()
            if r.status != 200:
                raise RuntimeError(f"GET {path}: {r.status} {body[:200]!r}")
            return json.loads(body)
        finally:
            c.close()


def watch(client: Client, tracker: ChainTracker, stop: threading.Event,
          ready: threading.Event, on_done, stats: dict) -> None:
    """Follow /v1/event/stream?topic=Evaluation until `stop`. A
    truncation marker or a reconnect resynchronises from the list
    endpoint, so a lapped ring delays a completion but never loses it."""

    def resync():
        stats["resyncs"] += 1
        now = time.time()
        for ev in client.get_json("/v1/evaluations?namespace=default"):
            if tracker.feed(ev, now):
                on_done()

    first = True
    while not stop.is_set():
        c = client.conn(timeout=30.0)
        try:
            c.request("GET", "/v1/event/stream?topic=Evaluation&wait=600s")
            r = c.getresponse()
            if r.status != 200:
                raise RuntimeError(f"event stream: {r.status}")
            if first:
                first = False
                ready.set()
            else:
                resync()
            while not stop.is_set():
                line = r.readline()
                if not line:
                    break
                line = line.strip()
                if not line:
                    continue
                msg = json.loads(line)
                if msg.get("Topic") == "Truncation":
                    resync()
                    continue
                if msg.get("Topic") == "Evaluation" and msg.get("Payload"):
                    if tracker.feed(msg["Payload"], time.time()):
                        on_done()
        except (OSError, http.client.HTTPException, ValueError) as e:
            if stop.is_set():
                return
            stats["stream_errors"].append(repr(e)[:200])
            time.sleep(0.05)
        finally:
            c.close()


def main(argv) -> int:
    with open(argv[1]) as f:
        plan = json.load(f)
    client = Client(plan["address"])
    jobs = {j["id"]: int(j["count"]) for j in plan["jobs"]}
    tracker = ChainTracker(jobs)
    total = sum(jobs.values())
    share = float(plan.get("stop_share", 1.0))
    stop, ready = threading.Event(), threading.Event()
    stats = {"resyncs": 0, "stream_errors": []}
    out_lock = threading.Lock()
    said = set()

    def say(obj: dict) -> None:
        with out_lock:
            sys.stdout.write(json.dumps(obj) + "\n")
            sys.stdout.flush()

    def on_done() -> None:
        done = tracker.allocs_done()
        now = time.time()
        if "share" not in said and done >= share * total:
            said.add("share")
            say({"event": "share", "t": now, "allocs": done})
        if "all" not in said and done >= total:
            said.add("all")
            say({"event": "all", "t": now, "allocs": done})

    watcher = threading.Thread(
        target=watch, args=(client, tracker, stop, ready, on_done, stats),
        name="loadgen-watch", daemon=True)
    watcher.start()
    if not ready.wait(30.0):
        say({"event": "error", "error": "event stream did not open",
             "detail": stats["stream_errors"][:3]})
        return 1
    say({"event": "ready", "jobs": len(jobs)})
    for line in sys.stdin:
        if line.strip() == "stop":
            break
    stop.set()
    with tracker.lock:
        report = {"event": "report", "done_at": dict(tracker.done_at),
                  "ended_bad": dict(tracker.ended_bad),
                  "evals": {j: [r[0] for r in rows.values()]
                            for j, rows in tracker.evals.items()},
                  "events": tracker.events, **stats}
    say(report)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
