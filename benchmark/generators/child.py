"""The parent's handle on `benchmark/loadgen.py`: start it on a plan
file, read its lines, tell it `stop`, and make sure it ended."""

from __future__ import annotations

import json
import queue
import subprocess
import sys
import threading
import time
from pathlib import Path

LOADGEN = Path(__file__).resolve().parents[1] / "loadgen.py"


class Child:
    def __init__(self, plan: dict, workdir: Path):
        workdir.mkdir(parents=True, exist_ok=True)
        self.plan_path = workdir / "loadgen_plan.json"
        with open(self.plan_path, "w") as f:
            json.dump(plan, f)
        self.lines: "queue.Queue[dict]" = queue.Queue()
        self.proc = subprocess.Popen(
            [sys.executable, str(LOADGEN), str(self.plan_path)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
            bufsize=1)
        self._reader = threading.Thread(target=self._read, daemon=True,
                                        name="loadgen-reader")
        self._reader.start()
        self.seen: dict = {}

    def _read(self) -> None:
        for line in self.proc.stdout:
            line = line.strip()
            if line:
                try:
                    self.lines.put(json.loads(line))
                except ValueError:
                    self.lines.put({"event": "noise", "line": line[:200]})
        self.lines.put({"event": "eof"})

    def wait_for(self, event: str, timeout: float):
        """The first `event` line within `timeout` seconds, else None.
        Other lines met on the way are kept in `seen`."""
        if event in self.seen:
            return self.seen[event]
        deadline = time.time() + timeout
        while True:
            left = deadline - time.time()
            if left <= 0:
                return None
            try:
                msg = self.lines.get(timeout=left)
            except queue.Empty:
                return None
            self.seen.setdefault(msg.get("event"), msg)
            if msg.get("event") == event:
                return msg
            if msg.get("event") in ("eof", "error"):
                raise RuntimeError(f"load generator ended early: {msg}")

    def say(self, word: str) -> None:
        self.proc.stdin.write(word + "\n")
        self.proc.stdin.flush()

    def finish(self, timeout: float = 30.0) -> dict:
        """`stop` -> the report line; the process has ended on return."""
        try:
            self.say("stop")
            report = self.wait_for("report", timeout)
        finally:
            self.close()
        if report is None:
            raise RuntimeError("load generator gave no report")
        return report

    def close(self) -> None:
        if self.proc.poll() is None:
            try:
                self.proc.stdin.close()
                self.proc.wait(5.0)
            except (OSError, subprocess.TimeoutExpired):
                self.proc.kill()
                self.proc.wait()
        self._reader.join(5.0)
