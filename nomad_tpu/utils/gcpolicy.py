"""Keep the cyclic collector's full passes proportional to what is new.

A server holds hundreds of thousands of long-lived objects that form no
cycles (nodes, allocations, log entries). CPython's full (generation 2)
collection walks every one of them, with every thread stopped, each time
the long-lived heap has grown by a quarter. On record at the benchmark's
size (10,000 nodes, 15,000 allocations through the log; sandbox CPU):
four passes of 0.14-0.21 s inside a 9.8 s window, 0 objects collected,
and the passes after it 0.27-0.44 s as the heap grows. A pass more or
less is 2-3% of such a window: the stalls are where a run's rate varies,
and each silences the raft leader's heartbeats for its length.

Policy, one for every server process: after a full pass, freeze what
survived it (`gc.freeze`: the permanent generation is not walked again),
so the passes that follow walk only what was made since (a few ms each).
Once the frozen objects are twice as many as the last pass over the whole
heap left, and that pass is at least a minute old, thaw them: the next
full pass walks everything once, which finds the cycles that formed among
old objects meanwhile, and freezes again. So the one long pause comes at
most once a minute, every object is walked a bounded number of times
while it lives, cycles wait for at most a doubling of the heap (or a
minute's growth), and reference counting frees everything acyclic at
once, as before.

The callback takes no lock and touches no registry: a collection can
start inside any allocation, also one made under a lock.
"""

from __future__ import annotations

import gc
import time

# what the passes cost, for whoever wants to print it (plain numbers,
# written only by the collector's callback, which is never re-entered)
STATS = {"full_passes": 0, "whole_heap_passes": 0, "full_pass_s": 0.0,
         "longest_s": 0.0}

WHOLE_HEAP_INTERVAL_S = 60.0

_state = {"installed": False, "whole_heap": 0, "whole_at": 0.0,
          "thawed": True, "t0": 0.0}


def _on_collection(phase: str, info: dict) -> None:
    if info["generation"] != 2:
        return
    if phase == "start":
        _state["t0"] = time.perf_counter()
        return
    now = time.perf_counter()
    took = now - _state["t0"]
    STATS["full_passes"] += 1
    STATS["full_pass_s"] += took
    STATS["longest_s"] = max(STATS["longest_s"], took)
    gc.freeze()
    frozen = gc.get_freeze_count()
    if _state["thawed"]:
        # this pass walked the whole heap
        STATS["whole_heap_passes"] += 1
        _state.update(whole_heap=frozen, whole_at=now, thawed=False)
    elif frozen >= 2 * _state["whole_heap"] \
            and now - _state["whole_at"] >= WHOLE_HEAP_INTERVAL_S:
        gc.unfreeze()
        _state["thawed"] = True


def install() -> None:
    """Idempotent; a process's first agent installs it."""
    if not _state["installed"]:
        _state["installed"] = True
        gc.callbacks.append(_on_collection)
