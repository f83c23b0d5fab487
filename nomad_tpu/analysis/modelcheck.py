"""nomadcheck dynamic prong: a deterministic interleaving model checker.

Where nomadsan (sanitizer.py) observes the ONE interleaving the OS
happens to schedule, nomadcheck OWNS the schedule: while a scenario
runs, ``threading.Thread``/``Lock``/``RLock``/``Condition``/``Event``/
``Timer`` are replaced with cooperative versions driven by one
scheduler, so exactly one thread executes at a time and every yield
point (lock acquire/release, cond wait/notify, thread start/join,
sleep) asks a seeded policy which thread runs next. The same seed
replays the same schedule bit-for-bit (loom/Shuttle style), so any
interleaving bug a sweep finds is a one-line repro.

Model
-----
- **Yield points**: lock acquire (before), lock release (after),
  notify (after), thread start (after), plus every blocking operation
  (cond wait, event wait, join, sleep). Code between yield points runs
  atomically — the model checks lock/condvar protocol races, not
  data-word tearing (nomadsan's lockset prong covers unlocked access).
- **Virtual clock**: ``time.time``/``monotonic`` return a virtual
  clock for managed threads (+1µs per scheduling step). Timed waits
  and timers fire ONLY when no thread is runnable (earliest virtual
  deadline first): timeouts "may happen eventually", never preempt
  real progress, and are deterministic.
- **Deadlock**: every live thread blocked with no timed waiter or
  pending timer to fire → reported with each thread's block site.
- **Livelock**: the schedule exceeds ``max_steps`` without the
  scenario finishing → reported with the trace tail.
- **Thread leaks**: tasks still alive when the scenario's main
  function returns → reported by name (shutdown-protocol bugs).
- **Schedule encoding**: the trace is ``["<step>:<thread>:<op>", ...]``
  — the full decision sequence. Replay = same seed + same policy;
  identical traces ⇒ identical outcomes.

Policies: ``random`` picks uniformly among runnable threads at every
yield point; ``pbound`` is preemption-bounded exploration (stay on the
running thread, spend a small budget of forced preemptions at
rng-chosen points) — the cheap way to hit the "K context switches"
bugs that uniform sampling dilutes.

Scenarios (``SCENARIOS``) drive REAL control-plane objects — RaftNode
with its log-writer/replicators, PlanApplier's proposer/reaper
pipeline, EvalBroker batch dequeue — and assert the chaos
``InvariantChecker`` safety properties plus scenario-local liveness.
``raft_commit`` optionally composes with the chaos FSFaults disk shim
(an EIO torn mid-schedule into a batch append). ``NOMAD_TPU_CHECK_SEED``
replays a sweep seed, mirroring ``NOMAD_TPU_CHAOS_SEED``.

Caveats: managed code must not block inside C (``queue.SimpleQueue``,
``ThreadPoolExecutor`` worker loops) — invisible to the scheduler.
Scenarios avoid those paths. Replay is guaranteed within a process;
across processes it additionally requires a fixed PYTHONHASHSEED if
the covered code iterates sets of strings (current scenarios do not).
"""

from __future__ import annotations

import _thread
import random
import threading
import time
import types
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

_REAL_TIME = time.time
_REAL_MONOTONIC = time.monotonic
_REAL_SLEEP = time.sleep
_REAL_THREAD = threading.Thread

# how long a parked OS thread waits for its grant before declaring the
# scheduler itself wedged (real seconds; a backstop for checker bugs,
# never hit by a correct run)
_GATE_STALL_S = 60.0

_ACTIVE: Optional["Scheduler"] = None


def current_scheduler() -> Optional["Scheduler"]:
    return _ACTIVE


class _Abort(BaseException):
    """Unwinds managed threads after a finding; BaseException so the
    code under test's ``except Exception`` handlers can't swallow it."""


class CheckFailure(Exception):
    """A scenario failed under some schedule (assertion, invariant
    violation, deadlock, livelock, or thread leak)."""


@dataclass
class CheckResult:
    scenario: str
    seed: int
    policy: str
    steps: int
    trace: List[str]
    error: Optional[str] = None          # rendered failure, or None
    error_type: str = ""                 # exception class name
    leaked: List[str] = field(default_factory=list)

    @property
    def ok(self) -> bool:
        return self.error is None

    def render(self) -> str:
        status = "ok" if self.ok else f"FAIL [{self.error_type}]"
        head = (f"{self.scenario} seed={self.seed} policy={self.policy} "
                f"steps={self.steps}: {status}")
        if self.ok:
            return head
        tail = " | ".join(self.trace[-8:])
        return f"{head}\n  {self.error}\n  trace tail: {tail}"


class _Task:
    __slots__ = ("tid", "name", "gate", "state", "block_kind",
                 "block_obj", "wake_reason", "deadline", "thread",
                 "abort_granted")

    def __init__(self, tid: int, name: str, thread=None):
        self.tid = tid
        self.name = name
        self.gate = _thread.allocate_lock()
        self.gate.acquire()              # parked until granted
        self.state = "runnable"          # runnable|running|blocked|finished
        self.block_kind = ""
        self.block_obj = None
        self.wake_reason = ""
        self.deadline: Optional[float] = None
        self.thread = thread
        self.abort_granted = False


class DeadlockError(CheckFailure):
    pass


class LivelockError(CheckFailure):
    pass


class ThreadLeakError(CheckFailure):
    pass


# --------------------------------------------------------------------
# schedule policies
# --------------------------------------------------------------------

class RandomPolicy:
    name = "random"

    def __init__(self, seed: int):
        self.rng = random.Random(seed)

    def pick(self, sch: "Scheduler", choices: List[_Task]) -> _Task:
        return choices[self.rng.randrange(len(choices))]


class PreemptionBoundedPolicy:
    """Run the current thread until it blocks, spending a small budget
    of forced preemptions at rng-chosen yield points."""

    name = "pbound"

    def __init__(self, seed: int, budget: int = 3, rate: float = 0.1):
        self.rng = random.Random(seed)
        self.budget = budget
        self.rate = rate

    def pick(self, sch: "Scheduler", choices: List[_Task]) -> _Task:
        cur = sch.current
        if cur in choices:
            others = [c for c in choices if c is not cur]
            if (others and self.budget > 0
                    and self.rng.random() < self.rate):
                self.budget -= 1
                return others[self.rng.randrange(len(others))]
            return cur
        return choices[self.rng.randrange(len(choices))]


POLICIES: Dict[str, Callable[[int], object]] = {
    "random": RandomPolicy,
    "pbound": PreemptionBoundedPolicy,
}


# --------------------------------------------------------------------
# the scheduler
# --------------------------------------------------------------------

class Scheduler:
    def __init__(self, policy, max_steps: int = 50_000):
        self.policy = policy
        self.max_steps = max_steps
        self.tasks: Dict[int, _Task] = {}
        self.idents: Dict[int, _Task] = {}   # OS thread ident -> task
        self.current: Optional[_Task] = None
        self.step = 0
        self.trace: List[str] = []
        self.vclock = 1_700_000_000.0        # arbitrary fixed epoch
        self.timers: List["MCTimer"] = []
        self.aborting = False
        self.error: Optional[BaseException] = None
        self._next_tid = 0
        self._abort_mu = _thread.allocate_lock()

    # -- registration --------------------------------------------------

    def register_main(self) -> _Task:
        task = self._new_task("main")
        task.state = "running"
        self.current = task
        self.idents[threading.get_ident()] = task
        return task

    def _new_task(self, name: str, thread=None) -> _Task:
        tid = self._next_tid
        self._next_tid += 1
        # keep names unique but readable: append tid only on collision
        if any(t.name == name for t in self.tasks.values()):
            name = f"{name}#{tid}"
        task = _Task(tid, name, thread)
        self.tasks[tid] = task
        return task

    def me(self) -> Optional[_Task]:
        return self.idents.get(threading.get_ident())

    def alive_named(self, prefix: str) -> int:
        return sum(1 for t in self.tasks.values()
                   if t.state != "finished" and t.name.startswith(prefix))

    # -- scheduling core ----------------------------------------------

    def _sorted_runnable(self) -> List[_Task]:
        return [t for t in sorted(self.tasks.values(),
                                  key=lambda t: t.tid)
                if t.state == "runnable"]

    def _record(self, task: _Task, op: str) -> None:
        self.step += 1
        self.vclock += 1e-6
        self.trace.append(f"{self.step}:{task.name}:{op}")
        if self.step > self.max_steps:
            self._begin_abort(LivelockError(
                f"no completion after {self.max_steps} steps "
                f"(livelock or runaway loop)"))
            raise _Abort()

    def switch(self, op: str) -> None:
        """Yield point for a RUNNING task: optionally hand off."""
        me = self.me()
        if me is None or me is not self.current or me.state != "running":
            return
        if self.aborting:
            raise _Abort()
        choices = [me] + [t for t in self._sorted_runnable()
                          if t is not me]
        choices.sort(key=lambda t: t.tid)
        nxt = self.policy.pick(self, choices)
        self._record(nxt, op)
        if nxt is me:
            return
        me.state = "runnable"
        nxt.state = "running"
        self.current = nxt
        nxt.gate.release()
        self._park(me)

    def block(self, kind: str, obj, timeout: Optional[float] = None
              ) -> str:
        """Block the running task; returns 'signal' or 'timeout'."""
        me = self.me()
        if me is None:
            raise RuntimeError(
                "unmanaged thread hit a model-checked blocking op")
        if self.aborting:
            raise _Abort()
        me.state = "blocked"
        me.block_kind = kind
        me.block_obj = obj
        me.wake_reason = ""
        me.deadline = (None if timeout is None
                       else self.vclock + max(timeout, 0.0))
        self._grant_next(f"block:{kind}")
        self._park(me)
        me.deadline = None
        me.block_kind = ""
        me.block_obj = None
        return me.wake_reason or "signal"

    def wake(self, task: _Task, reason: str = "signal") -> None:
        """Make a blocked task runnable (does NOT transfer control)."""
        if task.state == "blocked":
            task.state = "runnable"
            task.wake_reason = reason

    def wake_waiters(self, kind: str, obj) -> None:
        for t in self.tasks.values():
            if (t.state == "blocked" and t.block_kind == kind
                    and t.block_obj is obj):
                self.wake(t)

    def _park(self, me: _Task) -> None:
        if not me.gate.acquire(timeout=_GATE_STALL_S):
            self._begin_abort(CheckFailure(
                f"scheduler stalled: task {me.name} never granted"))
            raise _Abort()
        if self.aborting:
            raise _Abort()
        # granter already set our state/current

    def _grant_next(self, op: str) -> None:
        """Hand control to some runnable task; fire virtual deadlines
        when idle; detect deadlock. Runs on the ceding thread."""
        while True:
            runnable = self._sorted_runnable()
            if runnable:
                nxt = self.policy.pick(self, runnable)
                self._record(nxt, op)
                nxt.state = "running"
                self.current = nxt
                nxt.gate.release()
                return
            # idle: earliest virtual deadline fires (timed waiter or
            # timer); timeouts never preempt runnable threads
            cands = []
            for t in self.tasks.values():
                if t.state == "blocked" and t.deadline is not None:
                    cands.append((t.deadline, 0, t.tid, t))
            for tm in self.timers:
                cands.append((tm.mc_deadline, 1, tm.mc_seq, tm))
            if not cands:
                blocked = [f"{t.name}@{t.block_kind}"
                           for t in self.tasks.values()
                           if t.state == "blocked"]
                self._begin_abort(DeadlockError(
                    "deadlock: all live threads blocked "
                    f"({', '.join(sorted(blocked)) or 'none'}) with no "
                    "timed waiter or pending timer"))
                raise _Abort()
            cands.sort(key=lambda c: c[:3])
            deadline, kind, _seq, obj = cands[0]
            self.vclock = max(self.vclock, deadline)
            if kind == 0:
                obj.state = "runnable"
                obj.wake_reason = "timeout"
            else:
                self.timers.remove(obj)
                obj._mc_fire()           # registers a runnable task
            # loop: grant whoever is now runnable

    def on_thread_exit(self, task: _Task) -> None:
        task.state = "finished"
        if self.aborting:
            self._abort_release_all()
            return
        # wake joiners
        self.wake_waiters("join", task)
        if any(t.state != "finished" for t in self.tasks.values()):
            try:
                self._grant_next("exit")
            except _Abort:
                pass

    # -- failure handling ---------------------------------------------

    def _begin_abort(self, exc: BaseException) -> None:
        with self._abort_mu:
            if self.error is None:
                self.error = exc
            self.aborting = True
        # wake every parked task NOW so nobody waits out the gate
        # stall timeout; they observe `aborting` and unwind via _Abort
        self._abort_release_all()

    def record_error(self, exc: BaseException) -> None:
        self._begin_abort(exc)

    def _abort_release_all(self) -> None:
        me = self.me()
        with self._abort_mu:
            victims = [t for t in self.tasks.values()
                       if t.state != "finished" and not t.abort_granted
                       and t is not me]
            for t in victims:
                t.abort_granted = True
        for t in victims:
            t.gate.release()

    def finalize_abort(self) -> None:
        """Driver-side cleanup: release every parked task so it unwinds
        via _Abort, then join the real threads."""
        self._abort_release_all()
        deadline = _REAL_TIME() + 10.0
        for t in self.tasks.values():
            if t.thread is not None and t.state != "finished":
                t.thread.join(timeout=max(0.1, deadline - _REAL_TIME()))


# --------------------------------------------------------------------
# cooperative primitives
# --------------------------------------------------------------------

_NAME_SEQ = [0]


def _mc_name(prefix: str) -> str:
    _NAME_SEQ[0] += 1
    return f"{prefix}{_NAME_SEQ[0]}"


def _sch_task():
    sch = _ACTIVE
    if sch is None:
        return None, None
    return sch, sch.me()


class MCLock:
    _reentrant = False

    def __init__(self):
        self._mc_name = _mc_name("L")
        self.owner: Optional[_Task] = None
        self.count = 0
        self._fallback = _thread.allocate_lock()   # unmanaged callers

    def acquire(self, blocking: bool = True, timeout: float = -1) -> bool:
        sch, me = _sch_task()
        if sch is None or me is None:
            if timeout is not None and timeout >= 0:
                return self._fallback.acquire(blocking, timeout)
            return self._fallback.acquire(blocking)
        if sch.aborting:
            return True
        sch.switch(f"acq:{self._mc_name}")
        if self.owner is me:
            if self._reentrant:
                self.count += 1
                return True
            raise RuntimeError(
                f"non-reentrant lock {self._mc_name} re-acquired")
        deadline = (None if timeout is None or timeout < 0
                    else sch.vclock + timeout)
        while self.owner is not None:
            if not blocking:
                return False
            remaining = (None if deadline is None
                         else deadline - sch.vclock)
            if remaining is not None and remaining <= 0:
                return False
            reason = sch.block("lock", self, remaining)
            if reason == "timeout" and self.owner is not None:
                return False
        self.owner = me
        self.count = 1
        return True

    def release(self) -> None:
        sch, me = _sch_task()
        if sch is None or me is None:
            try:
                self._fallback.release()
            except RuntimeError:
                pass
            return
        if sch.aborting:
            return
        if self.owner is not me:
            raise RuntimeError(f"release of un-owned {self._mc_name}")
        self.count -= 1
        if self.count > 0:
            return
        self.owner = None
        sch.wake_waiters("lock", self)
        sch.switch(f"rel:{self._mc_name}")

    def locked(self) -> bool:
        return self.owner is not None

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, *exc):
        self.release()
        return False

    # condvar support: fully release / restore (RLock depth)
    def _mc_release_save(self, me: _Task) -> int:
        saved = self.count
        self.count = 0
        self.owner = None
        sch = _ACTIVE
        if sch is not None:
            sch.wake_waiters("lock", self)
        return saved

    def _mc_acquire_restore(self, saved: int) -> None:
        self.acquire()
        self.count = saved


class MCRLock(MCLock):
    _reentrant = True


class MCCondition:
    def __init__(self, lock=None):
        self._mc_name = _mc_name("C")
        self._lock = lock if lock is not None else MCRLock()
        self.waiters: List[_Task] = []

    def __enter__(self):
        self._lock.acquire()
        return self

    def __exit__(self, *exc):
        self._lock.release()
        return False

    def acquire(self, *a, **kw):
        return self._lock.acquire(*a, **kw)

    def release(self):
        return self._lock.release()

    def _check_owned(self, sch, me) -> bool:
        owner = getattr(self._lock, "owner", None)
        if owner is not me:
            if sch.aborting:
                return False
            raise RuntimeError(
                f"condvar {self._mc_name} op without its lock held")
        return True

    def wait(self, timeout: Optional[float] = None) -> bool:
        sch, me = _sch_task()
        if sch is None or me is None:
            raise RuntimeError(
                "unmanaged thread waited on a model-checked condvar")
        if sch.aborting:
            raise _Abort()
        if not self._check_owned(sch, me):
            return False
        self.waiters.append(me)
        saved = self._lock._mc_release_save(me)
        try:
            reason = sch.block("cond", self, timeout)
        finally:
            if me in self.waiters:
                self.waiters.remove(me)
        self._lock._mc_acquire_restore(saved)
        return reason == "signal"

    def wait_for(self, predicate, timeout: Optional[float] = None):
        sch = _ACTIVE
        endtime = None
        if timeout is not None and sch is not None:
            endtime = sch.vclock + timeout
        result = predicate()
        while not result:
            waittime = None
            if endtime is not None and sch is not None:
                waittime = endtime - sch.vclock
                if waittime <= 0:
                    break
            self.wait(waittime)
            result = predicate()
        return result

    def notify(self, n: int = 1) -> None:
        sch, me = _sch_task()
        if sch is None or me is None or sch.aborting:
            return
        if not self._check_owned(sch, me):
            return
        woken = self.waiters[:n]
        del self.waiters[:n]
        for t in woken:
            sch.wake(t)                  # they re-contend for the lock
        sch.switch(f"notify:{self._mc_name}")

    def notify_all(self) -> None:
        self.notify(len(self.waiters))


class MCEvent:
    def __init__(self):
        self._cond = MCCondition(MCLock())
        self._flag = False

    def is_set(self) -> bool:
        return self._flag

    def set(self) -> None:
        sch, me = _sch_task()
        if sch is None or me is None:
            self._flag = True
            return
        with self._cond:
            self._flag = True
            self._cond.notify_all()

    def clear(self) -> None:
        self._flag = False

    def wait(self, timeout: Optional[float] = None) -> bool:
        sch, me = _sch_task()
        if sch is None or me is None:
            deadline = (None if timeout is None
                        else _REAL_MONOTONIC() + timeout)
            while not self._flag:
                if deadline is not None and _REAL_MONOTONIC() >= deadline:
                    break
                _REAL_SLEEP(0.005)
            return self._flag
        deadline = (None if timeout is None
                    else sch.vclock + max(timeout, 0.0))
        with self._cond:
            while not self._flag:
                remaining = (None if deadline is None
                             else deadline - sch.vclock)
                if remaining is not None and remaining <= 0:
                    break
                self._cond.wait(remaining)
            return self._flag


class _StartedStub:
    """Replaces Thread._started under the checker: the real bootstrap
    sets it from UNMANAGED code at an uncontrolled real-time point, and
    Thread.start() blocks on it — a nondeterministic handoff. Under the
    checker the child's first user instruction is gated by the task
    gate instead, so start() must never wait on the bootstrap."""

    def __init__(self):
        self._flag = False

    def set(self) -> None:
        self._flag = True

    def is_set(self) -> bool:
        return self._flag

    def wait(self, timeout=None) -> bool:
        return True                      # never block on the bootstrap


class MCThread(_REAL_THREAD):
    def start(self) -> None:
        sch = _ACTIVE
        if sch is None:
            _REAL_THREAD.start(self)
            return
        me = sch.me()
        if me is None:
            _REAL_THREAD.start(self)
            return
        self._mc_task = sch._new_task(self.name or "thread", self)
        self._mc_sch = sch    # the OS thread may first run after the
        self._started = _StartedStub()          # type: ignore
        _REAL_THREAD.start(self)              # window closed (leaks)
        sch.switch(f"start:{self._mc_task.name}")

    def run(self) -> None:
        task = getattr(self, "_mc_task", None)
        if task is None:
            _REAL_THREAD.run(self)
            return
        sch = self._mc_sch
        sch.idents[threading.get_ident()] = task
        try:
            if not task.gate.acquire(timeout=_GATE_STALL_S):
                return
            if sch.aborting:
                return
            try:
                _REAL_THREAD.run(self)
            except _Abort:
                pass
            except BaseException as e:   # a finding: surface it
                sch.record_error(e)
        finally:
            sch.idents.pop(threading.get_ident(), None)
            sch.on_thread_exit(task)

    def join(self, timeout: Optional[float] = None) -> None:
        task = getattr(self, "_mc_task", None)
        sch = _ACTIVE
        if task is None or sch is None or sch.me() is None:
            _REAL_THREAD.join(self, timeout)
            return
        if sch.aborting:
            return
        deadline = (None if timeout is None
                    else sch.vclock + max(timeout, 0.0))
        while task.state != "finished":
            remaining = (None if deadline is None
                         else deadline - sch.vclock)
            if remaining is not None and remaining <= 0:
                return
            reason = sch.block("join", task, remaining)
            if reason == "timeout":
                return

    def is_alive(self) -> bool:
        task = getattr(self, "_mc_task", None)
        if task is None:
            return _REAL_THREAD.is_alive(self)
        return task.state != "finished"


class MCTimer:
    """threading.Timer stand-in with NO OS thread while pending: the
    scheduler fires it (spawning a managed thread) when the system is
    idle and its virtual deadline is earliest."""

    _seq = [0]

    def __init__(self, interval, function, args=None, kwargs=None):
        self.interval = interval
        self.function = function
        self.args = args if args is not None else []
        self.kwargs = kwargs if kwargs is not None else {}
        self.daemon = True
        self.name = _mc_name("timer-")
        self.mc_deadline = 0.0
        MCTimer._seq[0] += 1
        self.mc_seq = MCTimer._seq[0]
        self._cancelled = False
        self._thread: Optional[MCThread] = None

    def start(self) -> None:
        sch = _ACTIVE
        if sch is None or sch.me() is None:
            t = _REAL_THREAD(target=self._real_fire, daemon=True)
            self._thread = t             # degraded mode, off-scenario
            t.start()
            return
        self.mc_deadline = sch.vclock + max(self.interval, 0.0)
        sch.timers.append(self)

    def _real_fire(self):
        _REAL_SLEEP(self.interval)
        if not self._cancelled:
            self.function(*self.args, **self.kwargs)

    def _mc_fire(self) -> None:
        if self._cancelled:
            return
        t = MCThread(target=self.function, args=self.args,
                     kwargs=self.kwargs, name=self.name, daemon=True)
        self._thread = t
        t.start()

    def cancel(self) -> None:
        self._cancelled = True
        sch = _ACTIVE
        if sch is not None and self in sch.timers:
            sch.timers.remove(self)

    def is_alive(self) -> bool:
        sch = _ACTIVE
        if sch is not None and self in sch.timers:
            return True
        return self._thread is not None and self._thread.is_alive()

    def join(self, timeout=None) -> None:
        if self._thread is not None:
            self._thread.join(timeout)


# --------------------------------------------------------------------
# the patch window
# --------------------------------------------------------------------

def _mc_time() -> float:
    sch, me = _sch_task()
    if sch is None or me is None:
        return _REAL_TIME()
    return sch.vclock


def _mc_monotonic() -> float:
    sch, me = _sch_task()
    if sch is None or me is None:
        return _REAL_MONOTONIC()
    return sch.vclock


def _mc_sleep(seconds: float) -> None:
    sch, me = _sch_task()
    if sch is None or me is None:
        _REAL_SLEEP(seconds)
        return
    sch.block("sleep", None, max(seconds, 0.0))


class _PatchWindow:
    """Swap the threading/time primitives for their cooperative
    versions, suspend the nomadsan runtime (its TLS locksets don't see
    MC locks and would report false violations), seed the global PRNG
    (RaftNode election jitter consults it), and restore EVERYTHING on
    exit — including whatever factories nomadsan had installed."""

    def __init__(self, scheduler: Scheduler, seed: int):
        self.scheduler = scheduler
        self.seed = seed
        self._saved: dict = {}
        self._san_active = False
        self._rng_state = None

    def __enter__(self):
        global _ACTIVE
        if _ACTIVE is not None:
            raise RuntimeError("model-check scenarios cannot nest")
        self._saved = {
            "Thread": threading.Thread, "Timer": threading.Timer,
            "Lock": threading.Lock, "RLock": threading.RLock,
            "Condition": threading.Condition, "Event": threading.Event,
            "time": time.time, "monotonic": time.monotonic,
            "sleep": time.sleep,
        }
        from . import sanitizer
        self._san_active = sanitizer.GLOBAL.active
        sanitizer.GLOBAL.active = False
        self._rng_state = random.getstate()
        random.seed(0x6D6F6463 ^ self.seed)
        threading.Thread = MCThread                 # type: ignore
        threading.Timer = MCTimer                   # type: ignore
        threading.Lock = MCLock                     # type: ignore
        threading.RLock = MCRLock                   # type: ignore
        threading.Condition = MCCondition           # type: ignore
        threading.Event = MCEvent                   # type: ignore
        time.time = _mc_time                        # type: ignore
        time.monotonic = _mc_monotonic              # type: ignore
        time.sleep = _mc_sleep                      # type: ignore
        _ACTIVE = self.scheduler
        return self

    def __exit__(self, *exc):
        global _ACTIVE
        _ACTIVE = None
        threading.Thread = self._saved["Thread"]    # type: ignore
        threading.Timer = self._saved["Timer"]      # type: ignore
        threading.Lock = self._saved["Lock"]        # type: ignore
        threading.RLock = self._saved["RLock"]      # type: ignore
        threading.Condition = self._saved["Condition"]  # type: ignore
        threading.Event = self._saved["Event"]      # type: ignore
        time.time = self._saved["time"]             # type: ignore
        time.monotonic = self._saved["monotonic"]   # type: ignore
        time.sleep = self._saved["sleep"]           # type: ignore
        from . import sanitizer
        sanitizer.GLOBAL.active = self._san_active
        random.setstate(self._rng_state)
        return False


# --------------------------------------------------------------------
# scenario driver
# --------------------------------------------------------------------

@dataclass
class ScenarioEnv:
    seed: int
    fsfaults: bool = False


SCENARIOS: Dict[str, Callable[[ScenarioEnv], None]] = {}


def scenario(name: str):
    def register(fn):
        SCENARIOS[name] = fn
        return fn
    return register


_JAX_COMPILE_PATH_WARM = False


def _preload() -> None:
    """Import every module the scenarios touch BEFORE the patch window:
    module-level locks (logging, concurrent.futures internals) must be
    real OS primitives, and lazy imports inside the window would see
    the patched threading module."""
    import concurrent.futures
    import concurrent.futures.thread  # noqa: F401  (lazy in 3.8+)
    import queue  # noqa: F401
    import tempfile  # noqa: F401

    from ..chaos import fsfaults, invariants  # noqa: F401
    from ..core import broker, events, heartbeat, loadctl, metrics, plan_apply  # noqa: F401
    from ..utils import backoff  # noqa: F401
    from ..obs import trace  # noqa: F401
    from ..raft import durable, fsm, node, transport  # noqa: F401
    from ..state import persist, store, watch  # noqa: F401
    from ..structs import alloc, evaluation, node  # noqa: F401
    from ..tensor import jit_guard, placer  # noqa: F401  (module locks)
    from . import launch_ledger, ownership, shadow  # noqa: F401

    # jax imports big chunks of its compile path lazily on the FIRST
    # compile (jax._src.compilation_cache among them, whose module-level
    # _cache_initialized_mutex would otherwise be born inside the patch
    # window as a cooperative lock and deadlock against XLA's own C++
    # compile serialization). One throwaway compile here forces every
    # lazy import and lock on that path into existence as real OS
    # primitives; per-process, so repeat runs pay nothing.
    global _JAX_COMPILE_PATH_WARM
    if not _JAX_COMPILE_PATH_WARM:
        import jax
        import numpy as np

        from jax._src import compilation_cache  # noqa: F401
        jax.jit(lambda a: a + 0.0)(np.float32(0.0)).block_until_ready()
        _JAX_COMPILE_PATH_WARM = True
    assert concurrent.futures.ThreadPoolExecutor is not None


def run_scenario(name: str, seed: int, policy: str = "random",
                 max_steps: int = 50_000,
                 fsfaults: bool = False) -> CheckResult:
    """One scenario under one seeded schedule. Deterministic: the same
    (name, seed, policy) triple replays the same trace and outcome."""
    _preload()
    fn = SCENARIOS.get(name)
    if fn is None:
        raise KeyError(f"unknown scenario {name!r}; "
                       f"have {sorted(SCENARIOS)}")
    pol = POLICIES[policy](seed)
    _NAME_SEQ[0] = 0                     # trace names restart per run
    MCTimer._seq[0] = 0
    from ..obs import RECORDER
    RECORDER.clear()                     # per-run forensics isolation
    sch = Scheduler(pol, max_steps=max_steps)
    env = ScenarioEnv(seed=seed, fsfaults=fsfaults)
    leaked: List[str] = []
    with _PatchWindow(sch, seed):
        main = sch.register_main()
        try:
            fn(env)
            live = [t.name for t in sch.tasks.values()
                    if t is not main and t.state != "finished"]
            if live:
                leaked = sorted(live)
                raise ThreadLeakError(
                    f"threads still alive at scenario end: {leaked}")
        except _Abort:
            pass
        except BaseException as e:
            sch.record_error(e)
        finally:
            main.state = "finished"
            sch.finalize_abort()
    err = sch.error
    error = None
    if err is not None:
        error = f"{err}"
        # attach the flight recorder to the finding: the subsystem
        # transitions leading up to the failure, under this exact
        # deterministic schedule
        dump = RECORDER.dump_text(last=40)
        if dump:
            error += "\n  flight recorder (last 40 events):\n" + dump
    return CheckResult(
        scenario=name, seed=seed, policy=pol.name, steps=sch.step,
        trace=sch.trace, leaked=leaked,
        error=error,
        error_type="" if err is None else type(err).__name__)


def explore(name: str, seeds, policies=("random", "pbound"),
            max_steps: int = 50_000, fsfaults: bool = False,
            stop_on_failure: bool = True) -> List[CheckResult]:
    """Sweep a scenario over seeds × policies; returns every result
    (failures first if stop_on_failure ended the sweep early)."""
    results: List[CheckResult] = []
    for s in seeds:
        for p in policies:
            r = run_scenario(name, s, policy=p, max_steps=max_steps,
                             fsfaults=fsfaults)
            results.append(r)
            if not r.ok and stop_on_failure:
                return results
    return results


def seed_from_env(default: int = 0) -> int:
    import os
    raw = os.environ.get("NOMAD_TPU_CHECK_SEED", "")
    if raw:
        try:
            return int(raw, 0)
        except ValueError:
            pass
    return default


# --------------------------------------------------------------------
# scenarios
# --------------------------------------------------------------------

class _FakeServer:
    """Just enough server for chaos.InvariantChecker's raft checks."""

    def __init__(self, raft):
        self.id = raft.id
        self.raft = raft
        self.crashed = False


class _FakeCluster:
    def __init__(self, nodes):
        self.servers = {n.id: _FakeServer(n) for n in nodes}


def _force_leader(node, term: int = 1) -> None:
    with node._lock:
        node.current_term = term
        node._become_leader_locked()


@scenario("raft_commit")
def _scenario_raft_commit(env: ScenarioEnv) -> None:
    """A 3-node raft cluster (log-writer + per-peer replicators on the
    leader) commits two proposers' batches; chaos invariants hold on
    every schedule. With env.fsfaults, one EIO is torn into a durable
    batch append mid-schedule (the chaos FSFaults shim): the poisoned
    batch must fail loudly and every invariant still hold."""
    import contextlib
    import errno as _errno
    import os
    import shutil
    import tempfile

    from ..chaos.fsfaults import FSFaults
    from ..chaos.invariants import InvariantChecker
    from ..raft.durable import DurableLog
    from ..raft.node import NotLeaderError, RaftNode
    from ..raft.transport import InProcTransport

    tmp = tempfile.mkdtemp(prefix="nomadcheck-") if env.fsfaults else None
    transport = InProcTransport()
    applied = {nid: [] for nid in ("a", "b", "c")}
    nodes = []
    try:
        for nid in ("a", "b", "c"):
            log = None
            if tmp:
                os.makedirs(f"{tmp}/{nid}", exist_ok=True)
                log = DurableLog(f"{tmp}/{nid}", fsync=False)
            nodes.append(RaftNode(
                nid, [p for p in ("a", "b", "c") if p != nid],
                transport, applied[nid].append,
                election_timeout=1e6,      # no spontaneous elections
                heartbeat_interval=0.05, log=log))
        for n in nodes:
            n.start()
        _force_leader(nodes[0])
        shim = FSFaults() if env.fsfaults else None
        ctx = shim.installed() if shim else contextlib.nullcontext()
        with ctx:
            if shim:
                # torn batch append mid-schedule: the first durable
                # batch append on the leader dies with EIO
                shim.arm("log_append", errno_=_errno.EIO, count=1,
                         path_substr="/a/")
            errors: List[str] = []

            def propose(tag: str) -> None:
                for i in range(3):
                    try:
                        prop = nodes[0].apply_async((f"{tag}{i}",))
                        nodes[0].apply_wait(prop, timeout=30.0)
                    except (OSError, NotLeaderError, TimeoutError) as e:
                        if shim is None:
                            errors.append(f"{tag}{i}: {e!r}")

            t1 = threading.Thread(target=propose, args=("x",),
                                  name="proposer-x")
            t2 = threading.Thread(target=propose, args=("y",),
                                  name="proposer-y")
            t1.start()
            t2.start()
            t1.join()
            t2.join()
            if errors:
                raise AssertionError(
                    f"fault-free proposals failed: {errors}")
        checker = InvariantChecker()
        cluster = _FakeCluster(nodes)
        checker.check_election_safety(cluster)
        checker.check_log_matching(cluster)
        checker.check_committed_durability(cluster)
        if not env.fsfaults and nodes[0].commit_index < 6:
            raise AssertionError(
                f"leader committed {nodes[0].commit_index} < 6")
    finally:
        for n in nodes:
            n.stop()
        transport.close()
        if tmp:
            shutil.rmtree(tmp, ignore_errors=True)


@scenario("raft_stepdown")
def _scenario_raft_stepdown(env: ScenarioEnv) -> None:
    """change_config waits for a commit that can never happen (both
    peers unreachable) while a higher-term append_entries steps the
    leader down: the waiter must fail promptly with NotLeaderError —
    not burn its whole timeout (the change_config fix this PR)."""
    from ..raft.node import NotLeaderError, RaftNode
    from ..raft.transport import InProcTransport

    transport = InProcTransport()
    node = RaftNode("a", ["b", "c"], transport, lambda cmd: None,
                    election_timeout=1e6, heartbeat_interval=0.05)
    transport.partition("b")       # peers exist but never answer
    transport.partition("c")
    node.start()
    try:
        _force_leader(node)
        outcome: List[str] = []

        def change() -> None:
            try:
                node.add_server("d", timeout=30.0)
                outcome.append("committed")
            except NotLeaderError:
                outcome.append("not-leader")
            except TimeoutError:
                outcome.append("timeout")

        t = threading.Thread(target=change, name="config-changer")
        t.start()
        time.sleep(0.2)            # virtual: let the change register
        node.handle({"kind": "append_entries", "term": 9, "leader": "b",
                     "prev_log_index": 0, "prev_log_term": 0,
                     "entries": [], "leader_commit": 0})
        t.join()
        if outcome != ["not-leader"]:
            raise AssertionError(
                "config change through a step-down must fail fast with "
                f"NotLeaderError; got {outcome}")
    finally:
        node.stop()
        transport.close()


@scenario("read_index")
def _scenario_read_index(env: ScenarioEnv) -> None:
    """Read-path safety under adversarial schedules (the follower-read
    PR). Two independent hazards in one scenario:

    (1) Lease safety — a deposed leader holding a (lapsed) lease must
    never serve a read index: after the old leader is partitioned and a
    newer leader commits a write, read_index() on the old leader must
    raise NotLeaderError (its lease expired, its confirmation round
    cannot reach a quorum). Returning an index there would let a client
    read state that misses the new leader's committed write.

    (2) Waiter-table race — a blocking query whose deadline fires in
    the same window as the commit that satisfies it must either wake
    with the committed index or time out cleanly; the parked entry must
    never be lost or leak (WatchTable settles the race under its lock).
    """
    from ..raft.node import NotLeaderError, RaftNode
    from ..raft.transport import InProcTransport
    from ..state.store import StateStore

    # -- (1) lease safety across a silent deposition --
    transport = InProcTransport()
    nodes = {}
    for nid in ("a", "b", "c"):
        nodes[nid] = RaftNode(
            nid, [p for p in ("a", "b", "c") if p != nid],
            transport, lambda cmd: None,
            election_timeout=1e6,      # no spontaneous elections
            heartbeat_interval=0.05,
            lease_duration=0.01)       # lapses within one sleep below
    try:
        for n in nodes.values():
            n.start()
        _force_leader(nodes["a"])
        # a quorum-committed write under A (also commits A's barrier)
        prop = nodes["a"].apply_async(("w1",))
        nodes["a"].apply_wait(prop, timeout=30.0)
        idx1 = nodes["a"].read_index(timeout=5.0)
        if idx1 < 1:
            raise AssertionError(f"connected leader read index {idx1}")
        # cut A off; let any held lease lapse, then depose it silently
        transport.partition("a")
        time.sleep(0.2)
        _force_leader(nodes["b"], term=2)
        prop = nodes["b"].apply_async(("w2",))
        nodes["b"].apply_wait(prop, timeout=30.0)  # b+c quorum commits
        try:
            stale = nodes["a"].read_index(timeout=0.5)
        except (NotLeaderError, TimeoutError):
            stale = None
        if stale is not None:
            raise AssertionError(
                f"deposed leader served read index {stale} while the new "
                f"leader committed through {nodes['b'].commit_index}")
    finally:
        for n in nodes.values():
            n.stop()
        transport.close()

    # -- (2) waiter-table commit/deadline race --
    store = StateStore()
    results: List[tuple] = []

    def waiter() -> None:
        results.append(store.watches.wait_min_index(1, timeout=0.05))

    def committer() -> None:
        time.sleep(0.05)           # lands right on the waiter deadline
        with store._write_lock:
            gen, _ = store._begin()
            store._commit(gen, [])

    t1 = threading.Thread(target=waiter, name="block-waiter")
    t2 = threading.Thread(target=committer, name="committer")
    t1.start()
    t2.start()
    t1.join()
    t2.join()
    idx, wake_ts = results[0]
    if wake_ts is not None and idx < 1:
        raise AssertionError(
            f"woken waiter observed index {idx} below its threshold")
    if idx not in (0, 1):
        raise AssertionError(f"impossible observed index {idx}")
    if store.watches.parked() != 0:
        raise AssertionError(
            f"waiter leaked: parked={store.watches.parked()}")
    # liveness after the race: a fresh waiter still wakes
    results.clear()
    t3 = threading.Thread(target=lambda: results.append(
        store.watches.wait_min_index(2, timeout=10.0)), name="waiter-2")
    t3.start()
    time.sleep(0.05)
    with store._write_lock:
        gen, _ = store._begin()
        store._commit(gen, [])
    t3.join()
    if results[0][0] < 2:
        raise AssertionError(f"post-race waiter saw {results[0]}")


@scenario("snapshot_compact")
def _scenario_snapshot_compact(env: ScenarioEnv) -> None:
    """Off-lock snapshot capture interleaved with concurrent applies
    and an incoming chunked install_snapshot. A partitioned follower
    forces the leader's async snapshot worker to compact past the
    follower's next index; on heal the leader streams a chunked
    install while proposals keep committing, and the freshly installed
    follower then runs its own off-lock capture. Invariants checked on
    every save/compact under the schedule: a locally captured
    snapshot's index never exceeds the node's last_applied at save
    time, and the log base never passes an index no saved snapshot
    covers."""
    import os
    import shutil
    import tempfile

    from ..chaos.invariants import InvariantChecker
    from ..raft.durable import DurableLog, SnapshotStore
    from ..raft.node import NotLeaderError, RaftNode
    from ..raft.transport import InProcTransport

    tmp = tempfile.mkdtemp(prefix="nomadcheck-snap-")
    transport = InProcTransport()
    violations: List[str] = []
    applied = {nid: [] for nid in ("a", "b", "c")}
    nodes: list = []

    class AuditSnapshots(SnapshotStore):
        """only_if_newer=True is unique to the async capture worker, so
        gate the capture invariant on it (installs legitimately save an
        index ABOVE last_applied — disk before memory)."""

        def __init__(self, dir_path):
            super().__init__(dir_path)
            self.node = None

        def _save_text(self, index, text, only_if_newer):
            if (only_if_newer and self.node is not None
                    and index > self.node.last_applied):
                violations.append(
                    f"{self.node.id}: captured snapshot index {index} > "
                    f"last_applied {self.node.last_applied}")
            return super()._save_text(index, text, only_if_newer)

    class AuditLog(DurableLog):
        def __init__(self, dir_path, snaps):
            super().__init__(dir_path, fsync=False)
            self._snaps = snaps

        def _audit_base(self, what):
            if self.base_index > max(self._snaps.last_index, 0):
                violations.append(
                    f"{what}: log base {self.base_index} > snapshot "
                    f"index {self._snaps.last_index}")

        def compact(self, upto_index, upto_term):
            super().compact(upto_index, upto_term)
            self._audit_base("compact")

        def reset_to(self, index, term):
            super().reset_to(index, term)
            self._audit_base("reset_to")

    try:
        for nid in ("a", "b", "c"):
            os.makedirs(f"{tmp}/{nid}", exist_ok=True)
            snaps = AuditSnapshots(f"{tmp}/{nid}")
            alog = AuditLog(f"{tmp}/{nid}", snaps)
            lst = applied[nid]
            n = RaftNode(
                nid, [p for p in ("a", "b", "c") if p != nid],
                transport, lst.append,
                election_timeout=1e6,      # no spontaneous elections
                heartbeat_interval=0.05, log=alog, snapshots=snaps,
                fsm_restore=(lambda data, lst=lst: lst.__setitem__(
                    slice(None), [tuple(x) for x in data["items"]])),
                fsm_capture=(lambda lst=lst: list(lst)),
                fsm_serialize=(lambda cap: {"items": [list(c)
                                                      for c in cap]}),
                snapshot_threshold=3,
                snapshot_chunk_bytes=64)   # force a multi-frame install
            snaps.node = n
            nodes.append(n)
        for n in nodes:
            n.start()
        transport.partition("c")
        _force_leader(nodes[0])
        errors: List[str] = []

        def propose(tag: str) -> None:
            for i in range(4):
                try:
                    prop = nodes[0].apply_async((f"{tag}{i}",))
                    nodes[0].apply_wait(prop, timeout=30.0)
                except (OSError, NotLeaderError, TimeoutError) as e:
                    errors.append(f"{tag}{i}: {e!r}")

        t1 = threading.Thread(target=propose, args=("x",),
                              name="proposer-x")
        t2 = threading.Thread(target=propose, args=("y",),
                              name="proposer-y")
        t1.start()
        t2.start()
        t1.join()
        t2.join()
        if errors:
            raise AssertionError(f"proposals failed: {errors}")
        # the async worker must compact the leader past the cut
        # follower's next index (1) to force the install path
        for _ in range(300):
            if nodes[0].log.base_index > 0 and not nodes[0]._snap_active:
                break
            time.sleep(0.05)
        if nodes[0].log.base_index <= 0:
            raise AssertionError("leader never compacted its log")
        transport.heal("c")
        # traffic keeps flowing while the chunked install streams
        t3 = threading.Thread(target=propose, args=("z",),
                              name="proposer-z")
        t3.start()
        t3.join()
        if errors:
            raise AssertionError(f"post-heal proposals failed: {errors}")
        target = nodes[0].last_applied
        for _ in range(600):
            with nodes[0]._lock:
                inflight = bool(nodes[0]._snap_inflight)
            if nodes[2].last_applied >= target and not inflight \
                    and not any(n._snap_active for n in nodes):
                break
            time.sleep(0.05)
        if nodes[2].last_applied < target:
            raise AssertionError(
                f"wiped-in follower stuck at {nodes[2].last_applied} "
                f"< {target}")
        if violations:
            raise AssertionError("; ".join(violations))
        checker = InvariantChecker()
        cluster = _FakeCluster(nodes)
        checker.check_election_safety(cluster)
        checker.check_log_matching(cluster)
        checker.check_committed_durability(cluster)
        # install restores the leader's prefix and replication extends
        # it in log order, so the follower's applied sequence must be a
        # prefix of the leader's
        la, lc = applied["a"], applied["c"]
        if lc != la[:len(lc)]:
            raise AssertionError(
                f"follower state diverged after install: {lc} vs {la}")
    finally:
        for n in nodes:
            n.stop()
        transport.close()
        shutil.rmtree(tmp, ignore_errors=True)


class _PipelineStore:
    """Minimal async-proposing store for the plan_pipeline scenario: a
    managed apply thread turns propose_async tokens into applied
    indices, like RaftStore over a group-commit node."""

    can_propose_async = True
    latest_index = 0

    def __init__(self):
        self._cond = threading.Condition()
        self._q: List[int] = []
        self._applied: set = set()
        self._next = 0
        self._closed = False
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="store-apply")

    def start(self):
        self._thread.start()

    def propose_async(self, method: str, payloads) -> int:
        with self._cond:
            if self._closed:
                raise RuntimeError("store stopped")
            self._next += 1
            self._q.append(self._next)
            self._cond.notify_all()
            return self._next

    def wait_applied(self, token: int, timeout: float = 30.0) -> int:
        deadline = time.time() + timeout
        with self._cond:
            while token not in self._applied and not self._closed:
                remaining = deadline - time.time()
                if remaining <= 0:
                    raise TimeoutError(f"apply of round {token}")
                self._cond.wait(remaining)
            if token in self._applied:
                self.latest_index = max(self.latest_index, token)
                return token
            raise RuntimeError("store stopped")

    def upsert_plan_results_batch(self, payloads) -> int:
        with self._cond:
            self._next += 1
            return self._next

    def upsert_plan_results(self, **kw) -> int:
        with self._cond:
            self._next += 1
            return self._next

    def _run(self):
        with self._cond:
            while not self._closed:
                while not self._q and not self._closed:
                    self._cond.wait(0.2)
                while self._q:
                    self._applied.add(self._q.pop(0))
                self._cond.notify_all()

    def stop(self):
        with self._cond:
            self._closed = True
            self._cond.notify_all()
        self._thread.join(timeout=10.0)


@scenario("plan_pipeline")
def _scenario_plan_pipeline(env: ScenarioEnv) -> None:
    """PlanApplier proposer/reaper at COMMIT_PIPELINE_DEPTH with
    submitters racing stop(): every submitted future must resolve —
    success or RuntimeError — never strand until timeout (the
    stop()-drain fix this PR)."""
    from concurrent.futures import Future
    from concurrent.futures import TimeoutError as FutTimeout
    from ..core.plan_apply import PlanApplier, PlanQueue

    store = _PipelineStore()
    store.start()
    applier = PlanApplier(store, PlanQueue())
    applier.start()
    try:
        stranded: List[str] = []

        def submit(tag: str) -> None:
            for i in range(3):
                try:
                    fut: Future = applier.submit_eval_updates(
                        [{"id": f"{tag}{i}"}])
                except RuntimeError:
                    return               # applier already stopped: fine
                try:
                    fut.result(timeout=20.0)
                except (FutTimeout, TimeoutError):
                    stranded.append(f"{tag}{i}")
                    return
                except RuntimeError:
                    return               # failed at stop: answered, fine

        t1 = threading.Thread(target=submit, args=("u",),
                              name="submitter-u")
        t2 = threading.Thread(target=submit, args=("v",),
                              name="submitter-v")
        stopper = threading.Thread(target=applier.stop, name="stopper")
        t1.start()
        t2.start()
        stopper.start()
        t1.join()
        t2.join()
        stopper.join()
        if stranded:
            raise AssertionError(
                f"eval-update futures stranded across stop(): {stranded}")
    finally:
        applier.stop()
        store.stop()


@scenario("broker_batch")
def _scenario_broker_batch(env: ScenarioEnv) -> None:
    """EvalBroker dequeue_batch under concurrent enqueue/nack with an
    enable→disable→enable flip: at most one delay thread may survive
    the flip (the generation-counter fix this PR), every dequeued eval
    is acked or nacked exactly once, and everything shuts down."""
    from ..core.broker import EvalBroker
    from ..structs.evaluation import Evaluation

    broker = EvalBroker(nack_timeout=60.0)
    broker.set_enabled(True)
    try:
        # the racy flip: a delay thread parked in its timed wait from
        # before the disable must exit even though we re-enabled first
        broker.set_enabled(False)
        broker.set_enabled(True)
        sch = current_scheduler()
        for _ in range(60):
            if sch.alive_named("broker-delay") <= 1:
                break
            time.sleep(0.05)
        else:
            raise AssertionError(
                "two broker-delay threads alive after "
                "disable→enable flip (delay thread leaked)")

        def produce() -> None:
            for i in range(4):
                broker.enqueue(Evaluation(id=f"e{i}", job_id=f"j{i}",
                                          modify_index=i + 1))

        seen: List[str] = []
        seen_lock = threading.Lock()

        def consume(name: str) -> None:
            nacked = False
            while True:
                batch = broker.dequeue_batch(["service"], max_batch=4,
                                             timeout=1.0)
                if not batch:
                    with seen_lock:
                        if len(seen) >= 4:
                            return
                    continue
                for ev, token in batch:
                    if not nacked:
                        nacked = True    # exercise redelivery once
                        broker.nack(ev.id, token)
                        continue
                    broker.ack(ev.id, token)
                    with seen_lock:
                        seen.append(ev.id)

        prod = threading.Thread(target=produce, name="producer")
        c1 = threading.Thread(target=consume, args=("c1",),
                              name="consumer-1")
        c2 = threading.Thread(target=consume, args=("c2",),
                              name="consumer-2")
        prod.start()
        c1.start()
        c2.start()
        prod.join()
        c1.join()
        c2.join()
        if sorted(seen) != ["e0", "e1", "e2", "e3"]:
            raise AssertionError(f"acked set wrong: {sorted(seen)}")
    finally:
        broker.set_enabled(False)
        t = broker._delay_thread
        if t is not None:
            t.join(timeout=10.0)


@scenario("solve_batch")
def _scenario_solve_batch(env: ScenarioEnv) -> None:
    """BulkSolverService worker-batch rendezvous (the "tpu-solve" joint
    tier): two batched workers, each an open_batch(2) whose member
    evals race their first joint submit against the service thread's
    bounded launch hold, a third non-joint request that must never
    share a launch group with the joint tier, and a stop() racing the
    tail. Asserts: every member's future resolves (solved or
    failed-at-stop — never stranded), solved == launched, the
    joint/greedy grouping stays pure, and every confirmed solve closes
    its ledger entry (the plan-applier handshake)."""
    import numpy as np

    from ..tensor.solver import (BulkSolverService, _LedgerEntry,
                                 batch_member, open_batch)

    svc = BulkSolverService()
    launches: List[tuple] = []
    launches_lock = threading.Lock()

    class _Static:
        node_index = {"n0": 0}
        device_arrays: dict = {}

    static = _Static()

    def host_dispatch_group(rs):
        # host stub for the device dispatch: record the launch group
        # and hand back an inflight handle — the service pipelines the
        # FETCH (ledger + future resolution) exactly as it would a real
        # double-buffered device launch, so the checker explores the
        # deferred-resolution interleavings too
        with launches_lock:
            launches.append(tuple(sorted(bool(r.joint) for r in rs)))
        return types.SimpleNamespace(rs=rs)

    def host_fetch(inf, pipelined: bool = False) -> None:
        # host stub for the single device_get: same token/ledger/future
        # protocol as _fetch, no accelerator
        for r in inf.rs:
            with svc._lock:
                svc._token += 1
                r.token = svc._token
                svc._ledger[r.token] = _LedgerEntry(
                    static, np.array([0]), np.array([1]),
                    np.ones(2, np.float32), 0.0)
            r.future.set_result(np.zeros(8, np.int64))

    svc._dispatch_group = host_dispatch_group
    svc._fetch = host_fetch

    outcomes: List[str] = []
    out_lock = threading.Lock()

    def member(ctx, seed: int, joint: bool, reject: bool) -> None:
        with batch_member(ctx if joint else None):
            try:
                _counts, token = svc.solve(
                    static=static, feas_base=None, aff=None,
                    ask=np.ones(2), k=1, tg_count=1.0, seed=seed,
                    used_fn=lambda: None, joint=joint)
            except RuntimeError:
                with out_lock:
                    outcomes.append("failed")  # drained at stop: answered
                return
            svc.confirm(token, ["n0"] if reject else [])
            with out_lock:
                outcomes.append("solved")

    def worker(base: int) -> None:
        ctx = open_batch(2)
        ms = [threading.Thread(target=member,
                               args=(ctx, base + i, True, i == 0),
                               name=f"member-{base + i}")
              for i in range(2)]
        for m in ms:
            m.start()
        for m in ms:
            m.join()

    w1 = threading.Thread(target=worker, args=(0,), name="worker-0")
    w2 = threading.Thread(target=worker, args=(10,), name="worker-1")
    lone = threading.Thread(target=member, args=(None, 20, False, False),
                            name="greedy-lone")
    stopper = threading.Thread(target=svc.stop, name="stopper")
    w1.start()
    w2.start()
    lone.start()
    stopper.start()
    for t in (w1, w2, lone, stopper):
        t.join()
    svc.stop()

    if len(outcomes) != 5:
        raise AssertionError(f"member outcomes missing: {outcomes}")
    solved = outcomes.count("solved")
    launched = sum(len(group) for group in launches)
    if launched != solved:
        raise AssertionError(
            f"{launched} requests launched but {solved} futures "
            f"resolved with results")
    if any(len(set(group)) > 1 for group in launches):
        raise AssertionError(
            f"a launch group mixed joint and greedy requests: {launches}")
    with svc._lock:
        leaked = dict(svc._ledger)
    if leaked:
        raise AssertionError(
            f"{len(leaked)} ledger entr(ies) leaked past confirm: "
            f"{sorted(leaked)}")


@scenario("store_ownership")
def _scenario_store_ownership(env: ScenarioEnv) -> None:
    """nomadown integration: a proposer replicates eval upserts through
    FSM.apply and keeps mutating its own retained objects afterwards —
    legal ONLY because the FSM deep-copies every command before handing
    it to the store — while readers race snapshots and iteration
    against the writes. The ownership sanitizer must stay silent.

    tests/test_ownership.py replays this scenario at a pinned seed with
    the FSM's defensive deepcopy monkeypatched away: the store then
    shares the proposer's objects, the post-apply mutations rewrite
    MVCC history, and the same seed MUST fail — the historical
    propose-retain-alias bug, reproduced deterministically."""
    from ..raft.fsm import FSM
    from ..state.store import StateStore
    from ..structs.evaluation import Evaluation
    from . import ownership

    own = ownership.GLOBAL
    was_active = own.active
    if not was_active:
        ownership.install()
    base = len(own.violations)
    store = StateStore()
    fsm = FSM(store)
    try:
        def propose() -> None:
            for i in range(4):
                ev = Evaluation(id=f"own-e{i}", job_id=f"own-j{i}",
                                status="pending")
                fsm.apply(("upsert_evals", ([ev],), {"ts": float(i + 1)}))
                # the proposer's object is private — the FSM deep-copied
                # the command — so this must NOT trip the sanitizer
                ev.status = "complete"
                ev.modify_index = 999 + i

        def read(name: str) -> None:
            for _ in range(6):
                snap = store.snapshot()
                for ev in snap.evals():
                    if ev.status != "pending":
                        raise AssertionError(
                            f"{name} saw a store row mutated after "
                            f"insert: {ev.id} status={ev.status!r}")
                time.sleep(0)

        p = threading.Thread(target=propose, name="own-proposer")
        r1 = threading.Thread(target=read, args=("r1",),
                              name="own-reader-1")
        r2 = threading.Thread(target=read, args=("r2",),
                              name="own-reader-2")
        p.start()
        r1.start()
        r2.start()
        p.join()
        r1.join()
        r2.join()
        own.verify_all()
        fresh = own.violations[base:]
        if fresh:
            raise AssertionError(
                "ownership sanitizer tripped: " + fresh[0].render())
    finally:
        del own.violations[base:]
        if not was_active:
            ownership.uninstall()


@scenario("node_lifecycle")
def _scenario_node_lifecycle(env: ScenarioEnv) -> None:
    """The sharded HeartbeatManager under adversarial interleavings: a
    client heartbeating across its TTL, a remove() racing the expiry
    sweep, and a failover restore() with duplicate/ghost ids — all
    against the shard threads. Asserts: a removed node is NEVER marked
    down, a heartbeating node is marked down only after a real silence
    >= TTL since its last beat, restored ids expire exactly once each,
    and every entry in the expiry attribution log spans >= TTL."""
    from ..core.heartbeat import HeartbeatManager

    ttl = 1.0
    marks: List[tuple] = []            # (node_id, monotonic mark time)
    marks_lock = threading.Lock()

    class _HBServer:
        def mark_nodes_down(self, node_ids, reason=""):
            now = time.monotonic()
            with marks_lock:
                for nid in node_ids:
                    marks.append((nid, now))

        def mark_node_down(self, node_id, reason=""):
            self.mark_nodes_down([node_id], reason=reason)

    mgr = HeartbeatManager(_HBServer(), ttl=ttl, shards=2, expiry_rate=0.0)
    mgr.set_enabled(True)
    try:
        beat_times: List[float] = []

        def beater() -> None:
            for _ in range(6):
                mgr.reset("alive")
                beat_times.append(time.monotonic())
                time.sleep(ttl * 0.4)

        def remover() -> None:
            mgr.reset("removed")
            time.sleep(ttl * 0.3)
            mgr.remove("removed")

        def restorer() -> None:
            time.sleep(ttl * 0.2)
            if mgr.restore(["dup", "dup", "ghost", ""]) != 2:
                raise AssertionError("restore armed wrong timer count")

        threads = [threading.Thread(target=beater, name="hb-beater"),
                   threading.Thread(target=remover, name="hb-remover"),
                   threading.Thread(target=restorer, name="hb-restorer")]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        # everything has gone silent now; give every armed timer (last
        # "alive" beat + restore grace) room to fire
        time.sleep(ttl * 3.0)

        with marks_lock:
            down = list(marks)
        by_id: Dict[str, List[float]] = {}
        for nid, at in down:
            by_id.setdefault(nid, []).append(at)
        if "removed" in by_id:
            raise AssertionError(
                "remove()d node was marked down anyway (lost-removal "
                "race with the expiry sweep)")
        for nid in ("alive", "dup", "ghost"):
            if len(by_id.get(nid, [])) != 1:
                raise AssertionError(
                    f"{nid!r} marked down {len(by_id.get(nid, []))} "
                    f"times, want exactly 1: {by_id}")
        if by_id["alive"][0] < beat_times[-1] + ttl * 0.95:
            raise AssertionError(
                f"'alive' expired {by_id['alive'][0] - beat_times[-1]:.3f}s "
                f"after its last beat — a missed-TTL false positive")
        for nid, armed_at, expired_at in mgr.expiry_snapshot():
            if expired_at - armed_at < ttl * 0.95:
                raise AssertionError(
                    f"attribution log shows {nid!r} expired only "
                    f"{expired_at - armed_at:.3f}s after arming")
        if mgr.active() != 0:
            raise AssertionError(
                f"{mgr.active()} timers still armed after the sweep")
    finally:
        mgr.set_enabled(False)


@scenario("tensor_launch")
def _scenario_tensor_launch(env: ScenarioEnv) -> None:
    """nomadjit integration: the main task cold-launches each shape
    through placer._warm_launch (the real launch driver), then two
    racing workers hammer the warmed shapes under adversarial
    interleavings. Cold compiles stay on the main task deliberately:
    XLA serializes concurrent compiles behind C++ mutexes the scheduler
    cannot see, so a parked cooperative task mid-compile would wedge a
    peer blocked in native code. Warm launches take jit's C++ cache-hit
    fast path and are safe to race. Asserts: the cold launch of each
    shape attributes >= 1 compile to its ledger window, warm windows
    record ZERO compiles and exactly one host sync each, a quiesced
    strict sweep reports no leaked windows, and the violation list
    stays empty. A final leg opens a deliberately warm-marked window
    around an uncompiled shape and asserts the warm-compile violation
    IS recorded (then scrubs it) — the detector must be live, not
    vacuously green."""
    import jax
    import numpy as np

    from ..tensor.placer import _warm_launch
    from . import launch_ledger

    ledger = launch_ledger.GLOBAL
    was_active = ledger.active
    if not was_active:
        launch_ledger.install()
    base = len(ledger.violations)
    tag = f"mc_launch_{env.seed}"

    def kernel(a):
        return a * 2.0 + 1.0

    f = jax.jit(kernel)
    f.__name__ = tag
    warm: set = set()
    shapes = [(4 + (env.seed % 3),), (9 + (env.seed % 3),)]
    errors: List[str] = []

    def launch(shape) -> object:
        dev = jax.device_put(np.ones(shape, np.float32))
        with _warm_launch(f, shape, warm):
            return jax.device_get(f(dev))

    def worker(name: str) -> None:
        try:
            for _ in range(3):
                for shape in shapes:
                    if launch(shape).shape != shape:
                        errors.append(f"{name}: bad launch result")
                    time.sleep(0)
        except Exception as e:  # surfaced after join
            errors.append(f"{name}: {type(e).__name__}: {e}")

    try:
        for shape in shapes:       # cold, main task only (see docstring)
            if launch(shape).shape != shape:
                raise AssertionError("bad cold launch result")
        if set(shapes) - warm:
            raise AssertionError(
                f"cold launches left shapes unwarmed: {set(shapes) - warm}")
        t1 = threading.Thread(target=worker, args=("w1",),
                              name="launch-w1")
        t2 = threading.Thread(target=worker, args=("w2",),
                              name="launch-w2")
        t1.start()
        t2.start()
        t1.join()
        t2.join()
        if errors:
            raise AssertionError("; ".join(errors))
        mine = [r for r in ledger.records if r.name == tag]
        if not mine:
            raise AssertionError("no ledger records for the launches")
        cold_compiles = sum(r.compiles for r in mine if not r.warm)
        if cold_compiles < len(shapes):
            raise AssertionError(
                f"cold launches attributed only {cold_compiles} "
                f"compile(s) for {len(shapes)} shapes — the compile "
                "listener is not feeding the ledger")
        for r in mine:
            if r.warm and r.compiles:
                raise AssertionError(
                    f"warm window {r.key!r} recorded {r.compiles} "
                    f"compile(s): {r.sites}")
            if r.gets != 1:
                raise AssertionError(
                    f"launch window {r.key!r} recorded {r.gets} host "
                    f"syncs, want exactly 1: {r.sites}")
        problems = ledger.verify_all(strict=True)
        fresh = ledger.violations[base:]
        if fresh or problems:
            raise AssertionError(
                "launch ledger tripped on a clean schedule: "
                + (fresh[0].render() if fresh else problems[0]))
        # negative leg: a warm-marked window around a cold shape MUST
        # record the warm-compile violation
        g = jax.jit(kernel)
        g.__name__ = tag + "_neg"
        dev = jax.device_put(np.ones((17,), np.float32))
        with ledger.window(g.__name__, key=(17,), warm=True):
            jax.device_get(g(dev))
        fresh = ledger.violations[base:]
        if not any(v.kind == "warm-compile" for v in fresh):
            raise AssertionError(
                "warm-compile detector is dead: a compile inside a "
                "warm-marked window recorded no violation")
    finally:
        del ledger.violations[base:]
        if not was_active:
            launch_ledger.uninstall()


@scenario("event_flow")
def _scenario_event_flow(env: ScenarioEnv) -> None:
    """nomadflow integration: a store + event broker with a shadow
    replica attached, driven by concurrent mutators covering every
    Allocation/Node/Evaluation delta kind — bulk upserts, client status
    updates (including terminal flips), eval churn with deletes, a
    terminal-alloc GC sweep, and an operator dump/restore that forces
    the full-ring truncation → resync path. After every writer joins,
    the replica's fingerprint compare against a fresh MVCC snapshot
    rebuild (usage columns included) must be exact: under ANY
    interleaving the event stream carries enough information to
    reconstruct the store, or a consumer somewhere is silently stale.

    tests/test_flow_rules.py replays this scenario at a pinned seed
    with a delta kind suppressed to prove the compare actually bites."""
    import numpy as np

    from ..core.events import EventBroker
    from ..state.persist import dump_store, restore_store
    from ..state.store import StateStore
    from ..structs.alloc import Allocation
    from ..structs.evaluation import Evaluation
    from ..structs.node import Node
    from . import shadow as shadow_mod

    store = StateStore()
    broker = EventBroker(store, ring_size=32, shards=2)
    tracker = shadow_mod.ShadowTracker(every=3)
    tracker.install()
    rep = tracker.attach(store, broker)

    def write_nodes() -> None:
        for i in range(4):
            store.upsert_node(Node(id=f"fn{i}"))
        # rewrite a node (same id, new status) — the upsert event must
        # carry the new row, not the old
        store.upsert_node(Node(id="fn0", status="down"))

    def write_evals() -> None:
        store.upsert_evals([Evaluation(id=f"fe{i}", job_id="fj")
                            for i in range(5)])
        store.delete_evals(["fe1", "fe3"])

    def write_allocs() -> None:
        allocs = []
        for i in range(6):
            a = Allocation(id=f"fa{i}", node_id=f"fn{i % 4}",
                           job_id="fj", eval_id="fe0")
            a.allocated_vec = np.full_like(a.allocated_vec,
                                           float(i + 1))
            allocs.append(a)
        store.upsert_allocs(allocs)
        # client flips two to terminal, then GC reaps the orphans
        # (no job row exists, so terminal allocs are collectable)
        for aid in ("fa1", "fa4"):
            upd = Allocation(id=aid, client_status="complete")
            store.update_allocs_from_client([upd])
        store.gc_terminal_allocs(before_index=store._index + 1)

    def restore_leg() -> None:
        # operator restore: the broker truncates every ring and the
        # replica must resync instead of patching a holey stream
        restore_store(store, dump_store(store))
        store.upsert_node(Node(id="fn-post-restore"))

    threads = [threading.Thread(target=write_nodes, name="flow-nodes"),
               threading.Thread(target=write_evals, name="flow-evals"),
               threading.Thread(target=write_allocs, name="flow-allocs"),
               threading.Thread(target=restore_leg, name="flow-restore")]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    msg = rep.force_compare()
    if msg is not None:
        raise AssertionError(f"shadow diverged: {msg}")
    if tracker.violations:
        raise AssertionError("shadow tracker tripped: "
                             + tracker.violations[0].render())


@scenario("overload")
def _scenario_overload(env: ScenarioEnv) -> None:
    """nomadload admission plane under racing callers on a virtual
    clock: three submitter threads hammer the gate while one flips the
    watermarked queue between calm and hard-tripped and another reads
    snapshot()/ledger() concurrently. Checked across every explored
    interleaving:

    - tier-0 is NEVER shed while alive (invariant 10's kernel);
    - accounting closes: admitted + shed == calls made, and the ledger
      agrees with the stats;
    - the shared RetryBudget can never hand out more retries than its
      cap + ratio * recorded requests (no interleaving over-spends);
    - RetryLater survives its wire str() round trip from inside a
      racing thread."""
    from ..core.loadctl import (
        TIER_LIVENESS,
        TIER_SUBMIT,
        AdmissionController,
        RetryLater,
    )
    from ..utils.backoff import RetryBudget

    clock = [0.0]
    clock_lock = threading.Lock()

    def now() -> float:
        with clock_lock:
            clock[0] += 0.001  # every observation advances virtual time
            return clock[0]

    depth = [0]
    adm = AdmissionController(clock=now, refresh_s=0.0,
                              brownout_after=0.05, brownout_exit=0.1)
    adm.register_queue("q", lambda: depth[0], soft=10, hard=100,
                       commit_path=True)
    budget = RetryBudget(ratio=0.25, min_rate=0.0, cap=3.0, clock=now)

    calls = [0]
    calls_lock = threading.Lock()
    errors: List[str] = []

    def submitter(name: str) -> None:
        for _ in range(8):
            budget.record_request()
            with calls_lock:
                calls[0] += 1
            after = adm.try_admit(TIER_SUBMIT, source=name)
            if after is not None:
                # shed: retry once iff the budget allows, as a real
                # client would; rehydrate the wire form on the way
                e = RetryLater(TIER_SUBMIT, after, reason=name)
                r = RetryLater("RetryLater: " + str(e))
                if abs(r.after - e.after) > 0.001 or r.tier != e.tier:
                    errors.append(f"wire roundtrip broke: {e} -> {r}")
                if budget.spend_retry():
                    with calls_lock:
                        calls[0] += 1
                    adm.try_admit(TIER_SUBMIT, source=name)

    def liveness() -> None:
        for _ in range(12):
            with calls_lock:
                calls[0] += 1
            if adm.try_admit(TIER_LIVENESS, source="hb") is not None:
                errors.append("tier-0 shed while alive")

    def flipper() -> None:
        for _ in range(6):
            depth[0] = 100
            now()
            adm.shed_floor()
            depth[0] = 0
            now()
            adm.shed_floor()

    def reader() -> None:
        for _ in range(6):
            snap = adm.snapshot()
            if snap["shed_floor"] < TIER_SUBMIT:
                errors.append(f"floor below submit: {snap}")
            adm.ledger()

    threads = [threading.Thread(target=submitter, args=(f"s{i}",),
                                name=f"submitter-{i}") for i in range(3)]
    threads.append(threading.Thread(target=liveness, name="liveness"))
    threads.append(threading.Thread(target=flipper, name="flipper"))
    threads.append(threading.Thread(target=reader, name="reader"))
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    if errors:
        raise AssertionError(f"overload scenario: {errors[:3]}")
    ledger = adm.ledger()
    shed_t0 = [e for e in ledger if e[1] == TIER_LIVENESS
               and e[2] == "shed"]
    if shed_t0:
        raise AssertionError(f"{len(shed_t0)} tier-0 sheds while alive")
    # every try_admit records exactly one outcome, in both the stats
    # and the ledger — no interleaving loses or double-counts one
    if adm.stats["admitted"] + adm.stats["shed"] != calls[0]:
        raise AssertionError(
            f"gate accounting leak: {calls[0]} calls vs "
            f"{adm.stats['admitted']} + {adm.stats['shed']} outcomes")
    if len(ledger) != calls[0]:
        raise AssertionError(
            f"ledger/stats disagree: {calls[0]} calls, "
            f"{len(ledger)} ledger entries")
    # the retry budget can never over-spend: every retry was funded by
    # the starting cap or a recorded request's deposit
    max_retries = budget.cap + budget.ratio * budget.stats["requests"]
    if budget.stats["retries"] > max_retries + 1e-9:
        raise AssertionError(
            f"retry budget over-spent: {budget.stats} (max "
            f"{max_retries:.2f})")


SMOKE_SCENARIOS = ("raft_commit", "raft_stepdown", "read_index",
                   "snapshot_compact",
                   "plan_pipeline", "broker_batch", "solve_batch",
                   "store_ownership", "node_lifecycle", "tensor_launch",
                   "event_flow", "overload")


def smoke(base_seed: int, seeds_per_scenario: int = 3,
          out=print) -> int:
    """The bounded check.sh gate: a few seeds per scenario per policy,
    plus one fsfaults-composed raft schedule. Returns count of
    failures."""
    failures = 0
    for name in SMOKE_SCENARIOS:
        results = explore(
            name, range(base_seed, base_seed + seeds_per_scenario))
        for r in results:
            if not r.ok:
                failures += 1
                out(r.render())
        ok = sum(1 for r in results if r.ok)
        out(f"  {name}: {ok}/{len(results)} schedules ok")
    r = run_scenario("raft_commit", base_seed, policy="random",
                     fsfaults=True)
    out(f"  raft_commit+fsfaults: "
        f"{'ok' if r.ok else 'FAIL: ' + str(r.error)}")
    if not r.ok:
        failures += 1
    return failures
