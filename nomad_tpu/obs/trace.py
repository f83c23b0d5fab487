"""nomadtrace: lightweight eval-lifecycle tracing.

A process-global `Tracer` records named spans into per-thread bounded
ring buffers. The hot path is lock-free: each ring has exactly one
writer (its owning thread), so appends are plain GIL-atomic list ops;
the registry of rings takes a lock only at ring creation and at
export-time snapshot. Every span exit also feeds the span's duration
into the metrics Registry under ``nomad.eval.phase.<name>`` so the
prometheus surface gains per-phase histograms for free.

Span records are plain tuples (see the ``R_*`` index constants):

    (name, trace, parent, span_id, t0, t1, thread, args)

``trace`` ties a span to one evaluation's lifecycle (``Evaluation.trace()``
— the eval id unless explicitly stamped). Batch-level spans that cover
several evals at once (a shared worker snapshot, a pipelined commit
round, a joint solver launch) carry ``traces=[...]`` inside ``args``
instead; raft-internal spans (fsync, replicate, apply) are trace-less
and attach to evals only by time overlap (obs/export.py gap
attribution).

Kill switch: ``NOMAD_TPU_TRACE=0`` disables the tracer at import; every
``span()`` call then returns a shared no-op singleton and ``event`` /
``add_span`` return before touching a clock — the instrumentation
compiles down to a bool check per call site.

Clock: ``time.time()`` (wall). It is shared with the broker's
``_enqueue_times`` side table (which powers the retroactive
``eval.queued`` span) and comparable across threads; span durations are
milliseconds-scale, far above its resolution.

Two per-span extras, both off unless the call site asks:

- ``span(..., cpu=True)`` stamps ``cpu_s`` into the span's args: the
  thread's own CPU seconds over the span (``time.thread_time()``). Wall
  minus ``cpu_s`` is time the thread was runnable or blocked and not
  running — the interpreter lock or another lock.
- ``span(..., device=True)`` also enters a ``jax.profiler.TraceAnnotation``
  of the same name, so inside a profiler session the span lands in the
  ``.xplane.pb`` beside the device's ops, on the profiler's clock.
  Outside a session an annotation is a flag check.
"""

from __future__ import annotations

import itertools
import os
import sys
import threading
import time
from typing import List, Optional

# The Registry binds lazily: importing core.metrics here would run
# core/__init__ -> server -> broker -> back into this half-initialized
# package (obs must stay a leaf import for every subsystem).
_REGISTRY = None
# jax binds lazily too, and only for spans that ask for device=True:
# a process that never solves never imports it through obs
_ANNOTATION = None


def _annotation(name: str):
    """A profiler annotation, or None in a process that has not loaded
    jax: there is no profiler session there to land in, and a server
    that never schedules must not import jax through a span."""
    global _ANNOTATION
    if _ANNOTATION is None:
        if "jax" not in sys.modules:
            return None
        from jax.profiler import TraceAnnotation

        _ANNOTATION = TraceAnnotation
    return _ANNOTATION(name)


def _registry():
    global _REGISTRY
    if _REGISTRY is None:
        from ..core.metrics import REGISTRY

        _REGISTRY = REGISTRY
    return _REGISTRY


# record tuple layout
R_NAME, R_TRACE, R_PARENT, R_ID, R_T0, R_T1, R_THREAD, R_ARGS = range(8)

# default per-thread ring capacity (records); a span record is a small
# tuple, so even 64 threads hold only a few MB at this bound
RING_CAP = int(os.environ.get("NOMAD_TPU_TRACE_RING", "8192"))

_ids = itertools.count(1)  # next() is GIL-atomic: one span-id sequence


class _NullSpan:
    """The disabled-tracer span: a stateless, re-enterable no-op.
    Doubles as the disabled bind() context."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **kv) -> None:
        return None


NULL_SPAN = _NullSpan()


class _Ring:
    """Bounded record ring with a single writer (its owning thread)."""

    __slots__ = ("buf", "cap", "idx", "dropped")

    def __init__(self, cap: int):
        self.buf: list = []
        self.cap = cap
        self.idx = 0  # next overwrite position once full
        self.dropped = 0  # records overwritten since the ring filled

    def append(self, rec: tuple) -> None:
        if len(self.buf) < self.cap:
            self.buf.append(rec)
        else:
            self.buf[self.idx] = rec
            self.idx = (self.idx + 1) % self.cap
            self.dropped += 1

    def snapshot(self) -> list:
        # cross-thread read of a single-writer ring: list() is one
        # GIL-atomic copy; a concurrent wrap can at worst misorder the
        # boundary records, and export sorts by t0 anyway
        buf = list(self.buf)
        if len(buf) < self.cap:
            return buf
        i = self.idx
        return buf[i:] + buf[:i]


class _Span:
    """One open span (context manager). Created only when the tracer is
    enabled; records itself into the calling thread's ring on exit."""

    __slots__ = ("_tr", "name", "trace", "args", "_parent", "sid", "t0",
                 "_cpu0", "_ann")

    def __init__(self, tr: "Tracer", name: str, trace, args: dict,
                 cpu: bool = False, device: bool = False):
        self._tr = tr
        self.name = name
        self.trace = trace
        self.args = args
        self._parent = 0
        self.sid = 0
        self.t0 = 0.0
        self._cpu0 = 0.0 if cpu else None   # thread_time() at entry
        self._ann = device                  # the annotation once entered

    def __enter__(self):
        tl = self._tr._tl()
        stack = tl.stack
        if self.trace is None:
            if stack and stack[-1][1] is not None:
                self.trace = stack[-1][1]
            elif tl.bound:
                self.trace = tl.bound[-1]
        self._parent = stack[-1][0] if stack else 0
        self.sid = next(_ids)
        stack.append((self.sid, self.trace))
        if self._cpu0 is not None:
            self._cpu0 = time.thread_time()
        self.t0 = time.time()
        if self._ann:
            # an annotation's event starts when it is built
            self._ann = _annotation(self.name)
            if self._ann is not None:
                self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        if self._ann:
            self._ann.__exit__(*exc)
        t1 = time.time()
        if self._cpu0 is not None:
            self.args["cpu_s"] = time.thread_time() - self._cpu0
        tl = self._tr._tl()
        if tl.stack and tl.stack[-1][0] == self.sid:
            tl.stack.pop()
        tl.ring.append((self.name, self.trace, self._parent, self.sid,
                        self.t0, t1, tl.tid, self.args))
        _registry().observe("nomad.eval.phase." + self.name, t1 - self.t0)
        return False

    def set(self, **kv) -> None:
        """Attach args discovered mid-span (result sizes, verdicts)."""
        self.args.update(kv)


class _Bind:
    """Thread-local trace binding: spans opened inside inherit the
    bound trace id when they don't name one themselves."""

    __slots__ = ("_tr", "trace")

    def __init__(self, tr: "Tracer", trace):
        self._tr = tr
        self.trace = trace

    def __enter__(self):
        self._tr._tl().bound.append(self.trace)
        return self

    def __exit__(self, *exc):
        bound = self._tr._tl().bound
        if bound:
            bound.pop()
        return False


class Tracer:
    def __init__(self, enabled: Optional[bool] = None,
                 ring_cap: int = RING_CAP):
        if enabled is None:
            enabled = os.environ.get("NOMAD_TPU_TRACE", "1") != "0"
        self.enabled = bool(enabled)
        self.ring_cap = ring_cap
        self._local = threading.local()
        # ring registry: written once per thread generation under the
        # lock, read (snapshot) under the lock; ring CONTENTS stay
        # lock-free. _epoch bumps on clear(): a thread whose local ring
        # predates the current epoch lazily replaces it, so cleared
        # records never resurface
        self._reg_lock = threading.Lock()
        self._rings: dict = {}  # id(ring) -> _Ring
        self._epoch = 0

    def set_enabled(self, enabled: bool) -> None:
        self.enabled = bool(enabled)

    # -- thread-local state --

    def _tl(self):
        tl = self._local
        if getattr(tl, "ring", None) is None or tl.epoch != self._epoch:
            tl.ring = _Ring(self.ring_cap)
            tl.stack = getattr(tl, "stack", None) or []
            tl.bound = getattr(tl, "bound", None) or []
            tl.tid = threading.current_thread().name
            tl.epoch = self._epoch
            with self._reg_lock:
                self._rings[id(tl.ring)] = tl.ring
        return tl

    # -- recording --

    def span(self, name: str, trace=None, *, cpu: bool = False,
             device: bool = False, **args):
        """Open a named span as a context manager. ``trace`` defaults to
        the enclosing span's / bind()'s trace id. ``cpu`` stamps the
        thread's CPU seconds into ``args["cpu_s"]`` at exit; ``device``
        mirrors the span into the jax profiler's trace (module doc)."""
        if not self.enabled:
            return NULL_SPAN
        return _Span(self, name, trace, args, cpu, device)

    def bind(self, trace):
        """Context manager: spans opened inside (on this thread) inherit
        ``trace`` unless they name their own."""
        if not self.enabled:
            return NULL_SPAN
        return _Bind(self, trace)

    def add_span(self, name: str, t0: float, t1: float, trace=None,
                 **args) -> None:
        """Record a span retroactively from externally captured
        timestamps (e.g. the broker's enqueue-time side table)."""
        if not self.enabled:
            return
        tl = self._tl()
        tl.ring.append((name, trace, 0, next(_ids), t0, t1, tl.tid, args))
        _registry().observe("nomad.eval.phase." + name, max(0.0, t1 - t0))

    def event(self, name: str, trace=None, **args) -> None:
        """Record an instant (zero-duration span)."""
        if not self.enabled:
            return
        tl = self._tl()
        now = time.time()
        tl.ring.append((name, trace, 0, next(_ids), now, now, tl.tid, args))

    # -- export --

    def spans(self) -> List[tuple]:
        """Snapshot every thread's ring, merged and sorted by start
        time. Cheap enough for a scrape endpoint; never blocks
        writers."""
        with self._reg_lock:
            rings = list(self._rings.values())
        out: List[tuple] = []
        for r in rings:
            out.extend(r.snapshot())
        out.sort(key=lambda rec: rec[R_T0])
        return out

    @property
    def dropped(self) -> int:
        """Records overwritten in full rings since the last clear():
        what spans() can no longer return."""
        with self._reg_lock:
            return sum(r.dropped for r in self._rings.values())

    def clear(self) -> None:
        """Drop all recorded spans (bench/test isolation): unregister
        every ring and bump the epoch so each thread re-registers a
        fresh one on its next record."""
        with self._reg_lock:
            self._rings.clear()
            self._epoch += 1


TRACER = Tracer()
