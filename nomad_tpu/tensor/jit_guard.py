"""Perf-correctness guard for the jitted solver hot path.

Two silent performance killers on a warm solver:

- **retraces**: shape/dtype/weak-type drift recompiles a jitted
  function that was supposed to be warm, billing an XLA compile (tens
  of ms to seconds) to a production launch;
- **implicit host transfers**: a numpy array slipping into a launch (or
  a device array silently read back) ships bytes synchronously on every
  call.

``cache_size()`` probes a jitted function's compile-cache entry count
(the ``_cache_size`` hook on JAX's jit wrapper). ``no_retrace()`` turns
a code region into a hard window: any implicit transfer raises
immediately (``jax.transfer_guard("disallow")`` — explicit
``jax.device_put``/``jax.device_get`` stay legal), and on exit the
wrapped functions' caches must not have grown beyond ``expect``
compiles. The BulkSolverService wraps every non-sharded launch in a
window and folds the deltas into ``stats["compiles"]`` /
``stats["retraces"]`` so the tests (and any operator reading
/v1/agent/solver stats) can assert a warm steady state.
"""

from __future__ import annotations

import contextlib
from typing import Dict, Iterator


class RetraceError(AssertionError):
    """A jit cache grew inside a window that promised it would not."""


def cache_size(fn) -> int:
    """Number of compiled entries behind a jitted callable. A callable
    without the probe raises: a window that cannot count compiles must
    not pass for one that saw none."""
    probe = getattr(fn, "_cache_size", None)
    if probe is None:
        raise TypeError(
            f"{getattr(fn, '__name__', fn)!r} has no _cache_size probe: "
            f"not a jax.jit wrapper of the installed JAX, so a "
            f"no_retrace window over it could never trip")
    return int(probe())


@contextlib.contextmanager
def no_retrace(*fns, expect: int = 0) -> Iterator[Dict]:
    """Hard perf window: implicit host<->device transfers raise, and
    each fn in ``fns`` may gain at most ``expect`` new compile-cache
    entries (0 = must already be warm). Yields a dict whose "compiles"
    key holds the total cache growth observed on exit."""
    import jax

    before = [(fn, cache_size(fn)) for fn in fns]
    out: Dict = {"compiles": 0}
    with jax.transfer_guard("disallow"):
        try:
            yield out
        except Exception as e:
            # attribute the trip to the launch ledger (NOMAD_TPU_SAN=1)
            # before re-raising: the guard is the enforcement point, the
            # ledger is the attribution record
            if "transfer" in str(e).lower():
                from ..analysis import launch_ledger
                launch_ledger.note_unsanctioned(
                    f"a no_retrace window over "
                    f"{[getattr(f, '__name__', str(f)) for f in fns]}")
            raise
    grew = []
    for fn, b in before:
        a = cache_size(fn)
        out["compiles"] += max(0, a - b)
        if a - b > expect:
            grew.append(f"{getattr(fn, '__name__', fn)}: {b} -> {a}")
    if grew:
        raise RetraceError(
            "jit cache grew past the promised warmup inside a "
            f"no_retrace window ({'; '.join(grew)}): an argument's "
            "shape/dtype/weak-type drifted on the hot path")


@contextlib.contextmanager
def count_compiles(*fns) -> Iterator[Dict]:
    """Soft variant for warmup accounting: no transfer guard, no limit;
    yields a dict whose "compiles" key is filled on exit."""
    before = [(fn, cache_size(fn)) for fn in fns]
    out: Dict = {"compiles": 0}
    yield out
    for fn, b in before:
        out["compiles"] += max(0, cache_size(fn) - b)
