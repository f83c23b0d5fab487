"""One backend bootstrap for every entry point that can schedule.

`bootstrap()` runs before the first compile in `cli.cmd_agent`,
`chip_smoke.py`, `python -m nomad_tpu.chaos` and
`python -m nomad_tpu.obs`, and does two things nothing else in the tree
repeats:

- **Compile cache.** Where `JAX_COMPILATION_CACHE_DIR` is set, JAX's own
  handling of it stands and no directory is set in code. Where it is
  not, the cache lives at `<checkout>/.jax_cache` — derived from this
  package's location, because the path is part of what makes a cache
  survive from one process to the next.
- **Backend.** The device JAX resolved is named (platform, device kind,
  count) in the log and returned, so every start line, stats endpoint
  and benchmark line can carry it. A `tpu-*` algorithm runs on a TPU,
  or on the CPU only when `JAX_PLATFORMS` names `cpu` (the test arm —
  JAX's own variable). JAX falling to the CPU by itself because the
  chip failed to initialise is an error, not a slower run.

`require_tpu()` is the same check at the one factory every `tpu-*`
placement goes through (`TPUPlacer`), so an operator flipping the
algorithm on a running agent cannot reach a silent CPU arm either.
"""

from __future__ import annotations

import logging
import os
from dataclasses import asdict, dataclass
from pathlib import Path

logger = logging.getLogger("nomad_tpu.backend")

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# compiles faster than this are cheaper to redo than to read back
MIN_CACHED_COMPILE_S = 0.5


class BackendError(RuntimeError):
    """The backend JAX resolved cannot serve a `tpu-*` algorithm."""


@dataclass(frozen=True)
class Device:
    platform: str
    kind: str
    count: int

    def as_dict(self) -> dict:
        return asdict(self)

    def __str__(self) -> str:
        return f"{self.platform}:{self.kind}x{self.count}"


def cache_dir() -> str:
    """Where compiled programs persist: the environment's directory, or
    `<checkout>/.jax_cache`."""
    return os.environ.get(CACHE_ENV) or str(
        Path(__file__).resolve().parents[2] / ".jax_cache")


def device() -> Device:
    """The backend as JAX reports it. A backend that fails to
    initialise raises here; nothing turns that into 'no device'."""
    import jax

    devs = jax.devices()
    return Device(platform=devs[0].platform, kind=devs[0].device_kind,
                  count=len(devs))


def require_tpu() -> Device:
    """The device a `tpu-*` algorithm may run on."""
    import jax

    dev = device()
    if dev.platform == "tpu":
        return dev
    named = [p.strip() for p in (jax.config.jax_platforms or "").split(",")]
    if dev.platform == "cpu" and "cpu" in named:
        return dev
    raise BackendError(
        f"a tpu-* scheduler algorithm needs a TPU, but JAX resolved "
        f"{dev} (JAX_PLATFORMS={jax.config.jax_platforms!r}). If the chip "
        f"failed to initialise, fix that; to run the device path on the "
        f"CPU on purpose, set JAX_PLATFORMS=cpu.")


def bootstrap(algorithm: str = "") -> Device:
    """Place the compile cache, resolve the backend, and hold a `tpu-*`
    `algorithm` to `require_tpu()`. Idempotent; call before the first
    compile."""
    import jax

    if not os.environ.get(CACHE_ENV):
        jax.config.update("jax_compilation_cache_dir", cache_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      MIN_CACHED_COMPILE_S)
    dev = require_tpu() if algorithm.startswith("tpu-") else device()
    logger.info("backend: %s, compile cache at %s", dev, cache_dir())
    return dev
