"""UUID helpers (reference helper/uuid).

`uuid.uuid4()` costs an os.urandom syscall per id; at bulk-placement
scale (2M allocations) id minting is a measurable slice of the commit
path. A process-local PRNG seeded once from os.urandom gives the same
128 random bits per id (collision resistance is what matters here — ids
are object NAMES) at ~6x less cost. getrandbits is a single C call, so
concurrent scheduler workers can't interleave mid-update under the GIL.

The fast stream is observable (alloc/eval ids are public API output) and
Mersenne Twister state is recoverable from its outputs, so anything that
acts as a bearer credential MUST use generate_secret_uuid() instead —
same format, CSPRNG-backed.
"""

import os
import random
import secrets

_rng = random.Random(int.from_bytes(os.urandom(16), "big"))


def _format_uuid(h: str) -> str:
    return f"{h[:8]}-{h[8:12]}-{h[12:16]}-{h[16:20]}-{h[20:]}"


def generate_uuid() -> str:
    """Fast non-cryptographic uuid for object names (allocs, evals, ...)."""
    return _format_uuid(f"{_rng.getrandbits(128):032x}")


def generate_secret_uuid() -> str:
    """CSPRNG uuid for bearer credentials (ACL secret_ids, ack tokens)."""
    return _format_uuid(secrets.token_hex(16))


def short_id(full: str) -> str:
    return full.split("-")[0]
