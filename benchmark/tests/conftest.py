"""Run by hand: JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
(not part of the repo's tier-1 tests)."""

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
