#!/usr/bin/env python3
"""One cell of the benchmark, once, in a fresh process.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up in BENCHMARK.json; its configuration, traffic mix
and per-layer metrics are data files under benchmark/ found by name
(benchmark/harness.py). The last line of standard output is the result:
one JSON object with `correct`, `attempted`, `failed`, `metrics` and
`device` (and `breakdown` in a traced run), then `checked`: each number
`correct` compared beside its limit, which are also the last lines of
standard error. Off a TPU, with fewer chips
than the cell asks for, or in a directory without the program, nothing
is printed there and the exit code is not 0.

`--toy` (sizes of a CPU test, any backend) is for rehearsal and tests
only: its numbers are never written anywhere.
"""

import sys
import time

T_START = time.time()

if __name__ == "__main__":
    from pathlib import Path

    # the checkout, in place of this directory: nothing under benchmark/
    # may shadow a module of the standard library or of the program
    sys.path[0] = str(Path(__file__).resolve().parents[1])
    from benchmark.harness import main

    sys.exit(main(sys.argv[1:], T_START))
