"""Drive one benchmark run on the CPU, skipping the look for a chip:

    python bench/tests/run_tiny.py <root> <cell> <seed> [fault|-] [--control]

Used by the tests in a process of their own, so that the run's JAX
configuration (its compile cache) stays out of the test process."""
from __future__ import annotations

import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1]))
sys.path.insert(0, str(HERE))

from bench import run  # noqa: E402
from faults import FAULTS  # noqa: E402

if __name__ == "__main__":
    root, cell, seed, fault = (Path(sys.argv[1]), sys.argv[2], sys.argv[3],
                               sys.argv[4])
    argv = ["--workload", cell, "--seed", seed, "--seconds", "2",
            "--trace", "0"]
    if "--control" in sys.argv:
        argv += ["--control", "1"]
    sys.exit(run.main(argv, root=root, require_chip=False,
                      fault=FAULTS.get(fault)))
