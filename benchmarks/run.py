"""One run of one benchmark cell:

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Prints one JSON object as the last line of standard output.  Fails,
printing none, unless jax's first device is a TPU in the peaks table.
"""

import time

_T0 = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

_HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(_HERE))
sys.path.append(str(_HERE.parent))  # the system under test

if __name__ == "__main__":
    from lobench import runner

    raise SystemExit(runner.main(t0=_T0))
