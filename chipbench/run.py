#!/usr/bin/env python3
"""One run of one benchmark cell; see `harness.py`.

    python3 chipbench/run.py --workload qwen3-32b.code --seed 7 --seconds 10 --trace 0
"""

import time

T0 = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

# run from the checkout root as a package, never with this directory first
# on the path (its module names would shadow the standard library's)
_HERE = str(Path(__file__).resolve().parent)
sys.path[:] = [p for p in sys.path if p not in ("", _HERE)]
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench.harness import main  # noqa: E402

if __name__ == "__main__":
    main(t0=T0)
