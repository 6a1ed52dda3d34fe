"""Tests of the benchmark itself: CPU only, at tiny sizes.

    JAX_PLATFORMS=cpu python3 -m pytest -q chipbench/tests
"""

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
