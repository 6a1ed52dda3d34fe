"""The control at a tiny size: a lower precision in the program's place.

On the chip, `chipbench/control.py` reads the control at each cell's own
size (PERF.md gives the readings the limits were set from).  Here the same
code runs at a tiny size on the CPU: the program comes out correct under
the cell's limits, and the reference computed in bfloat16, read at the
same prompts and served tokens and judged by the same comparison, does
not, on every seed.
"""

import pytest

from chipbench import control

from chipbench.tests.test_faults import tiny_cell


@pytest.mark.parametrize("seeds", [[2**31 + 5, 9]])
def test_control_reads_beyond_the_limit(seeds):
    cell = tiny_cell()
    # enough near ties for a lower precision to show: a wider vocabulary
    # and more compared tokens than the fault tests need
    cell["cfg"]["vocab"] = 4096
    recs = control.readings("tiny.code", seeds, len(seeds), 0.5,
                            need_chip=False, cell_override=cell,
                            emit=lambda s: None)
    assert {r["who"] for r in recs} == {"program", "control_bfloat16"}
    for r in recs:
        assert r["correct"] is (r["who"] == "program"), r
