"""The control has to come out not correct: the reference computed with
its statistics in bfloat16 (the precision step below the configurations'
float32), put in the program's place, fails the committed limits. Here at
a size a test run holds; on the chip it was read at each cell's own size
(PERF.md gives those readings)."""
import pytest


@pytest.mark.parametrize("cell", ["chembl-train", "ml20m-serve-mixed"])
def test_control_fails_the_limits(tiny, cell):
    out = tiny(cell, control=True)
    assert not out["correct"], out["compared"]
