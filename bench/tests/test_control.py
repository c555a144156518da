"""The control of ``correct``: the plain reference in the program's place,
computed one precision below the configuration's (bfloat16 for float32),
must fail the comparison with each cell's own limits.  On the chip the same
reading is made at the cell's size by ``bench/calibrate.py``; here at a tiny
size."""

import jax.numpy as jnp
import pytest

from bench import cells, correct, harness
from bench.tests.tiny import cell_names, tiny_cell


@pytest.mark.parametrize("name", cell_names())
def test_bfloat16_reference_is_not_correct(name):
    cell = tiny_cell(name)
    run = harness.Run(cell, 2**31 + 5, cells.program(cell).build(cell.config, int(cell.traffic["batch"])),
                      harness.Spans(annotate=False))
    ref = run.reference()
    control = run.reference(dtype=jnp.bfloat16, precision="default")
    ok, checks = correct.verdict(correct.numbers(control, ref), cell.limits)
    assert not ok, checks


def test_reference_imports_nothing_of_the_program():
    src = (cells.BENCH / "refs" / "bt_mlp.py").read_text()
    assert "repro" not in src and "bench" not in src.replace("bench/", "")
