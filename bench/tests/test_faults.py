"""``correct`` on the CPU at a tiny size: runs whose timed path is broken
underneath come out not correct.

Each run skips the harness's look for a chip and drives the rest of a run with
the cell's own limits.  The faults a one-chip training cell can have: a step
that returns its state unchanged, and half of the batch left out with the mean
taken over the rest; and, for the regularizer's backward pass, the gradient
through the regularizer zeroed with the loss unchanged.  All but the first are
the program module's own (``faults``), the ones that are read on the chip.
"""

import pytest

from bench import cells
from bench.tests.runs import run
from bench.tests.tiny import cell_names, tiny_cell


def _broken(module, fault, n):
    real = module.build

    def build(cfg, batch):
        prog = real(cfg, batch)
        if fault != "state_unchanged":
            return module.faults(prog, n)[fault]
        step = prog.step
        prog.step = lambda s, b: (s, step(s, b)[1])
        return prog

    return build


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch", "reg_grad_zero"])
@pytest.mark.parametrize("name", cell_names())
def test_broken_step_is_not_correct(name, fault, monkeypatch):
    cell = tiny_cell(name)
    module = cells.program(cell)
    monkeypatch.setattr(module, "build", _broken(module, fault, int(cell.traffic["batch"])))
    r = run(cell)
    assert not r["correct"], r["checks"]


