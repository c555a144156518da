"""A short run of a tiny cell on the CPU, shared by the correctness tests."""

import time

from bench import harness

SEED = 2**31 + 77


def run(cell):
    """One run of ``cell`` without the look for a chip: set-up, the first steps,
    a short window, the reference and the verdict, with the cell's limits."""
    return harness.run_cell(cell, SEED, 0.2, False, time.perf_counter())
