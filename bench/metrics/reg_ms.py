"""Device time per train step of the regularizer R(C), in ms: the operations
traced under the program's ``regularizer`` scope (``bench/scopes.py``),
forward and backward, whatever implements it (R_off's C, the FFT or grouped
R_sum, their Pallas kernels, the permutation)."""

from bench import scopes


def read(r):
    return scopes.part_ms(r, scopes.REGULARIZER)
