"""Device time per train step of the optimizer's update, in ms: the
operations traced under the program's ``optimizer`` scope
(``bench/scopes.py``), LARS's norms, momentum and parameter update."""

from bench import scopes


def read(r):
    return scopes.part_ms(r, scopes.OPTIMIZER)
