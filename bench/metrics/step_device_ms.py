"""Device busy time per train step in the traced window, in ms."""

from bench import trace


def read(r):
    if r.trace is None or not r.trace.ops or r.steps == 0:
        return None
    return 1e3 * trace.busy_seconds(r.trace) / r.steps
