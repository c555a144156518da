"""Share of the traced window in which no operation ran on the device, in %."""

from bench import trace


def read(r):
    if r.trace is None or not r.trace.ops:
        return None
    return 100.0 * (1.0 - trace.busy_seconds(r.trace) / trace.window_seconds(r.trace))
