"""Device memory that training needs, in GiB: the allocator's peak of bytes in
use (``memory_stats()["peak_bytes_in_use"]``) read after the three synced steps
of set-up, before the window.  It holds the state that the caller of
``run_training`` keeps, the state a step takes and the one it returns (nothing
is donated), the step's temporaries and the traffic's device pool.  The
window's own peak is not this: there the host runs up to ``log_every`` steps
ahead, each holding a state of its own."""


def read(r):
    return r.step_peak_bytes / 2**30 if r.step_peak_bytes else None
