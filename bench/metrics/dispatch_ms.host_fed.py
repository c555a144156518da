"""``dispatch_ms`` in a host-fed cell, where it moves ``host_fed_samples_per_s``."""

from bench.metrics.dispatch_ms import read  # noqa: F401
