"""``device_idle_share`` in a host-fed cell, where it moves ``host_fed_samples_per_s``."""

from bench.metrics.device_idle_share import read  # noqa: F401
