"""Production meshes.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import and only then calls these.

Every mesh has Auto axes: the program shards by annotation
(``parallel/sharding.py`` constraints, GSPMD).  ``jax.make_mesh`` defaults
to Explicit axes; ``parallel/sharding.sharding_context`` takes those too.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(shape, axes) -> Mesh:
    """``jax.make_mesh`` over the local devices, with Auto axes."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh_for_devices(n_devices: int, model_parallel: int = 1) -> Mesh:
    """Elastic helper: best (data, model) mesh for an arbitrary device count."""
    assert n_devices % model_parallel == 0
    return make_mesh((n_devices // model_parallel, model_parallel), ("data", "model"))
