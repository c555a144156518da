"""Device time per train step of the grouped R_sum's Pallas kernels, in ms.

The kernels are the custom calls that ``jax.jit(r_sum_kernel)`` puts into the
step (``repro.kernels.grouped_sumvec.ops``).  The trace names an operation by
its HLO instruction, and Mosaic's kernels carry no name of their own there:
these are ``jvp_jit_r_sum_kernel__.<i>`` forward and
``transpose_jvp_jit_r_sum_kernel___.<i>`` backward: two calls a step,
``spectral_gram`` (the forward: both views' block DFTs and the cross-spectra)
and ``spectral_gram_vjp`` (its backward).  A step without them reads nothing.
"""

from bench import trace

PATTERN = "r_sum_kernel"


def is_kernel(name: str) -> bool:
    return PATTERN in name


def read(r):
    if r.trace is None or r.steps == 0:
        return None
    times = trace.op_seconds(r.trace, match=is_kernel)
    if not times:
        return None
    return 1e3 * sum(times.values()) / r.steps
