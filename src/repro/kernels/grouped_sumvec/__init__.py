from repro.kernels.grouped_sumvec.ops import (
    r_sum_kernel,
    grouped_frequency_accumulator_kernel,
)
from repro.kernels.grouped_sumvec.ref import r_sum_grouped_ref, r_sum_ref, grouped_sumvec_ref
