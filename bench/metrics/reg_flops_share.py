"""The regularizer's share of the chip's bf16 peak while it runs, in %: the
operations R(C) requires (the cell's program module's ``regularizer_flops``,
the same count whatever implements it) per step, over ``reg_ms``'s device
seconds per step, over the peak (``bench/peaks.py``)."""

from bench import cells, peaks, scopes


def read(r):
    ms = scopes.part_ms(r, scopes.REGULARIZER)
    if ms is None:
        return None
    rate = cells.program(r.cell).regularizer_flops(r.cell.config, int(r.cell.traffic["batch"])) / (ms / 1e3)
    return 100.0 * rate / peaks.peak(r.device_kind)["bf16_flops"]
