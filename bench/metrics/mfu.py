"""The train step's share of the chip's bf16 peak, in %: the operations the
step requires (the cell's program module's ``step_flops``) times the steps per
second of the window, over the peak (``bench/peaks.py``)."""

from bench import cells, peaks


def read(r):
    if r.steps == 0:
        return None
    n = int(r.cell.traffic["batch"])
    rate = cells.program(r.cell).step_flops(r.cell.config, n) * r.steps / r.window_s
    return 100.0 * rate / peaks.peak(r.device_kind)["bf16_flops"]
