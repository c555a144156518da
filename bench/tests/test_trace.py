"""The reduction from a profiler trace to busy time, per-operation time, idle
gaps and the Pallas kernels' time."""

import pytest

from bench import cells
from bench import trace as tm

FIXTURE = cells.BENCH / "tests" / "data" / "sum-b256-3steps.xplane.pb"
reg = cells.load_module("metrics", "reg_kernels_ms")


def _trace():
    ops = {"/device:TPU:0": [("a", 0, 10), ("b", 5, 20), ("a", 30, 40),
                             ("jvp_jit_r_sum_kernel__.5", 40, 45), ("c", 90, 120)]}
    spans = [("bench.window", 2, 100), ("bench.batch", 21, 29), ("bench.dispatch", 46, 60),
             ("bench.batch", 60, 89)]
    return tm.Trace(ops=ops, spans=spans)


def test_busy_is_the_union_inside_the_window():
    t = _trace()
    assert tm.busy_intervals(t.ops["/device:TPU:0"], 2, 100) == [(2, 20), (30, 45), (90, 100)]
    assert tm.busy_seconds(t) == pytest.approx(43e-9)
    assert tm.window_seconds(t) == pytest.approx(98e-9)


def test_op_seconds_clip_to_the_window_and_sum_by_name():
    got = tm.op_seconds(_trace())
    assert got["a"] == pytest.approx(18e-9) and got["b"] == pytest.approx(15e-9)
    assert got["c"] == pytest.approx(10e-9)
    assert tm.op_seconds(_trace(), match=reg.is_kernel) == {"jvp_jit_r_sum_kernel__.5": pytest.approx(5e-9)}


def test_idle_gaps_are_named_by_the_host_span_over_them():
    assert tm.idle_gaps(_trace()) == [("bench.batch", pytest.approx(45e-9)), ("bench.batch", pytest.approx(10e-9))]


def test_one_window_span_is_required():
    t = _trace()
    t.spans.append(("bench.window", 0, 5))
    with pytest.raises(ValueError):
        t.window()


# A trace recorded on one TPU v5e: three steps of grouped R_sum at d=8192 and
# batch 256 inside a bench.window span, with the harness's batch and dispatch
# spans.  It was recorded with an earlier encoder (3072 -> 4096 -> 4096, two
# projector layers); the regularizer's kernels are those of the cells now.

@pytest.fixture(scope="module")
def recorded():
    return tm.load(str(FIXTURE))


def test_recorded_trace_has_its_device_ops_and_spans(recorded):
    assert list(recorded.ops) == ["/device:TPU:0"]
    assert len(recorded.ops["/device:TPU:0"]) == 1155
    names = [n for n, _, _ in recorded.spans]
    assert names.count("bench.window") == 1 and names.count("bench.dispatch") == 3
    assert names.count("bench.batch") == 3


def test_recorded_busy_and_window(recorded):
    assert tm.window_seconds(recorded) == pytest.approx(0.035018008)
    assert tm.busy_seconds(recorded) == pytest.approx(0.032578109)
    gaps = tm.idle_gaps(recorded, 2)
    assert gaps[0] == ("bench.dispatch", pytest.approx(0.001270087))
    assert gaps[1] == ("host.other", pytest.approx(0.001113076))


def test_recorded_ops_are_named_by_their_instruction(recorded):
    ops = tm.op_seconds(recorded)
    assert max(ops, key=ops.get) == "multiply_subtract_fusion"
    assert all(" " not in name and not name.startswith("%") for name in ops)


def test_recorded_pallas_kernels_are_found_by_name(recorded):
    kernels = tm.op_seconds(recorded, match=reg.is_kernel)
    assert len(kernels) == 10  # three pmatmul and two freq_outer calls, each with its transpose
    assert sum(1 for n, _, _ in recorded.ops["/device:TPU:0"] if reg.is_kernel(n)) == 30
    assert sum(kernels.values()) == pytest.approx(0.001221995)
