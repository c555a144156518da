"""Attribution of device time to the train step's named parts
(``bench/scopes.py``) and the per-layer metrics that read it."""

import pytest

from bench import cells, harness, scopes
from bench import trace as tm
from bench.tests.tiny import tiny_cell

READERS = ("encoder_fwd_ms", "encoder_bwd_ms", "reg_ms", "optimizer_ms", "reg_flops_share")
SUM_B256 = "bt-sum-d8192.dev-b256"

HLO = """\
ENTRY %main.9 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0), metadata={op_name="state.params"}
  %fusion.3 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fc.3, metadata={op_name="jit(train_step)/jvp(encoder)/dot_general" source_file="ssl.py" source_line=75}
  %copy-start = (f32[8]{0:S(1)}, f32[8]{0}, u32[]) copy-start(%p)
  ROOT %r_sum_kernel.2 = f32[8]{0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/transpose(jvp(loss))/regularizer/jit(r_sum_kernel)/pallas_call"}
}
"""


def test_op_names_reads_each_instruction_with_metadata():
    assert scopes.op_names(HLO) == {
        "p": "state.params",
        "fusion.3": "jit(train_step)/jvp(encoder)/dot_general",
        "r_sum_kernel.2": "jit(train_step)/transpose(jvp(loss))/regularizer/jit(r_sum_kernel)/pallas_call",
    }


@pytest.mark.parametrize("op_name,want", [
    ("jit(train_step)/jvp(encoder)/dot_general", "encoder.fwd"),
    ("jit(train_step)/transpose(jvp(encoder))/dot_general", "encoder.bwd"),
    ("jit(train_step)/jvp(loss)/sub", "loss"),
    ("jit(train_step)/transpose(jvp(loss))/regularizer/jit(r_sum_kernel)/pallas_call", "regularizer"),
    ("jit(train_step)/jvp(loss)/regularizer/jit(diagonal)/cond/branch_0_fun/gather", "regularizer"),
    ("jit(train_step)/optimizer/jit(norm)/sqrt", "optimizer"),
    # a fusion of several roots: the first path decides
    ("jit(train_step)/optimizer/sub;jit(train_step)/transpose(jvp(encoder))/dot_general", "optimizer"),
    ("jit(train_step)/jit(_where)/select_n", None),
    ("jit(train_step)/jit(encoder)/add", None),  # a function named like a scope is not one
    ("", None),
])
def test_part_is_the_innermost_scope(op_name, want):
    assert scopes.part(op_name) == want


NAMES = {
    "fwd": "jit(train_step)/jvp(encoder)/dot_general",
    "bwd": "jit(train_step)/transpose(jvp(encoder))/dot_general",
    "std": "jit(train_step)/jvp(loss)/div",
    "reg": "jit(train_step)/jvp(loss)/regularizer/dot_general",
    "reg_t": "jit(train_step)/transpose(jvp(loss))/regularizer/transpose",
    "lars": "jit(train_step)/optimizer/sub",
    "sched": "jit(train_step)/jit(_where)/select_n",
}


def _reading(names=NAMES, steps=2, cell=SUM_B256):
    # two steps in a 1 ms window; events in ns
    ops = {"/device:TPU:0": [("fwd", 0, 100_000), ("bwd", 100_000, 300_000), ("std", 300_000, 310_000),
                             ("reg", 310_000, 330_000), ("reg_t", 330_000, 370_000),
                             ("lars", 370_000, 470_000), ("sched", 470_000, 471_000),
                             ("unknown", 471_000, 472_000),
                             ("fwd", 500_000, 600_000), ("reg", 600_000, 640_000), ("lars", 990_000, 1_200_000)]}
    tr = tm.Trace(ops=ops, spans=[("bench.window", 0, 1_000_000)])
    r = harness.Reading(cells.load_cell(cell), "TPU v5 lite", steps, 1e-3, harness.Spans(False), tr, 0)
    return r


@pytest.fixture
def mapped(monkeypatch):
    names = dict(NAMES)
    monkeypatch.setattr(scopes, "step_op_names", lambda cell: names)
    return names


def test_part_seconds_sums_by_part_inside_the_window(mapped):
    got = scopes.part_seconds(_reading().trace, mapped)
    assert got == {"encoder.fwd": pytest.approx(200e-6), "encoder.bwd": pytest.approx(200e-6),
                   "loss": pytest.approx(10e-6), "regularizer": pytest.approx(100e-6),
                   "optimizer": pytest.approx(110e-6), None: pytest.approx(2e-6)}


def test_readers_give_ms_per_step(mapped):
    r = _reading()
    read = {name: cells.load_module("metrics", name).read(r) for name in READERS}
    assert read["encoder_fwd_ms"] == pytest.approx(0.1)
    assert read["encoder_bwd_ms"] == pytest.approx(0.1)
    assert read["reg_ms"] == pytest.approx(0.05)
    assert read["optimizer_ms"] == pytest.approx(0.055)


def test_reg_flops_share_against_a_hand_count(mapped):
    """Grouped R_sum at d=8192, b=128 (64 blocks, 65 frequencies), n=256: the
    two views' block DFTs forward and backward, 8 n d (b + 2) =
    2 181 038 080, and the cross-spectra forward and backward,
    24 n nb^2 nf = 1 635 778 560; in 0.05 ms a step, at 197 TFLOP/s."""
    flop = 8 * 256 * 8192 * 130 + 24 * 256 * 64 * 64 * 65
    assert flop == 3_816_816_640
    share = cells.load_module("metrics", "reg_flops_share").read(_reading())
    assert share == pytest.approx(100 * flop / 0.05e-3 / 197e12)


def test_reg_flops_share_counts_r_off(mapped):
    share = cells.load_module("metrics", "reg_flops_share").read(_reading(cell="bt-off-d8192.dev-b256"))
    assert share == pytest.approx(100 * 6 * 256 * 8192**2 / 0.05e-3 / 197e12)


def test_a_step_without_scopes_reads_nothing(monkeypatch):
    """The program before the scopes: its instructions carry no part."""
    plain = {k: v.replace("encoder", "embed").replace("loss", "ssl").replace("regularizer", "r")
             .replace("optimizer", "opt") for k, v in NAMES.items()}
    monkeypatch.setattr(scopes, "step_op_names", lambda cell: plain)
    r = _reading()
    assert all(cells.load_module("metrics", name).read(r) is None for name in READERS)


def test_no_trace_reads_nothing(mapped):
    r = _reading()
    r.trace = None
    assert all(cells.load_module("metrics", name).read(r) is None for name in READERS)


@pytest.mark.parametrize("cell", [SUM_B256, "bt-off-d8192.dev-b256"])
def test_the_abstract_compile_is_the_steps_own(cell):
    """``step_hlo`` compiles from shapes alone the instructions that the step
    run on real arrays compiles, as the window runs it (after a first step)."""
    import jax

    c = tiny_cell(cell)
    program = cells.program(c)
    prog = program.build(c.config, int(c.traffic["batch"]))
    key = jax.random.PRNGKey(3)
    spec = program.batch_spec(c.config, c.traffic)
    batch = {v: jax.random.normal(jax.random.fold_in(key, i), s.shape, s.dtype)
             for i, (v, s) in enumerate(spec.items())}
    state, _ = prog.step(prog.make_state(key, key), batch)
    real = scopes.op_names(prog.step.lower(state, batch).compile().as_text())
    abstract = scopes.op_names(scopes.step_hlo(c))
    assert abstract == real
    parts = {scopes.part(op) for op in abstract.values()}
    assert set(scopes.PARTS) <= parts


# A trace recorded on one TPU v5e: four steps of grouped R_sum (q=2, b=128,
# permuted) at d=1024, batch 64, over a 256 -> 256 backbone, driven by
# run_training at log interval 2 inside a bench.window span; beside it the op
# names of the instructions it ran, from the step's compiled HLO.

RECORDED = cells.BENCH / "tests" / "data" / "sum-d1024-b64-4steps"


@pytest.fixture(scope="module")
def recorded():
    import json

    return tm.load(str(RECORDED) + ".xplane.pb"), json.loads(open(str(RECORDED) + ".op_names.json").read())


def test_recorded_parts_hold_the_device_time(recorded):
    """The four parts hold 92% of the device's busy time at this size; the
    rest is the compiler's own copies and async slices, which carry no op
    name, and the permutation key's fold-in."""
    tr, names = recorded
    parts = scopes.part_seconds(tr, names)
    busy = tm.busy_seconds(tr)
    assert sum(parts.values()) == pytest.approx(busy)
    assert set(parts) == set(scopes.PARTS) | {None}
    assert sum(s for p, s in parts.items() if p) / busy == pytest.approx(0.91996, abs=1e-4)
    assert parts["regularizer"] > parts["optimizer"] > parts["encoder.bwd"] > parts["encoder.fwd"] > parts["loss"]


def test_recorded_pallas_kernels_are_regularizer_work(recorded):
    tr, names = recorded
    kernels = tm.op_seconds(tr, match=cells.load_module("metrics", "reg_kernels_ms").is_kernel)
    assert len(kernels) == 10  # three pmatmul and two freq_outer calls, each with its transpose
    assert {scopes.part(names[k]) for k in kernels} == {"regularizer"}


def test_recorded_loop_spans(recorded):
    """run_training's spans are in the host planes, on the device's clock."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(RECORDED) + ".xplane.pb")
    names = [ev.name for plane in data.planes if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events]
    assert [names.count(s) for s in ("train.batch", "train.dispatch", "train.sync", "train.publish")] == [4, 4, 2, 2]
    lo, hi = recorded[0].window()
    assert lo < min(s for evs in recorded[0].ops.values() for _, s, _ in evs)
