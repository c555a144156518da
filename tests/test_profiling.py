"""The train step's named scopes and the train loop's profiler spans
(``repro.obs.profiling``), as a compiled step and a CPU trace show them."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.losses import DecorrConfig
from repro.obs import profiling
from repro.optim import lars, warmup_cosine
from repro.train import LoopConfig, create_train_state, run_training
from repro.train.ssl import SSLModelConfig, init_ssl_params, make_ssl_train_step

SCOPES = (profiling.ENCODER, profiling.LOSS, profiling.REGULARIZER, profiling.OPTIMIZER)
INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?(\S+) = .*?\s([\w-]+)\(")
OP_NAME = re.compile(r'\bop_name="((?:[^"\\]|\\.)*)"')
# what may run outside the four parts: the step counter, the learning-rate
# schedule and the permutation key's fold-in
OUTSIDE = re.compile(r"^jit\(train_step\)/(?:add$|jit\(_where\)/|jit\(_threefry_fold_in\)/)")
PLUMBING = {"parameter", "constant", "copy", "tuple", "get-tuple-element", "bitcast"}


def _entry(text):
    """(name, opcode, op_name) of each instruction of the ENTRY computation."""
    body = text[text.index("\nENTRY"):]
    body = body[body.index("{") + 1:body.index("\n}")]
    out = []
    for line in body.splitlines():
        m = INSTRUCTION.match(line)
        if m:
            op = OP_NAME.search(line)
            out.append((m.group(1), m.group(2), op.group(1) if op else ""))
    return out


def _scoped(op_name):
    return any(s in re.split(r"[/()]", op_name.split(";")[0]) for s in SCOPES)


def _tiny_step(style, reg):
    model = SSLModelConfig(input_dim=32, backbone_widths=(32,), projector_widths=(512, 512, 512))
    loss = DecorrConfig(style=style, reg=reg, q=2, block_size=32 if reg == "sum" else None,
                        lam=2.0**-10, permute=True)
    opt = lars(weight_decay=1.5e-6)
    step_fn, _ = make_ssl_train_step(model, loss, opt, warmup_cosine(0.2, 3, 30, 0.01))
    state = create_train_state(init_ssl_params(jax.random.PRNGKey(0), model), opt)
    batch = {"view1": jnp.ones((16, 32)), "view2": jnp.ones((16, 32))}
    return step_fn, state, batch


@pytest.mark.parametrize("style,reg", [("bt", "sum"), ("bt", "off"), ("vic", "sum")])
def test_every_part_of_the_compiled_step_is_scoped(style, reg):
    step_fn, state, batch = _tiny_step(style, reg)
    entry = _entry(jax.jit(step_fn).lower(state, batch).compile().as_text())
    stray = [(name, opcode, op) for name, opcode, op in entry
             if not _scoped(op) and opcode not in PLUMBING and not OUTSIDE.match(op)
             # the CPU splits reductions into reduce-window fusions that keep no metadata
             and not (name.startswith("wrapped_reduce-window") and not op)]
    assert not stray
    ops = [op for _, _, op in entry]
    assert any("/jvp(encoder)/" in op for op in ops)
    assert any("/transpose(jvp(encoder))/" in op for op in ops)
    assert any("/jvp(loss)/regularizer/" in op for op in ops)
    assert any("/transpose(jvp(loss))/regularizer/" in op for op in ops)
    assert any("/optimizer/" in op for op in ops)


def test_train_loop_spans_land_in_a_profile(tmp_path):
    """Four steps at log interval 2: one batch and one dispatch span per step,
    one sync and one publish span per log interval, inside a step annotation."""
    from jax.profiler import ProfileData

    step = jax.jit(lambda s, b: (s + jnp.sum(b), {"loss": jnp.sum(b)}))

    class State:
        def __init__(self, x):
            self.x = x

        @property
        def step(self):
            return 0

    def train_step(state, batch):
        x, m = step(state.x, batch)
        return State(x), m

    logged = []
    batches = lambda i: jnp.full((4,), float(i))
    train_step(State(jnp.zeros(())), batches(0))  # compile outside the capture
    jax.profiler.start_trace(str(tmp_path))
    run_training(State(jnp.zeros(())), train_step, batches, LoopConfig(total_steps=4, log_interval=2),
                 log_fn=lambda s, m: logged.append(s))
    jax.profiler.stop_trace()
    assert logged == [2, 4]
    (path,) = tmp_path.glob("**/*.xplane.pb")
    names = [ev.name for plane in ProfileData.from_file(str(path)).planes if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events]
    counts = {s: names.count(s) for s in (profiling.SPAN_BATCH, profiling.SPAN_DISPATCH, profiling.SPAN_SYNC,
                                          profiling.SPAN_PUBLISH, profiling.SPAN_CKPT)}
    assert counts == {profiling.SPAN_BATCH: 4, profiling.SPAN_DISPATCH: 4, profiling.SPAN_SYNC: 2,
                      profiling.SPAN_PUBLISH: 2, profiling.SPAN_CKPT: 0}
    assert names.count(profiling.STEP_NAME) == 4


def test_train_loop_checkpoint_span(tmp_path):
    from jax.profiler import ProfileData

    from repro.train.train_state import TrainState

    state = TrainState(jnp.zeros((), jnp.int32), {"w": jnp.ones((2,))}, {}, jax.random.PRNGKey(0))
    step = lambda s, b: (s._replace(step=s.step + 1), {"loss": jnp.zeros(())})
    jax.profiler.start_trace(str(tmp_path / "trace"))
    run_training(state, step, lambda i: np.zeros(1),
                 LoopConfig(total_steps=2, log_interval=10, ckpt_dir=str(tmp_path / "ckpt"), ckpt_interval=1))
    jax.profiler.stop_trace()
    (path,) = (tmp_path / "trace").glob("**/*.xplane.pb")
    names = [ev.name for plane in ProfileData.from_file(str(path)).planes if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events]
    assert names.count(profiling.SPAN_CKPT) == 3  # each step, then the final save
    assert names.count(profiling.SPAN_SYNC) == 0
