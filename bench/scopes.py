"""Device time of the train step's named parts, from a trace and the step's
compiled HLO.

The program traces its step under ``jax.named_scope`` names (documented in
``repro.obs.profiling``): ``encoder`` (backbone and projector, both views),
``loss`` (the decorrelation loss), ``regularizer`` (R(C), inside ``loss``) and
``optimizer``.  Each compiled instruction keeps the path of scopes it was
traced under as its ``op_name`` metadata, e.g.
``jit(train_step)/transpose(jvp(encoder))/dot_general`` for a backward matmul
of the encoder; a fusion carries its root's path, or the paths of what it
fused joined by ``;``.  A trace names each executed operation by its
instruction, so the compiled step's text maps the trace's operations to parts:

- an operation belongs to the innermost of the four scope names on the first
  path of its ``op_name`` (``regularizer`` wins over ``loss``), or to none;
- ``encoder`` under ``transpose(`` is the encoder's backward
  (``encoder.bwd``), otherwise its forward (``encoder.fwd``).

The step is compiled again from the cell's configuration and abstract shapes
(the cell's program module: ``build`` and ``batch_spec``), after the window
and past the persistent compile cache: the same module gives the same
instructions, and the metadata is this program's even where the window ran an
executable that the cache kept from a version without the scopes (the cache's
key leaves metadata out).  A program
without the scopes maps nothing, and every part then reads nothing.
"""

from __future__ import annotations

import json
import re

from bench import trace

ENCODER, LOSS, REGULARIZER, OPTIMIZER = "encoder", "loss", "regularizer", "optimizer"
SCOPES = (ENCODER, LOSS, REGULARIZER, OPTIMIZER)
ENCODER_FWD, ENCODER_BWD = "encoder.fwd", "encoder.bwd"
PARTS = (ENCODER_FWD, ENCODER_BWD, LOSS, REGULARIZER, OPTIMIZER)

_INSTRUCTION = re.compile(r'^\s*(?:ROOT\s+)?%?(\S+) = .*?\bop_name="((?:[^"\\]|\\.)*)"', re.M)
# transformations that wrap a scope in the path: jvp(encoder), transpose(jvp(loss))
_WRAPPER = re.compile(r"^(?:jvp|transpose|vmap)\((.*)\)$")


def op_names(hlo_text: str) -> dict:
    """Instruction name -> ``op_name``, for every instruction that has one."""
    return {m.group(1): m.group(2) for m in _INSTRUCTION.finditer(hlo_text)}


def part(op_name: str) -> str | None:
    """The part an operation with this ``op_name`` belongs to, or None."""
    path = op_name.split(";", 1)[0]
    found = None
    for component in path.split("/"):
        while (m := _WRAPPER.match(component)):
            component = m.group(1)
        if component in SCOPES:
            found = component
    if found == ENCODER:
        return ENCODER_BWD if "transpose(" in path else ENCODER_FWD
    return found


def part_seconds(tr: trace.Trace, names: dict) -> dict:
    """Per part, the device seconds of its operations inside the window
    (averaged over the devices); ``names`` maps instruction to ``op_name``.
    Operations of no part are summed under None."""
    out: dict = {}
    for name, s in trace.op_seconds(tr).items():
        p = part(names[name]) if name in names else None
        out[p] = out.get(p, 0.0) + s
    return out


def step_hlo(cell) -> str:
    """The optimized HLO text of the cell's jitted train step, compiled for
    the first device with the shapes the window's steps take: the state as a
    step returns it and a batch of the traffic's size."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    from bench import cells

    program = cells.program(cell)
    prog = program.build(cell.config, int(cell.traffic["batch"]))
    key = jax.random.PRNGKey(0)
    batch = program.batch_spec(cell.config, cell.traffic)
    state = jax.eval_shape(prog.step_fn, jax.eval_shape(prog.make_state, key, key), batch)[0]
    on_device = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    args = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=on_device,
                                                       weak_type=s.weak_type), (state, batch))
    # The persistent cache keys a module without its metadata: an executable
    # that another version of the program compiled would bring that version's
    # op names.  This compile reads the program's own.
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return prog.step.lower(*args).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)
        compilation_cache.reset_cache()


_NAMES: dict = {}


def step_op_names(cell) -> dict:
    """``op_names(step_hlo(cell))``, compiled once per configuration and batch."""
    key = json.dumps([cell.config, cell.traffic["batch"]], sort_keys=True)
    if key not in _NAMES:
        _NAMES[key] = op_names(step_hlo(cell))
    return _NAMES[key]


def part_ms(r, name: str) -> float | None:
    """Device ms per step of part ``name`` in a traced reading; None without a
    trace, or where the program's step carries no scopes or the part no time."""
    if r.trace is None or not r.trace.ops or r.steps == 0:
        return None
    names = step_op_names(r.cell)
    if not any(part(op) for op in names.values()):
        return None
    seconds = part_seconds(r.trace, names).get(name, 0.0)
    return 1e3 * seconds / r.steps if seconds > 0 else None
