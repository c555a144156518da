"""One run of one cell: set-up, the first steps that ``correct`` compares, the
measured window, the reference, and the result line's contents.

The harness reaches the model only through the cell's program module
(``bench/programs/<program>.py``; ``bench/programs/__init__.py`` says what one
provides), and the traffic's generator gives batches in the form that program
takes.  Set-up builds the program's jitted step and its state from the seed,
makes the traffic, and drives the first ``FIRST_STEPS`` steps through
``run_training`` with the window's own step and batch calls (which compiles
the step).  The same state then runs on in the window, in chunks of the
traffic's ``log_every`` steps, until ``seconds`` have passed;
``block_until_ready`` on the state ends it.  Once the window is closed and
the peak memory read, the program's state is dropped and the plain reference
replays the first steps.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
import shutil
import sys
import tempfile
import time

from bench import cells, correct
from bench import trace as trace_mod

FIRST_STEPS = 3
TOP = 10


def peak_bytes() -> int:
    """The device allocator's peak of bytes in use so far in this process."""
    import jax

    return (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use", 0)


def seed_key(seed: int):
    """A PRNG key that depends on every bit of a 64-bit seed."""
    import jax

    seed %= 2**64
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), seed >> 32)


class Spans:
    """The harness's host spans around the calls into the program: seconds and
    calls per span name; with ``annotate``, also profiler annotations."""

    def __init__(self, annotate: bool):
        self.annotate = annotate
        self.reset()

    def reset(self) -> None:
        self.seconds: dict = {}
        self.calls: dict = {}

    def annotation(self, name: str):
        if not self.annotate:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(trace_mod.SPAN_PREFIX + name)

    def wrap(self, name: str, fn):
        def timed(*args):
            t = time.perf_counter()
            with self.annotation(name):
                out = fn(*args)
            self.seconds[name] = self.seconds.get(name, 0.0) + time.perf_counter() - t
            self.calls[name] = self.calls.get(name, 0) + 1
            return out

        return timed


@dataclasses.dataclass
class Reading:
    """What a per-layer metric's ``read(r)`` gets."""

    cell: cells.Cell
    device_kind: str
    steps: int
    window_s: float
    spans: Spans
    trace: trace_mod.Trace | None
    step_peak_bytes: int


def _traffic(cell: cells.Cell, seed: int, key):
    """``batch_fn(step)`` of the cell's feed; a host-rendered batch is copied to
    the device inside the call."""
    import jax
    import jax.numpy as jnp

    gen = cells.load_module("gen", cell.traffic["generator"], cell.root)
    if cell.traffic["feed"] == "device":
        pool = gen.device_pool(cell.traffic, cell.config, key)
        return lambda step: pool[step % len(pool)]
    if cell.traffic["feed"] == "host":
        return lambda step: jax.tree.map(jnp.asarray, gen.host_batch(cell.traffic, cell.config, seed, step))
    raise ValueError(f"unknown feed {cell.traffic['feed']!r}")


class Run:
    """A cell's program, state and traffic for one seed, with the calls the
    window makes: ``train(total, log_every)`` advances ``state`` through the
    program module's ``run_training`` with the spans' step and batch calls.
    ``prog`` is what the module's ``build`` returns."""

    def __init__(self, cell: cells.Cell, seed: int, prog, spans: Spans):
        import jax

        self.cell, self.seed, self.prog, self.spans = cell, seed % 2**64, prog, spans
        self.module = cells.program(cell)
        key = seed_key(seed)
        self.k_w, self.k_aux, k_data = (jax.random.fold_in(key, i) for i in range(3))
        self.state = prog.make_state(self.k_w, self.k_aux)
        self.batch_fn = _traffic(cell, self.seed, k_data)
        self.call_step = spans.wrap("dispatch", prog.step)
        self.call_batch = spans.wrap("batch", self.batch_fn)
        self.loss_key = prog.loss_key
        self.logged: list = []

    def train(self, total: int, log_every: int) -> None:
        def log_fn(_step, m):
            self.logged.append(m[self.loss_key])

        cfg_loop = self.module.LoopConfig(total_steps=total, log_interval=log_every)
        self.state = self.module.run_training(self.state, self.call_step, self.call_batch, cfg_loop, log_fn=log_fn)

    def first_steps(self) -> dict:
        """Drive the first ``FIRST_STEPS`` steps (step 0 compiles); return the
        program's readings that ``correct`` compares.  Each of these steps is
        synced, so ``step_peak`` is the memory training needs without the
        window's run-ahead: the state held by ``run_training``'s caller, the
        state a step takes and the one it returns, temporaries and traffic."""
        self.train(1, 1)
        grad = [float(x) for x in correct.leaf_norms(self.prog.first_gradient(self.state))]
        self.train(FIRST_STEPS, 1)
        self.step_peak = peak_bytes()
        p0 = self.prog.make_state(self.k_w, self.k_aux).params  # the same executable: the same bits
        delta = correct.change(self.state.params, p0)
        del p0
        change = [float(x) for x in correct.leaf_norms(delta)]
        read = {"loss": list(self.logged), "grad": grad, "change": change, "delta": delta}
        self.logged.clear()
        self.spans.reset()
        return read

    def reference(self, **kw) -> dict:
        """The plain reference's readings over the same first batches."""
        batches = [self.batch_fn(s) for s in range(FIRST_STEPS)]
        ref = cells.load_module("refs", self.cell.config["reference"], self.cell.root)
        return ref.readings(self.cell.config, batches, self.k_w, self.k_aux, **kw)


def run_cell(cell: cells.Cell, seed: int, seconds: float, trace: bool, t0: float) -> dict:
    """One run; ``t0`` is the process's start on ``time.perf_counter``."""
    import jax

    n, chunk = int(cell.traffic["batch"]), int(cell.traffic["log_every"])
    spans = Spans(annotate=trace)
    marks = [time.perf_counter()]  # set-up's phases, for the log
    run = Run(cell, seed, cells.program(cell).build(cell.config, n), spans)
    marks.append(time.perf_counter())
    prog_read = run.first_steps()
    marks.append(time.perf_counter())

    tmp = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        jax.profiler.start_trace(tmp)
    t_start = time.perf_counter()
    setup_s = t_start - t0
    steps = 0
    ends = []  # each chunk's end on the window's clock, for the log
    with spans.annotation("window"):
        while True:
            run.train(int(run.state.step) + chunk, chunk)
            steps += chunk
            ends.append(time.perf_counter() - t_start)
            if ends[-1] >= seconds:
                break
        jax.block_until_ready(run.state)
    window_s = time.perf_counter() - t_start
    if trace:
        jax.profiler.stop_trace()
    ms = [round(1e3 * (b - a) / chunk, 3) for a, b in zip([0.0] + ends, ends)]
    phases = [round(b - a, 3) for a, b in zip([t0] + marks, marks + [t_start])]
    print(f"bench: setup_s {setup_s:.3f} (start to harness, build state and traffic, first steps, "
          f"profiler: {phases}); ms per step by chunk of {chunk}: {ms}", file=sys.stderr)

    dev = jax.devices()[0]
    peak = peak_bytes()
    failed = chunk * sum(1 for v in run.logged if not math.isfinite(float(v)))
    run.state = run.prog = None  # the program's state is freed before the reference runs

    nums = correct.numbers(prog_read, run.reference())
    ok, checks = correct.verdict(nums, cell.limits)
    checks["failed_steps"] = {"value": failed, "limit": 0}

    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices()),
              "memory_peak_bytes": peak}
    result = {"correct": bool(ok and failed == 0), "attempted": steps, "failed": failed}
    if trace:
        tr = trace_mod.load(trace_mod.find_xplane(tmp))
        shutil.rmtree(tmp, ignore_errors=True)
        device["busy_s"] = trace_mod.busy_seconds(tr)
        device["window_s"] = trace_mod.window_seconds(tr)
        ops = sorted(trace_mod.op_seconds(tr).items(), key=lambda kv: -kv[1])[:TOP]
        result["breakdown"] = {"device_ops": [list(kv) for kv in ops],
                               "idle_gaps": [list(g) for g in trace_mod.idle_gaps(tr, TOP)]}
        reading = Reading(cell, dev.device_kind, steps, window_s, spans, tr, run.step_peak)
        metrics = {}
        for m in cell.per_layer:
            value = cells.load_module("metrics", m["name"], cell.root).read(reading)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        # every end-to-end metric in samples/s is the window's rate, named per kind of feed
        e2e = {"s": setup_s, "samples/s": steps * n / window_s}
        metrics = {m["name"]: {"value": e2e[m["unit"]], "unit": m["unit"]} for m in cell.end_to_end}
    result.update(metrics=metrics, device=device, checks=checks)
    return result
