"""A second program, unlike ``ssl_mlp`` in its batch, optimizer and loss, runs
through the harness by files alone.

The test writes a benchmark root of its own: a manifest, a configuration that
names its program, reference and traffic, the program module (a one-layer
token model: next-token cross-entropy on a batch of token ids, AdamW from
``repro.optim``, the logged loss ``ce_loss``), its plain reference, a token
generator, the traffic and the limits.  No file of the harness is edited, and
every module is found under that root.
"""

import json
import textwrap
import time

import pytest

from bench import cells, harness

SEED = 2**31 + 91
CELL = "toy-lm.tokens-b16"

CONFIG = {
    "name": "toy-lm", "source": "a one-layer token model written for this test",
    "program": "toy_lm", "reference": "toy_lm",
    "vocab": 64, "width": 16, "lr": 0.01,
    "optimizer": {"name": "adamw", "b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1},
    "init": "embed = normal(split(key)[0]), out = normal(split(key)[1]) / sqrt(width), bias = 0",
}
TRAFFIC = {"name": "tokens-b16", "batch": 16, "seq": 8, "feed": "device", "pool": 4, "generator": "tokens",
           "log_every": 5}
LIMITS = {"loss_gap": 1e-4, "grad_gap": 1e-3, "change_gap": 1e-3, "direction_gap": 1e-4}
MANIFEST = {
    "command": ["python3", "bench/run.py"], "paths": ["bench"], "run_seconds": 10,
    "configs": [{"name": "toy-lm", "source": CONFIG["source"], "file": "bench/configs/toy-lm.json", "reduced": [],
                 "why": "a second program"}],
    "workloads": [{"name": CELL, "config": "toy-lm", "traffic": "tokens-b16", "chips": 1, "why": "a second program"}],
    "end_to_end": [{"name": "samples_per_s", "unit": "samples/s", "better": "higher", "bound": 0.01,
                    "source": "host_clock"},
                   {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.25, "source": "host_clock"}],
    "per_layer": [],
}

PROGRAM = '''
from types import SimpleNamespace

import jax
import jax.numpy as jnp

from repro.optim import adamw
from repro.train import LoopConfig, create_train_state, run_training


def _loss(params, tokens):
    logits = params["embed"][tokens[:, :-1]] @ params["out"] + params["bias"]
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1))


def build(cfg, batch):
    o = cfg["optimizer"]
    opt = adamw(b1=o["b1"], b2=o["b2"], eps=o["eps"], weight_decay=o["weight_decay"])

    def step_fn(state, batch):
        loss, grads = jax.value_and_grad(_loss)(state.params, batch["tokens"])
        params, opt_state = opt.update(grads, state.opt_state, state.params, cfg["lr"])
        return state._replace(step=state.step + 1, params=params, opt_state=opt_state), {"ce_loss": loss}

    @jax.jit
    def make_state(key, aux_key):
        k_embed, k_out = jax.random.split(key)
        v, w = cfg["vocab"], cfg["width"]
        params = {"embed": jax.random.normal(k_embed, (v, w)), "out": jax.random.normal(k_out, (w, v)) / w**0.5,
                  "bias": jnp.zeros((v,))}
        return create_train_state(params, opt)._replace(rng=aux_key)

    return SimpleNamespace(step_fn=step_fn, step=jax.jit(step_fn), make_state=make_state, loss_key="ce_loss",
                           first_gradient=lambda state: state.opt_state["m"])


def batch_spec(cfg, traffic):
    return {"tokens": jax.ShapeDtypeStruct((traffic["batch"], traffic["seq"] + 1), jnp.int32)}


def step_flops(cfg, n):
    return 0


def regularizer_flops(cfg, n):
    return 0


def _with_step(prog, step):
    return SimpleNamespace(**dict(vars(prog), step=step))


def faults(prog, n):
    altered = lambda b: {"tokens": b["tokens"].at[0, -1].set((b["tokens"][0, -1] + 1) % 64)}
    return {"half_batch": _with_step(prog, lambda s, b: prog.step(s, {"tokens": b["tokens"][: n // 2]})),
            "token_altered": _with_step(prog, lambda s, b: prog.step(s, altered(b)))}


def tiny(cfg, traffic):
    return cfg, traffic
'''

REFERENCE = '''
import jax
import jax.numpy as jnp


def readings(cfg, batches, key, aux_key, *, dtype=jnp.float32, precision="highest"):
    o = cfg["optimizer"]
    k_embed, k_out = jax.random.split(key)
    v, w = cfg["vocab"], cfg["width"]
    p0 = {"embed": jax.random.normal(k_embed, (v, w)).astype(dtype),
          "out": (jax.random.normal(k_out, (w, v)) / w**0.5).astype(dtype), "bias": jnp.zeros((v,), dtype)}

    def loss(p, tokens):
        one_hot = jax.nn.one_hot(tokens[:, :-1], v, dtype=dtype)
        logits = jnp.dot(jnp.dot(one_hot, p["embed"], precision=precision), p["out"], precision=precision)
        logits = logits + p["bias"]
        logp = logits - jax.scipy.special.logsumexp(logits, axis=-1, keepdims=True)
        return -jnp.mean(jnp.sum(jax.nn.one_hot(tokens[:, 1:], v, dtype=dtype) * logp, axis=-1))

    params, m, s = p0, jax.tree.map(jnp.zeros_like, p0), jax.tree.map(jnp.zeros_like, p0)
    out = {"loss": []}
    for t, batch in enumerate(batches, start=1):
        value, g = jax.value_and_grad(loss)(params, batch["tokens"])
        m = jax.tree.map(lambda m, g: o["b1"] * m + (1 - o["b1"]) * g, m, g)
        s = jax.tree.map(lambda s, g: o["b2"] * s + (1 - o["b2"]) * g * g, s, g)

        def update(p, m, s):
            u = (m / (1 - o["b1"] ** t)) / (jnp.sqrt(s / (1 - o["b2"] ** t)) + o["eps"])
            if p.ndim >= 2:
                u = u + o["weight_decay"] * p
            return p - cfg["lr"] * u

        params = jax.tree.map(update, params, m, s)
        out["loss"].append(float(value))
        if t == 1:
            out["grad_raw"] = [float(jnp.linalg.norm(x)) for x in jax.tree.leaves(g)]
            out["grad"] = [float(jnp.linalg.norm(x)) for x in jax.tree.leaves(m)]
    out["delta"] = [(a - b).astype(jnp.float32) for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p0))]
    out["change"] = [float(jnp.linalg.norm(x)) for x in out["delta"]]
    return out
'''

GENERATOR = '''
import jax
import numpy as np


def device_pool(traffic, cfg, key):
    shape = (traffic["pool"], traffic["batch"], traffic["seq"] + 1)
    tokens = jax.random.randint(key, shape, 0, cfg["vocab"])
    return [{"tokens": t} for t in tokens]


def host_batch(traffic, cfg, seed, step):
    rng = np.random.default_rng([seed, step])
    return {"tokens": rng.integers(0, cfg["vocab"], (traffic["batch"], traffic["seq"] + 1), dtype=np.int32)}
'''


@pytest.fixture
def root(tmp_path):
    files = {
        "BENCHMARK.json": json.dumps(MANIFEST),
        "bench/configs/toy-lm.json": json.dumps(CONFIG),
        "bench/traffic/tokens-b16.json": json.dumps(TRAFFIC),
        f"bench/limits/{CELL}.json": json.dumps(LIMITS),
        "bench/programs/toy_lm.py": PROGRAM,
        "bench/refs/toy_lm.py": REFERENCE,
        "bench/gen/tokens.py": GENERATOR,
    }
    for name, text in files.items():
        (tmp_path / name).parent.mkdir(parents=True, exist_ok=True)
        (tmp_path / name).write_text(textwrap.dedent(text))
    return tmp_path


def _run(cell):
    return harness.run_cell(cell, SEED, 0.2, False, time.perf_counter())


def test_a_second_program_runs_by_files_alone(root):
    cell = cells.load_cell(CELL, root)
    assert cells.program(cell).__file__ == str(root / "bench" / "programs" / "toy_lm.py")
    r = _run(cell)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) == {"samples_per_s", "setup_s"}


@pytest.mark.parametrize("fault", ["half_batch", "token_altered"])
def test_the_second_programs_faults_are_not_correct(root, fault, monkeypatch):
    cell = cells.load_cell(CELL, root)
    module = cells.program(cell)
    real = module.build
    monkeypatch.setattr(module, "build", lambda cfg, n: module.faults(real(cfg, n), n)[fault])
    r = _run(cell)
    assert not r["correct"], r["checks"]
