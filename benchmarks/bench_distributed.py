"""Distributed decorrelation engine (DESIGN.md §4): per-mode SSL step time +
collective bytes from compiled HLO, on an 8-virtual-device subprocess.

Validates the beyond-paper claim: ``global`` mode upgrades every statistic in
the loss (moments, diagonal, frequency accumulator) to the exact global batch
for O(d) psum traffic — versus the O(n d) all-gather a naive global
implementation needs.  Emits ``BENCH_distributed.json``; CI gates that
``global`` mode's extra loss traffic stays O(d) (a handful of length-d
accumulator psums, NOT an n x d gather).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

from benchmarks.common import fmt_row

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_BODY = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import time

import jax
import jax.numpy as jnp
from functools import partial
from jax import shard_map
from jax.sharding import PartitionSpec as P
from repro.core import distributed as dist
from repro.core import regularizers as regs
from repro.core.losses import DecorrConfig, ssl_loss
from repro.launch.hlo_cost import analyze_hlo
from repro.launch.mesh import make_mesh
from repro.train.ssl import (SSLModelConfig, init_ssl_params,
                             make_sharded_ssl_train_step, shard_ssl_batch)
from repro.optim import adamw, warmup_cosine

out = {}

# ---- regularizer-level collective traffic (n, d) = (256, 2048) ----------
n, d = 256, 2048
out["reg"] = {"n": n, "d": d}
mesh = make_mesh((8,), ("data",))
z1 = jax.random.normal(jax.random.PRNGKey(0), (n, d))
z2 = jax.random.normal(jax.random.PRNGKey(1), (n, d))

local = shard_map(lambda a, b: regs.r_sum(a, b, q=2, scale=float(a.shape[0]))[None],
                  mesh=mesh, in_specs=(P("data"), P("data")), out_specs=P("data"))
out["reg"]["local_coll_bytes"] = analyze_hlo(
    jax.jit(local).lower(z1, z2).compile().as_text()).total_collective_bytes

glob = shard_map(lambda a, b: dist.r_sum_global(a, b, axis_name="data", q=2, scale=a.shape[0])[None],
                 mesh=mesh, in_specs=(P("data"), P("data")), out_specs=P())
out["reg"]["global_coll_bytes"] = analyze_hlo(
    jax.jit(glob).lower(z1, z2).compile().as_text()).total_collective_bytes
out["reg"]["global_value"] = float(glob(z1, z2)[0])
out["reg"]["exact_value"] = float(regs.r_sum(z1, z2, q=2, scale=n))

naive = shard_map(lambda a, b: regs.r_sum(
    jax.lax.all_gather(a, "data", tiled=True), jax.lax.all_gather(b, "data", tiled=True),
    q=2, scale=float(n))[None], mesh=mesh, in_specs=(P("data"), P("data")), out_specs=P("data"))
out["reg"]["naive_global_coll_bytes"] = analyze_hlo(
    jax.jit(naive).lower(z1, z2).compile().as_text()).total_collective_bytes

mesh2 = make_mesh((2, 4), ("data", "model"))
tp = shard_map(lambda a, b: dist.r_sum_tp(a, b, model_axis="model", batch_axis="data",
                                          q=2, scale=a.shape[0])[None],
               mesh=mesh2, in_specs=(P("data", "model"), P("data", "model")), out_specs=P())
out["reg"]["tp_coll_bytes"] = analyze_hlo(
    jax.jit(tp).lower(z1, z2).compile().as_text()).total_collective_bytes
out["reg"]["tp_value"] = float(tp(z1, z2)[0])

# ---- full SSL train step per engine mode --------------------------------
n_ssl, d_ssl = 128, 512
out["ssl"] = {"n": n_ssl, "d": d_ssl}
model = SSLModelConfig(input_dim=64, backbone_widths=(128,), projector_widths=(d_ssl, d_ssl))
params = init_ssl_params(jax.random.PRNGKey(0), model)
batch = {"view1": jax.random.normal(jax.random.PRNGKey(2), (n_ssl, 64)),
         "view2": jax.random.normal(jax.random.PRNGKey(3), (n_ssl, 64))}
rng = jax.random.PRNGKey(4)

for mode in ("local", "global", "tp"):
    m = make_mesh((8,), ("data",)) if mode != "tp" else make_mesh((2, 4), ("data", "model"))
    cfg = DecorrConfig(style="bt", reg="sum", q=2, block_size=128, distributed=mode)
    step, lag = make_sharded_ssl_train_step(model, cfg, adamw(), warmup_cosine(1e-3, 2, 10), m)
    sb = shard_ssl_batch(batch, m)

    # loss+grad collective bytes (the decorr engine's own traffic + grad reduce)
    lagj = jax.jit(lag)
    a = analyze_hlo(lagj.lower(params, sb, rng).compile().as_text())
    # forward-only loss traffic: grads dominate the step, so gate on this
    fwd = jax.jit(lambda p, b, r: lag(p, b, r)[0])
    af = analyze_hlo(fwd.lower(params, sb, rng).compile().as_text())

    loss, _, _ = lagj(params, sb, rng)
    t0 = time.time()
    for _ in range(3):
        loss, _, grads = lagj(params, sb, rng)
    jax.block_until_ready(grads)
    out[mode] = {
        "loss_fwd_coll_bytes": af.total_collective_bytes,
        "loss_grad_coll_bytes": a.total_collective_bytes,
        "step_us": (time.time() - t0) / 3 * 1e6,
        "loss": float(loss),
    }

# ---- O(d) gate: global's extra FORWARD loss traffic vs an n x d gather ---
extra = out["global"]["loss_fwd_coll_bytes"] - out["local"]["loss_fwd_coll_bytes"]
budget = 128 * d_ssl + 16384  # a handful of length-d psums (ring-counted)
gather = 2 * n_ssl * d_ssl * 4  # what all-gathering both views would move
out["gate"] = {"extra_bytes": extra, "budget_bytes": budget,
               "allgather_bytes": gather,
               "ok": bool(extra <= budget and extra < gather)}
print(json.dumps(out))
"""


def run():
    # the child counts HLO on virtual CPU devices: it must never claim a chip
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("XLA_FLAGS", None)
    code = textwrap.dedent(_BODY)
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, env=env, timeout=560
    )
    if proc.returncode != 0:
        raise RuntimeError(f"distributed child failed:\n{proc.stderr[-3000:]}")
    res = json.loads(proc.stdout.strip().splitlines()[-1])
    with open(os.path.join(os.getcwd(), "BENCH_distributed.json"), "w") as f:
        json.dump(res, f, indent=2, sort_keys=True)
    reg = res["reg"]
    rows = [
        fmt_row("distributed/local", 0.0, f"loss_collective_bytes={reg['local_coll_bytes']:.3g}"),
        fmt_row(
            "distributed/global", 0.0,
            f"loss_collective_bytes={reg['global_coll_bytes']:.3g};"
            f"value_err={abs(reg['global_value']-reg['exact_value']):.2e};"
            f"vs_naive_allgather={reg['naive_global_coll_bytes']/max(reg['global_coll_bytes'],1):.0f}x_less",
        ),
        fmt_row(
            "distributed/tp", 0.0,
            f"loss_collective_bytes={reg['tp_coll_bytes']:.3g};"
            f"value_err={abs(reg['tp_value']-reg['exact_value']):.2e}",
        ),
    ]
    for mode in ("local", "global", "tp"):
        m = res[mode]
        rows.append(fmt_row(
            f"distributed/ssl_step_{mode}", m["step_us"],
            f"fwd_coll_bytes={m['loss_fwd_coll_bytes']:.3g};"
            f"grad_coll_bytes={m['loss_grad_coll_bytes']:.3g}",
        ))
    g = res["gate"]
    rows.append(fmt_row(
        "distributed/gate_global_O(d)", 0.0,
        f"extra_bytes={g['extra_bytes']:.3g};budget={g['budget_bytes']:.3g};"
        f"allgather={g['allgather_bytes']:.3g};ok={g['ok']}",
    ))
    return rows


if __name__ == "__main__":
    print("\n".join(run()))
