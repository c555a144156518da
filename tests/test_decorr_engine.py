"""Decorrelation-engine tests: mode routing, the tp misconfiguration guard,
and 8-virtual-device agreement of the shard_map SSL step with the
single-device oracle — losses AND gradients — across
{local, global, tp} x {bt, vic} x {q=1,2} x {grouped, ungrouped}.

Multi-device cases run in a SUBPROCESS with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` so the main pytest
process keeps its single CPU device."""

import json
import os
import subprocess
import sys
import textwrap

import jax
import pytest

from repro.core.losses import DecorrConfig, ssl_loss
from repro.decorr import engine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_in_subprocess(*parts: str, n_devices: int = 8) -> dict:
    code = textwrap.dedent(
        f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={n_devices}"
        import json
        import jax
        import jax.numpy as jnp
        import numpy as np
        """
    ) + "".join(textwrap.dedent(p) for p in parts)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, env=env, timeout=420
    )
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}\nstdout:\n{out.stdout[-1000:]}"
    return json.loads(out.stdout.strip().splitlines()[-1])


_COMMON = """
    from repro.core.losses import DecorrConfig, ssl_loss
    from repro.train.ssl import (SSLModelConfig, init_ssl_params, embed,
                                 make_sharded_ssl_train_step, shard_ssl_batch)
    from repro.optim import adamw, warmup_cosine
    from repro.train import create_train_state

    model = SSLModelConfig(input_dim=16, backbone_widths=(24,), projector_widths=(32, 32))
    params = init_ssl_params(jax.random.PRNGKey(0), model)
    k1, k2 = jax.random.split(jax.random.PRNGKey(7))
    batch = {"view1": jax.random.normal(k1, (32, 16)),
             "view2": jax.random.normal(k2, (32, 16))}
    rng = jax.random.PRNGKey(3)

    def oracle(cfg_local, params, batch, rng):
        def lf(p):
            return ssl_loss(embed(p, batch["view1"]), embed(p, batch["view2"]),
                            cfg_local, perm_key=rng)[0]
        l, g = jax.value_and_grad(lf)(params)
        return l, g

    def max_grad_err(ga, gb):
        pairs = zip(jax.tree.leaves(ga), jax.tree.leaves(gb))
        return max(float(jnp.max(jnp.abs(a - b))) for a, b in pairs)
"""


# ---------------------------------------------------------------------------
# Satellite regression: tp must not silently fall through to the local path
# ---------------------------------------------------------------------------


class TestTpMisconfigGuard:
    def test_ssl_loss_tp_without_model_axis_raises(self):
        z = jax.random.normal(jax.random.PRNGKey(0), (8, 16))
        cfg = DecorrConfig(style="bt", reg="sum", distributed="tp")
        with pytest.raises(ValueError, match="model_axis"):
            ssl_loss(z, z + 0.1, cfg)

    def test_regularizer_tp_without_model_axis_raises(self):
        z = jax.random.normal(jax.random.PRNGKey(0), (8, 16))
        cfg = DecorrConfig(style="vic", reg="sum", distributed="tp")
        with pytest.raises(ValueError, match="model_axis"):
            engine.regularizer(z, z, cfg, scale=7.0)

    def test_tp_with_model_axis_passes_validation(self):
        cfg = DecorrConfig(distributed="tp", model_axis="model")
        assert engine.effective_mode(cfg) == "tp"

    def test_tp_rejects_matrix_only_regs(self):
        # R_off / block_size<=1 need the cross-shard d x d matrix
        cfg = DecorrConfig(style="bt", reg="off", distributed="tp", model_axis="m")
        z = jax.random.normal(jax.random.PRNGKey(0), (8, 16))
        with pytest.raises(NotImplementedError):
            engine.regularizer(z, z, cfg, scale=8.0)


# ---------------------------------------------------------------------------
# Single-device shims: engine == historical local behavior
# ---------------------------------------------------------------------------


class TestLocalShims:
    def test_global_mode_without_axis_degrades_to_local(self):
        z1 = jax.random.normal(jax.random.PRNGKey(0), (16, 12))
        z2 = jax.random.normal(jax.random.PRNGKey(1), (16, 12))
        la, _ = ssl_loss(z1, z2, DecorrConfig(style="bt", distributed="local"),
                         jax.random.PRNGKey(2))
        lb, _ = ssl_loss(z1, z2, DecorrConfig(style="bt", distributed="global"),
                         jax.random.PRNGKey(2))
        assert abs(float(la) - float(lb)) < 1e-6

    def test_sharded_step_on_trivial_mesh_matches_unsharded(self):
        # a (1,)-device mesh exercises the shard_map plumbing end to end
        from repro.optim import adamw, warmup_cosine
        from repro.train import create_train_state
        from repro.train.ssl import (
            SSLModelConfig,
            init_ssl_params,
            make_sharded_ssl_train_step,
            make_ssl_train_step,
        )

        model = SSLModelConfig(input_dim=8, backbone_widths=(12,), projector_widths=(16, 16))
        cfg = DecorrConfig(style="bt", reg="sum", q=2, block_size=8, distributed="global")
        opt, sched = adamw(), warmup_cosine(1e-3, 1, 10)
        mesh = jax.make_mesh((1,), ("data",))
        step_s, _ = make_sharded_ssl_train_step(model, cfg, opt, sched, mesh)
        step_u, _ = make_ssl_train_step(
            model, DecorrConfig(style="bt", reg="sum", q=2, block_size=8), opt, sched
        )
        params = init_ssl_params(jax.random.PRNGKey(0), model)
        state = create_train_state(params, opt)
        batch = {
            "view1": jax.random.normal(jax.random.PRNGKey(1), (16, 8)),
            "view2": jax.random.normal(jax.random.PRNGKey(2), (16, 8)),
        }
        _, ms = jax.jit(step_s)(state, batch)
        _, mu = jax.jit(step_u)(state, batch)
        assert abs(float(ms["bt_loss"]) - float(mu["bt_loss"])) < 1e-5


# ---------------------------------------------------------------------------
# 8-device oracle agreement (losses + grads through the shard_map step)
# ---------------------------------------------------------------------------


def test_global_and_tp_sharded_step_match_single_device_oracle():
    res = run_in_subprocess(
        _COMMON,
        """
        errs = {}
        for style in ("bt", "vic"):
            for q in (1, 2):
                for block in (None, 8):
                    for mode in ("global", "tp"):
                        mesh = (jax.make_mesh((8,), ("data",)) if mode == "global"
                                else jax.make_mesh((2, 4), ("data", "model")))
                        cfg = DecorrConfig(style=style, reg="sum", q=q,
                                           block_size=block, distributed=mode)
                        _, lag = make_sharded_ssl_train_step(
                            model, cfg, adamw(), warmup_cosine(1e-3, 1, 10), mesh)
                        loss, metrics, grads = jax.jit(lag)(
                            params, shard_ssl_batch(batch, mesh), rng)
                        cfg_l = DecorrConfig(style=style, reg="sum", q=q,
                                             block_size=block, distributed="local")
                        lo, go = oracle(cfg_l, params, batch, rng)
                        key = f"{style}/q{q}/b{block}/{mode}"
                        errs[key] = [
                            abs(float(loss) - float(lo)) / max(abs(float(lo)), 1e-6),
                            max_grad_err(grads, go),
                        ]
        print(json.dumps(errs))
        """
    )
    for key, (loss_err, grad_err) in res.items():
        assert loss_err < 5e-4, (key, loss_err)
        assert grad_err < 5e-4, (key, grad_err)


def test_local_sharded_step_matches_per_shard_oracle():
    """DDP semantics: sharded 'local' loss/grads == mean over the 8 batch
    slices of the single-device loss/grads."""
    res = run_in_subprocess(
        _COMMON,
        """
        errs = {}
        mesh = jax.make_mesh((8,), ("data",))
        for style in ("bt", "vic"):
            for block in (None, 8):
                cfg = DecorrConfig(style=style, reg="sum", q=2,
                                   block_size=block, distributed="local")
                _, lag = make_sharded_ssl_train_step(
                    model, cfg, adamw(), warmup_cosine(1e-3, 1, 10), mesh)
                loss, metrics, grads = jax.jit(lag)(
                    params, shard_ssl_batch(batch, mesh), rng)
                n = batch["view1"].shape[0]
                losses, gsum = [], None
                for i in range(8):
                    sl = slice(i * n // 8, (i + 1) * n // 8)
                    sub = {k: v[sl] for k, v in batch.items()}
                    l, g = oracle(cfg, params, sub, rng)
                    losses.append(float(l))
                    gsum = g if gsum is None else jax.tree.map(jnp.add, gsum, g)
                want = sum(losses) / 8.0
                gmean = jax.tree.map(lambda x: x / 8.0, gsum)
                key = f"{style}/b{block}"
                errs[key] = [abs(float(loss) - want) / max(abs(want), 1e-6),
                             max_grad_err(grads, gmean)]
        print(json.dumps(errs))
        """
    )
    for key, (loss_err, grad_err) in res.items():
        assert loss_err < 5e-4, (key, loss_err)
        assert grad_err < 5e-4, (key, grad_err)


def test_vic_global_uses_global_moments():
    """Satellite regression: the VICReg 'global' variance hinge + centering
    must come from psum'd moments.  Build shards with wildly different local
    means — shard-local moments give a visibly different (wrong) loss."""
    res = run_in_subprocess(
        _COMMON,
        """
        from functools import partial
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        mesh = jax.make_mesh((8,), ("data",))
        n, d = 64, 12
        shift = jnp.repeat(jnp.arange(8.0), n // 8)[:, None] * 3.0
        z1 = jax.random.normal(jax.random.PRNGKey(0), (n, d)) + shift
        z2 = jax.random.normal(jax.random.PRNGKey(1), (n, d)) + shift
        cfg = DecorrConfig(style="vic", reg="sum", q=2, distributed="global",
                           axis_name="data", permute=False)

        @partial(shard_map, mesh=mesh, in_specs=(P("data"), P("data")), out_specs=P())
        def sharded(a, b):
            return ssl_loss(a, b, cfg)[0][None]

        got = float(jax.jit(sharded)(z1, z2)[0])
        cfg_l = DecorrConfig(style="vic", reg="sum", q=2, permute=False)
        want = float(ssl_loss(z1, z2, cfg_l)[0])
        # and what the old shard-local-moments bug would have computed
        locals_ = [float(ssl_loss(z1[i*8:(i+1)*8], z2[i*8:(i+1)*8], cfg_l)[0])
                   for i in range(8)]
        buggy = sum(locals_) / 8.0
        print(json.dumps({"got": got, "want": want, "buggy": buggy}))
        """
    )
    assert abs(res["got"] - res["want"]) < 1e-3 * max(abs(res["want"]), 1)
    assert abs(res["buggy"] - res["want"]) > 1e-2 * abs(res["want"])  # bug was visible


def test_regularizer_global_ddof_uses_exact_effective_scale():
    """engine.regularizer(ddof=1) must normalize by n_global - 1 (the LM aux
    path), not the historical (n_local - 1) * P."""
    res = run_in_subprocess(
        """
        from functools import partial
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from repro.core import regularizers as regs
        from repro.decorr import engine
        from repro.core.losses import DecorrConfig

        mesh = jax.make_mesh((8,), ("data",))
        n, d = 64, 16
        z = jax.random.normal(jax.random.PRNGKey(0), (n, d))
        zc = z - jnp.mean(z, axis=0, keepdims=True)
        cfg = DecorrConfig(style="vic", reg="sum", q=2, distributed="global",
                           axis_name="data", permute=False)

        @partial(shard_map, mesh=mesh, in_specs=(P("data"),), out_specs=P())
        def sharded(a):
            scale = float(max(a.shape[0] - 1, 1))
            return engine.regularizer(a, a, cfg, scale, ddof=1)[None]

        got = float(jax.jit(sharded)(zc)[0])
        want = float(regs.r_sum_auto(zc, zc, q=2, scale=float(n - 1)))
        legacy = float(regs.r_sum_auto(zc, zc, q=2, scale=float((n // 8 - 1) * 8)))
        print(json.dumps({"got": got, "want": want, "legacy": legacy}))
        """
    )
    assert abs(res["got"] - res["want"]) < 1e-3 * abs(res["want"])
    assert abs(res["legacy"] - res["want"]) > 1e-3 * abs(res["want"])


def test_sharded_train_step_updates_params():
    res = run_in_subprocess(
        _COMMON,
        """
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        cfg = DecorrConfig(style="bt", reg="sum", q=2, block_size=8, distributed="tp")
        step, _ = make_sharded_ssl_train_step(
            model, cfg, adamw(), warmup_cosine(1e-3, 1, 10), mesh, clip_norm=1.0)
        state = create_train_state(params, adamw())
        step = jax.jit(step)
        sb = shard_ssl_batch(batch, mesh)
        state1, m1 = step(state, sb)
        state2, m2 = step(state1, sb)
        delta = max(float(jnp.max(jnp.abs(a - b))) for a, b in
                    zip(jax.tree.leaves(state2.params), jax.tree.leaves(params)))
        print(json.dumps({"loss1": float(m1["bt_loss"]), "loss2": float(m2["bt_loss"]),
                          "step": int(state2.step), "delta": delta,
                          "finite": bool(jnp.isfinite(m2["bt_loss"]))}))
        """
    )
    assert res["finite"] and res["step"] == 2 and res["delta"] > 0.0
