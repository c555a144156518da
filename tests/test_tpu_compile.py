"""Compile the Pallas kernel families for a TPU v5e that is described, not
attached: what Mosaic refuses (unaligned slices, scalar stores to VMEM, too
much fast memory) fails here at no chip time.  Nothing runs; each test checks
that the compiled program holds the kernel (``tpu_custom_call``).

The topology is described inside module fixtures, never at import: only one
process at a time may load the TPU library, and the test workers all import
this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import pallas_utils


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def mosaic(topo):
    """The described chips, with the kernels lowered through Mosaic.

    The process backend is the CPU, which would pick the Pallas interpreter:
    lower the kernels for the described chip instead.  A TPU compile cannot be
    read back without a chip, so the persistent cache stays off around these
    compiles."""
    from jax.experimental.compilation_cache import compilation_cache

    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(pallas_utils, "interpret", lambda: False)
        jax.clear_caches()
        yield topo
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", cache_was_on)


@pytest.fixture(scope="module")
def one_chip(mosaic):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(mosaic.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_text(fn, *specs) -> str:
    return jax.jit(fn).lower(*specs).compile().as_text()


def _value_and_grads(loss):
    return jax.value_and_grad(loss, argnums=(0, 1))


N, D = 256, 8192


def test_grouped_sumvec_fwd_and_grad(one_chip):
    from repro.kernels.grouped_sumvec import ops as gops

    z = _spec((N, D), jnp.float32, one_chip)
    loss = lambda a, b: gops.r_sum_kernel(a, b, block_size=128, q=2, scale=float(N))
    assert "tpu_custom_call" in _compile_text(_value_and_grads(loss), z, z)


def test_sumvec_fft_fourstep_fwd_and_grad(one_chip):
    from repro.kernels.sumvec_fft import ops as fops

    z = _spec((N, D), jnp.float32, one_chip)
    loss = lambda a, b: fops.r_sum_fourstep(a, b, q=2, scale=float(N))
    assert "tpu_custom_call" in _compile_text(_value_and_grads(loss), z, z)


def test_xcorr_offdiag_fwd_and_grad(one_chip):
    from repro.kernels.xcorr_offdiag import ops as xops

    z = _spec((N, D), jnp.float32, one_chip)
    loss = lambda a, b: xops.off_diagonal_sq_sum(a, b, scale=float(N))
    assert "tpu_custom_call" in _compile_text(_value_and_grads(loss), z, z)


def test_paged_attention_decode_gemma2_2b_bf16(one_chip):
    from repro.configs import get_config
    from repro.kernels.paged_attention import ops as pops

    cfg = get_config("gemma2-2b")
    slots, page, max_len = 8, 32, 4096
    nb = max_len // page
    pages = slots * nb + 1  # + the scratch page
    q = _spec((slots, cfg.n_heads, cfg.head_dim), jnp.bfloat16, one_chip)
    kv = _spec((pages, page, cfg.n_kv_heads, cfg.head_dim), jnp.bfloat16, one_chip)
    bt = _spec((slots, nb), jnp.int32, one_chip)
    lens = _spec((slots,), jnp.int32, one_chip)
    decode = lambda q, k, v, bt, lens: pops.paged_decode_attention_raw(
        q, k, v, bt, lens, scale=cfg.head_dim**-0.5,
        softcap=cfg.attn_softcap, window=cfg.window_size,
    )
    assert "tpu_custom_call" in _compile_text(decode, q, kv, kv, bt, lens)


@pytest.mark.parametrize(
    "mode, shape, axes", [("global", (4,), ("data",)), ("tp", (2, 2), ("data", "model"))]
)
def test_sharded_ssl_step_on_four_chips(mosaic, mode, shape, axes):
    """The sharded SSL step of chip_smoke.py --four-chips, at a small width:
    the Pallas R_sum route must type-check and transpose under shard_map
    (varying-manual-axes checks on) and reduce across the chips."""
    import numpy as np
    from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

    from repro.decorr.config import DecorrConfig
    from repro.optim import lars, warmup_cosine
    from repro.train.ssl import SSLModelConfig, init_ssl_params, make_sharded_ssl_train_step
    from repro.tune import dispatch

    model = SSLModelConfig(input_dim=256, backbone_widths=(256,), projector_widths=(512, 512))
    cfg = DecorrConfig(style="bt", reg="sum", q=2, block_size=128, lam=2.0**-10,
                       permute=True, distributed=mode)
    mesh = Mesh(np.array(mosaic.devices).reshape(shape), axes,
                axis_types=(AxisType.Auto,) * len(axes))
    replicated = NamedSharding(mesh, P())
    params = jax.tree.map(
        lambda x: _spec(x.shape, x.dtype, replicated),
        jax.eval_shape(lambda: init_ssl_params(jax.random.PRNGKey(0), model)),
    )
    view = _spec((64, model.input_dim), jnp.float32, NamedSharding(mesh, P("data", None)))
    rng = _spec((2,), jnp.uint32, replicated)
    with dispatch.override("r_sum_grouped", impl="pallas"):
        _, loss_and_grads = make_sharded_ssl_train_step(
            model, cfg, lars(weight_decay=1e-4), warmup_cosine(0.2, 1, 5), mesh)
        hlo = _compile_text(loss_and_grads, params, {"view1": view, "view2": view}, rng)
    assert "tpu_custom_call" in hlo
    assert "all-reduce" in hlo
