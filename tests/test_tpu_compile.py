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
import numpy as np
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


@pytest.mark.parametrize("q,one_view", [(1, False), (2, True), (1, True)])
def test_grouped_sumvec_q1_and_one_view(one_chip, q, one_view):
    """q=1 (the Gram's cotangent from the synthesis) and one view passed once
    (VICReg's R of one view: the one-view kernels)."""
    from repro.kernels.grouped_sumvec import ops as gops

    z = _spec((N, D), jnp.float32, one_chip)
    if one_view:
        fn = jax.value_and_grad(lambda a: gops.r_sum_kernel(a, None, block_size=128, q=q, scale=float(N)))
        text = _compile_text(fn, z)
    else:
        loss = lambda a, b: gops.r_sum_kernel(a, b, block_size=128, q=q, scale=float(N))
        text = _compile_text(_value_and_grads(loss), z, z)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("d,b,fits", [(8192, 1024, True), (8192, 2048, False), (16384, 128, False)])
def test_grouped_sumvec_routes_by_vmem(one_chip, d, b, fits):
    """The largest chunk basis the kernels take compiles within Mosaic's VMEM;
    a larger one, or rows too wide for a batch tile, take the jnp FFT route."""
    from repro.core import regularizers as regs
    from repro.kernels.grouped_sumvec import ops as gops

    assert gops.fits(d, b) == fits
    z = _spec((N, d), jnp.float32, one_chip)
    loss = lambda a, c: regs.r_sum_grouped(a, c, b, q=2, scale=float(N), impl="pallas")
    assert ("tpu_custom_call" in _compile_text(_value_and_grads(loss), z, z)) == fits


@pytest.mark.parametrize("n", [256, 2048])
def test_grouped_sumvec_moves_no_glue(one_chip, n):
    """The grouped R_sum's layout contract: between its Pallas calls nothing of
    the order of Z is relaid out.  No compiled instruction traced under
    ``jit(r_sum_kernel)``, other than the Mosaic calls (and the tuple elements
    that name their outputs), produces n * d / 8 elements or more."""
    import re

    from repro.kernels.grouped_sumvec import ops as gops

    z = _spec((n, D), jnp.float32, one_chip)
    loss = lambda a, b: gops.r_sum_kernel(a, b, block_size=128, q=2, scale=float(n))
    hlo = _compile_text(_value_and_grads(loss), z, z)
    glue, fused = [], False
    for line in hlo.splitlines():
        if line and not line[0].isspace():  # a computation's header
            fused = line.startswith("%fused")  # a fusion's body writes nothing
            continue
        m = re.match(r"\s*(?:ROOT\s+)?%?(\S+) = (.*?) ([\w-]+)\(", line)
        if fused or not m or "jit(r_sum_kernel)" not in line or "tpu_custom_call" in line:
            continue
        if m.group(3) in ("get-tuple-element", "tuple", "bitcast"):
            continue
        shapes = re.findall(r"\w+\[([\d,]*)\]", m.group(2))
        elements = max(int(np.prod([int(x) for x in s.split(",") if x])) for s in shapes)
        if elements >= n * D // 8:
            glue.append((m.group(1), m.group(3), m.group(2)))
    assert "tpu_custom_call" in hlo
    assert not glue, glue


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


def test_ssl_step_kernels_run_under_the_regularizer_scope(one_chip):
    """The grouped R_sum's Mosaic calls in the whole SSL step keep
    ``r_sum_kernel`` in their instruction names (the name a trace shows) and
    carry the program's ``regularizer`` scope, forward and backward."""
    import re

    from repro.decorr.config import DecorrConfig
    from repro.optim import lars, warmup_cosine
    from repro.train import create_train_state
    from repro.train.ssl import SSLModelConfig, init_ssl_params, make_ssl_train_step

    model = SSLModelConfig(input_dim=128, backbone_widths=(128,), projector_widths=(1024,))
    cfg = DecorrConfig(style="bt", reg="sum", q=2, block_size=128, lam=2.0**-10, permute=True,
                       use_kernel=True)
    opt = lars()
    step_fn, _ = make_ssl_train_step(model, cfg, opt, warmup_cosine(0.2, 3, 30))
    state = jax.tree.map(
        lambda x: _spec(x.shape, x.dtype, one_chip),
        jax.eval_shape(lambda: create_train_state(init_ssl_params(jax.random.PRNGKey(0), model), opt)),
    )
    view = _spec((64, model.input_dim), jnp.float32, one_chip)
    hlo = _compile_text(step_fn, state, {"view1": view, "view2": view})
    calls = [ln for ln in hlo.splitlines() if " custom-call(" in ln and "tpu_custom_call" in ln]
    names = [re.match(r"\s*%?(\S+) = ", ln).group(1) for ln in calls]
    assert calls and all("r_sum_kernel" in n for n in names)
    op_names = [re.search(r'op_name="([^"]*)"', ln).group(1) for ln in calls]
    assert all("/regularizer/jit(r_sum_kernel)/" in op for op in op_names)
    assert any(op.startswith("jit(train_step)/jvp(loss)/") for op in op_names)
    assert any(op.startswith("jit(train_step)/transpose(jvp(loss))/") for op in op_names)
