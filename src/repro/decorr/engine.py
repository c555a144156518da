"""The decorrelation engine — the ONE place that routes decorrelation work.

``apply(z1, z2, cfg, perm_key)`` (and the style-specific ``barlow_twins`` /
``vicreg``) own, for every ``DecorrConfig``:

  * normalization — standardize (BT) / center (VICReg) with shard-local
    moments in ``local`` mode and psum'd global-batch moments in
    ``global``/``tp`` mode (two O(d) psums: mean, then centered variance);
  * feature permutation — one permutation per step, derived from the caller's
    ``perm_key`` identically on every shard; in ``tp`` mode it is applied to
    the full-feature rows *after* the all_to_all transpose so it equals the
    permutation a single-device run applies to the unsharded d;
  * mode routing — ``local | global | tp`` (see ``repro.decorr.modes``), with
    ``tp`` refusing to run without a ``model_axis`` instead of silently
    computing the shard-local loss;
  * impl routing — jnp vs Pallas via ``repro.tune`` (``use_kernel=True`` pins
    Pallas); kernels resolve their tile configs from the SHARD-LOCAL shapes
    they actually see inside shard_map;
  * scale bookkeeping — n vs n-1, local vs effective global batch, full vs
    shard-local feature width.

Everything in ``core/losses.py`` / ``core/decorrelation.py`` is a thin shim
over this module.  All distributed paths assume ``shard_map`` (axis names
bound by the caller, e.g. ``train/ssl.make_sharded_ssl_train_step``).
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import permutation as perm_lib
from repro.core import regularizers as regs
from repro.decorr import modes
from repro.decorr.config import DecorrConfig
from repro.obs import profiling

Array = jax.Array


def effective_mode(cfg: DecorrConfig) -> str:
    """'local' | 'global' | 'tp' — with the tp misconfiguration rejected.

    ``global`` with no ``axis_name`` is the local computation, so it degrades
    quietly.  ``tp`` with no ``model_axis`` would silently compute the wrong
    (shard-local) loss, so it raises instead.
    """
    if cfg.distributed == "tp" and cfg.model_axis is None:
        raise ValueError(
            "DecorrConfig(distributed='tp') requires model_axis (the mesh axis "
            "the feature dim is sharded over); refusing to fall back to the "
            "shard-local loss. Set model_axis or use distributed='local'/'global'."
        )
    return cfg.mode


def _batch_axis(cfg: DecorrConfig, mode: str) -> Optional[str]:
    return cfg.axis_name if mode in ("global", "tp") else None


# ---------------------------------------------------------------------------
# Normalization + moment statistics (local vs psum'd global moments)
# ---------------------------------------------------------------------------


def _mean_and_n(z: Array, batch_axis: Optional[str]) -> Tuple[Array, Array]:
    z = z.astype(jnp.float32)
    s1 = modes.psum_if(jnp.sum(z, axis=0), batch_axis)
    n = modes.effective_batch(z.shape[0], batch_axis)
    return s1 / n, n


def standardize(z: Array, cfg: DecorrConfig, mode: Optional[str] = None) -> Array:
    """Per-feature zero-mean unit-std over the (mode-effective) batch."""
    batch_axis = _batch_axis(cfg, mode or effective_mode(cfg))
    mean, n = _mean_and_n(z, batch_axis)
    zc = z.astype(jnp.float32) - mean
    var = modes.psum_if(jnp.sum(zc * zc, axis=0), batch_axis) / n
    return zc / jnp.sqrt(var + cfg.eps)


def center(z: Array, cfg: DecorrConfig, mode: Optional[str] = None) -> Array:
    """Per-feature zero-mean over the (mode-effective) batch."""
    batch_axis = _batch_axis(cfg, mode or effective_mode(cfg))
    mean, _ = _mean_and_n(z, batch_axis)
    return z.astype(jnp.float32) - mean


def variance_hinge(
    z: Array, cfg: DecorrConfig, mode: str, eps: float = 1e-4
) -> Array:
    """VICReg Eq. (4) hinge from ddof-1 moments of the effective batch,
    summed over ALL features (psum over the model axis in tp mode)."""
    batch_axis = _batch_axis(cfg, mode)
    mean, n = _mean_and_n(z, batch_axis)
    zc = z.astype(jnp.float32) - mean
    var = modes.psum_if(jnp.sum(zc * zc, axis=0), batch_axis) / max(n - 1.0, 1.0)
    hinge = jnp.sum(jnp.maximum(0.0, cfg.gamma - jnp.sqrt(var + eps)))
    if mode == "tp":
        hinge = jax.lax.psum(hinge, cfg.model_axis)
    return hinge


# ---------------------------------------------------------------------------
# Regularizer routing (mode x impl x grouped/ungrouped x q)
# ---------------------------------------------------------------------------


def _maybe_permute(z1: Array, z2: Array, cfg: DecorrConfig, perm_key) -> Tuple[Array, Array]:
    if cfg.permute and perm_key is not None and cfg.reg == "sum":
        return perm_lib.permute_views(perm_key, z1, z2)
    return z1, z2


def _impl(cfg: DecorrConfig) -> Optional[str]:
    # None defers to repro.tune.best_impl at the call site
    return "pallas" if cfg.use_kernel else None


def _local_regularizer(z1: Array, z2: Array, cfg: DecorrConfig, scale: float, perm_key) -> Array:
    if cfg.reg == "off":
        if cfg.use_kernel:
            from repro.kernels.xcorr_offdiag import ops as xops

            return xops.off_diagonal_sq_sum(z1, z2, scale=scale)
        return regs.r_off(regs.cross_correlation_matrix(z1, z2, scale=scale))
    z1, z2 = _maybe_permute(z1, z2, cfg, perm_key)
    return regs.r_sum_auto(
        z1, z2, q=cfg.q, block_size=cfg.block_size, scale=scale, impl=_impl(cfg)
    )


def _global_regularizer(z1: Array, z2: Array, cfg: DecorrConfig, total_scale, perm_key) -> Array:
    if cfg.reg == "off":
        return modes.r_off_global(z1, z2, axis_name=cfg.axis_name, total_scale=total_scale)
    z1, z2 = _maybe_permute(z1, z2, cfg, perm_key)
    b, d = cfg.block_size, z1.shape[-1]
    if b is not None and b <= 1 and b < d:
        # R_sum^(1): exactly the off-diagonal penalty (paper §4.4) — matrix
        # route on the psum'd correlation accumulator.
        c = z1.astype(jnp.float32).T @ z2.astype(jnp.float32)
        c = modes.psum_if(c, cfg.axis_name) / jnp.asarray(total_scale, jnp.float32)
        if cfg.q == 2:
            return regs.r_off(c)
        return jnp.sum(jnp.abs(c)) - jnp.sum(jnp.abs(jnp.diagonal(c)))
    return modes.r_sum_from_psummed(
        z1, z2, cfg.axis_name, q=cfg.q, block_size=b, total_scale=total_scale, impl=_impl(cfg)
    )


def _tp_regularizer(z1: Array, z2: Array, cfg: DecorrConfig, total_scale, perm_key) -> Array:
    if cfg.reg == "off" or (cfg.block_size is not None and cfg.block_size <= 1):
        raise NotImplementedError(
            "tp mode supports the R_sum family only (reg='sum', block_size > 1): "
            "the baseline R_off needs the cross-shard d x d matrix."
        )
    same = z1 is z2
    z1f = modes.all_to_all_features(z1.astype(jnp.float32), cfg.model_axis)
    z2f = z1f if same else modes.all_to_all_features(z2.astype(jnp.float32), cfg.model_axis)
    if cfg.permute and perm_key is not None:
        z1f, z2f = perm_lib.permute_views(perm_key, z1f, z2f)
    d = z1f.shape[-1]
    g = modes.frequency_accumulator(z1f, z2f, cfg.block_size, impl=_impl(cfg))
    g = jax.lax.psum(g, cfg.model_axis)
    g = modes.psum_if(g, cfg.axis_name)
    g = g / jnp.asarray(total_scale, jnp.float32).astype(g.dtype)
    if cfg.block_size is None or cfg.block_size >= d:
        return modes.reg_from_freq(g, d, cfg.q)
    return modes.grouped_reg_from_freq(g, int(cfg.block_size), cfg.q)


def _route(z1: Array, z2: Array, cfg: DecorrConfig, mode: str, scale, perm_key) -> Array:
    """R(C) by mode, under the profiler's regularizer scope: every route of
    every loss passes here."""
    with jax.named_scope(profiling.REGULARIZER):
        if mode == "local":
            return _local_regularizer(z1, z2, cfg, float(scale), perm_key)
        if mode == "global":
            return _global_regularizer(z1, z2, cfg, scale, perm_key)
        return _tp_regularizer(z1, z2, cfg, scale, perm_key)


def regularizer(
    z1: Array,
    z2: Array,
    cfg: DecorrConfig,
    scale,
    perm_key: Optional[Array] = None,
    *,
    ddof: Optional[int] = None,
) -> Array:
    """Mode/impl-routed decorrelating term R(C).

    ``scale`` is the LOCAL normalizer of C (n_local or n_local - 1).  With
    ``ddof=None`` the ``global``/``tp`` modes multiply it by the batch-axis
    size (the historical ``r_sum_global`` semantics); passing ``ddof``
    instead normalizes by the EXACT effective-batch scale
    max(n_global - ddof, 1), matching a single-device run on the
    concatenated batch (ddof=0: BT-style n; ddof=1: VICReg-style n - 1).
    Permutation is applied inside, mode-correctly — callers must NOT
    pre-permute.
    """
    mode = effective_mode(cfg)
    if mode == "local":
        return _route(z1, z2, cfg, mode, scale, perm_key)
    if ddof is None:
        total = float(scale) * (
            modes.effective_batch(1, cfg.axis_name) if cfg.axis_name else 1.0
        )
    else:
        n_eff = modes.effective_batch(z1.shape[0], _batch_axis(cfg, mode))
        total = max(n_eff - float(ddof), 1.0)
    return _route(z1, z2, cfg, mode, total, perm_key)


# ---------------------------------------------------------------------------
# Full losses (paper Eq. 14 / Eq. 15), mode-correct end to end
# ---------------------------------------------------------------------------


def barlow_twins(
    z1: Array,
    z2: Array,
    cfg: DecorrConfig,
    perm_key: Optional[Array] = None,
) -> Tuple[Array, Dict[str, Array]]:
    """Eq. (14) with mode-correct statistics: in ``global``/``tp`` mode every
    term (standardization moments, diagonal, regularizer, n) matches a
    single-device run on the concatenated, unsharded batch."""
    cfg.validate()
    mode = effective_mode(cfg)
    batch_axis = _batch_axis(cfg, mode)
    n_local = z1.shape[0]

    z1n = standardize(z1, cfg, mode)
    z2n = standardize(z2, cfg, mode)

    # Diagonal (invariance) term: C_ii in O(n d) — additive over batch shards
    # (psum over the batch axis) and over feature shards (psum over model).
    n_eff = modes.effective_batch(n_local, batch_axis)
    cii = modes.psum_if(jnp.sum(z1n * z2n, axis=0), batch_axis) / n_eff
    invariance = jnp.sum((1.0 - cii) ** 2)
    if mode == "tp":
        invariance = jax.lax.psum(invariance, cfg.model_axis)

    reg = _route(z1n, z2n, cfg, mode, n_local if mode == "local" else n_eff, perm_key)

    loss = invariance + cfg.lam * reg
    return loss, {"bt_invariance": invariance, "bt_reg": reg, "bt_loss": loss}


def vicreg(
    z1: Array,
    z2: Array,
    cfg: DecorrConfig,
    perm_key: Optional[Array] = None,
) -> Tuple[Array, Dict[str, Array]]:
    """Eq. (15) with mode-correct statistics (psum'd mean/variance in
    ``global`` mode — the shard-local variance hinge was a bug)."""
    cfg.validate()
    mode = effective_mode(cfg)
    batch_axis = _batch_axis(cfg, mode)
    n_local, d_local = z1.shape
    z1 = z1.astype(jnp.float32)
    z2 = z2.astype(jnp.float32)

    # invariance: before centering (paper Eq. 3 uses raw embeddings)
    inv = jnp.sum((z1 - z2) ** 2)
    if mode == "tp":
        inv = jax.lax.psum(inv, cfg.model_axis)
    n_eff = modes.effective_batch(n_local, batch_axis)
    inv = modes.psum_if(inv, batch_axis) / n_eff

    var1 = variance_hinge(z1, cfg, mode)
    var2 = variance_hinge(z2, cfg, mode)

    c1 = center(z1, cfg, mode)
    c2 = center(z2, cfg, mode)
    scale = float(max(n_local - 1, 1)) if mode == "local" else max(n_eff - 1.0, 1.0)
    reg1 = _route(c1, c1, cfg, mode, scale, perm_key)
    reg2 = _route(c2, c2, cfg, mode, scale, perm_key)

    d_full = float(d_local)
    if mode == "tp":
        d_full = d_full * modes.effective_batch(1, cfg.model_axis)

    loss = (
        cfg.alpha * inv
        + (cfg.mu / d_full) * (var1 + var2)
        + (cfg.nu / d_full) * (reg1 + reg2)
    )
    return loss, {
        "vic_invariance": inv,
        "vic_var": var1 + var2,
        "vic_reg": reg1 + reg2,
        "vic_loss": loss,
    }


def apply(
    z1: Array,
    z2: Array,
    cfg: DecorrConfig,
    perm_key: Optional[Array] = None,
) -> Tuple[Array, Dict[str, Array]]:
    """The engine entry point: full SSL loss for ``cfg.style``, under the
    profiler's loss scope."""
    with jax.named_scope(profiling.LOSS):
        if cfg.style == "bt":
            return barlow_twins(z1, z2, cfg, perm_key)
        return vicreg(z1, z2, cfg, perm_key)
