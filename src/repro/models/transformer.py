"""Generic decoder stack over a repeating block pattern.

One ``lax.scan`` over pattern repetitions (stacked params => small HLO even
at 96 layers / 512-way SPMD) with optional remat; heterogeneous patterns
(gemma2 local/global, jamba mamba/attn/MoE) apply their pattern positions
sequentially inside the scan body.

Modes (all through ``forward``):
  * train/score:   caches=None — full-sequence causal forward
  * prefill:       caches given, S > 1 — fills caches, returns logits + caches
  * decode:        caches given, S == 1 — one-token step at ``cache_len``
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn_lib
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models.common import (
    ArchConfig,
    BlockSpec,
    dense_init,
    init_rms_norm,
    mlp_apply,
    mlp_init,
    rms_norm,
    softcap,
)
from repro.parallel.sharding import lookup, shard

Array = jax.Array


class ModelOutput(NamedTuple):
    logits: Array
    hidden: Array  # final hidden states (pre-head) — decorrelation target
    caches: Optional[Any]
    aux: Dict[str, Array]


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _block_init(key: Array, cfg: ArchConfig, spec: BlockSpec) -> Dict[str, Any]:
    ks = jax.random.split(key, 4)
    p: Dict[str, Any] = {
        "norm1": init_rms_norm(cfg.d_model, cfg.param_dtype),
        "norm2": init_rms_norm(cfg.d_model, cfg.param_dtype),
    }
    if cfg.post_block_norm:
        p["post_norm1"] = init_rms_norm(cfg.d_model, cfg.param_dtype)
        p["post_norm2"] = init_rms_norm(cfg.d_model, cfg.param_dtype)
    if spec.mixer == "attn":
        p["attn"] = attn_lib.attn_init(ks[0], cfg)
    elif spec.mixer == "mamba":
        p["mamba"] = ssm_lib.mamba_init(ks[0], cfg)
    elif spec.mixer == "rwkv":
        p["rwkv"] = ssm_lib.rwkv_init(ks[0], cfg)
    if spec.ffn == "dense":
        p["mlp"] = mlp_init(ks[1], cfg)
    elif spec.ffn == "moe":
        p["moe"] = moe_lib.moe_init(ks[1], cfg)
    return p


def init_params(key: Array, cfg: ArchConfig) -> Dict[str, Any]:
    ks = jax.random.split(key, 4 + len(cfg.pattern))
    params: Dict[str, Any] = {}
    if cfg.frontend == "audio_codes":
        params["embed"] = (
            jax.random.normal(ks[0], (cfg.n_codebooks, cfg.vocab_size, cfg.d_model), jnp.float32)
            * 0.02
        ).astype(cfg.param_dtype)
        params["heads"] = dense_init(ks[1], cfg.d_model, cfg.n_codebooks * cfg.vocab_size, cfg.param_dtype)
    else:
        params["embed"] = (
            jax.random.normal(ks[0], (cfg.vocab_size, cfg.d_model), jnp.float32) * 0.02
        ).astype(cfg.param_dtype)
        if not cfg.tie_embeddings:
            params["lm_head"] = dense_init(ks[1], cfg.d_model, cfg.vocab_size, cfg.param_dtype)
    params["final_norm"] = init_rms_norm(cfg.d_model, cfg.param_dtype)

    # stacked per-pattern-position block params: leaves (repeats, ...)
    blocks = {}
    for pos, spec in enumerate(cfg.pattern):
        rep_keys = jax.random.split(ks[4 + pos], cfg.repeats)
        blocks[f"pos{pos}"] = jax.vmap(lambda k: _block_init(k, cfg, spec))(rep_keys)
    params["blocks"] = blocks
    return params


# ---------------------------------------------------------------------------
# Caches / recurrent state
# ---------------------------------------------------------------------------


def init_caches(cfg: ArchConfig, batch: int, max_len: int) -> Dict[str, Any]:
    """Per-pattern-position stacked (repeats, ...) decode state."""

    def one(spec: BlockSpec):
        if spec.mixer == "attn":
            base = attn_lib.init_kv_cache(cfg, batch, max_len)
        elif spec.mixer == "mamba":
            base = ssm_lib.mamba_init_state(cfg, batch)
        elif spec.mixer == "rwkv":
            base = ssm_lib.rwkv_init_state(cfg, batch)
        else:
            base = {}
        return jax.tree.map(lambda x: jnp.broadcast_to(x, (cfg.repeats,) + x.shape), base)

    return {f"pos{pos}": one(spec) for pos, spec in enumerate(cfg.pattern)}


def init_paged_caches(cfg: ArchConfig, batch: int, num_pages: int, page: int) -> Dict[str, Any]:
    """Paged decode state, dispatched per pattern position: attention gets a
    block-table page pool (repeats, P, page, kv, hd) shared by all slots,
    while SSM/RWKV state stays dense per slot — recurrent state is O(1) in
    context length, so paging it buys nothing (paging is attention-only)."""

    def one(spec: BlockSpec):
        if spec.mixer == "attn":
            base = attn_lib.init_paged_kv_cache(cfg, num_pages, page)
        elif spec.mixer == "mamba":
            base = ssm_lib.mamba_init_state(cfg, batch)
        elif spec.mixer == "rwkv":
            base = ssm_lib.rwkv_init_state(cfg, batch)
        else:
            base = {}
        return jax.tree.map(lambda x: jnp.broadcast_to(x, (cfg.repeats,) + x.shape), base)

    return {f"pos{pos}": one(spec) for pos, spec in enumerate(cfg.pattern)}


def cache_shardings_logical(cfg: ArchConfig):
    """Logical axes of each cache leaf (for input_specs/dry-run)."""

    def one(spec: BlockSpec):
        if spec.mixer == "attn":
            return {
                "k": ("stack", "batch", "kv_seq", None, None),
                "v": ("stack", "batch", "kv_seq", None, None),
            }
        if spec.mixer == "mamba":
            return {
                "conv": ("stack", "batch", None, "ff"),
                "ssm": ("stack", "batch", "ff", None),
            }
        if spec.mixer == "rwkv":
            return {
                "wkv": ("stack", "batch", None, None, None),
                "shift_t": ("stack", "batch", None),
                "shift_c": ("stack", "batch", None),
            }
        return {}

    return {f"pos{pos}": one(spec) for pos, spec in enumerate(cfg.pattern)}


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------


def _apply_block(
    p: Dict[str, Any],
    x: Array,
    cfg: ArchConfig,
    spec: BlockSpec,
    positions: Array,
    cache: Optional[Dict[str, Array]],
    cache_len: Optional[Array],
    block_tables: Optional[Array] = None,
    chunked_prefill: bool = False,
) -> Tuple[Array, Optional[Dict[str, Array]], Array]:
    aux = jnp.asarray(0.0, jnp.float32)
    h = rms_norm(x, p["norm1"], cfg.rms_eps)
    new_cache = cache
    if spec.mixer == "attn":
        out, new_cache = attn_lib.attn_apply(
            p["attn"], h, cfg, spec, positions, cache, cache_len,
            block_tables=block_tables, chunked=chunked_prefill,
        )
    elif spec.mixer == "mamba":
        out, new_cache = ssm_lib.mamba_apply(p["mamba"], h, cfg, cache)
    elif spec.mixer == "rwkv":
        out, new_cache = ssm_lib.rwkv_time_mix(p["rwkv"], h, cfg, cache)
    else:
        out = jnp.zeros_like(h)
    if cfg.post_block_norm:
        out = rms_norm(out, p["post_norm1"], cfg.rms_eps)
    x = x + out
    x = shard(x, ("batch", "seq", "embed"))

    h = rms_norm(x, p["norm2"], cfg.rms_eps)
    if spec.ffn == "dense":
        out = mlp_apply(p["mlp"], h, cfg)
    elif spec.ffn == "moe":
        out, moe_aux = moe_lib.moe_apply(p["moe"], h, cfg)
        aux = aux + moe_aux
    elif spec.ffn == "rwkv_cmix":
        out, new_cache = ssm_lib.rwkv_channel_mix(p["rwkv"], h, cfg, new_cache)
    else:
        out = jnp.zeros_like(h)
    if cfg.post_block_norm:
        out = rms_norm(out, p["post_norm2"], cfg.rms_eps)
    x = x + out
    x = shard(x, ("batch", "seq", "embed"))
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _embed_inputs(params, cfg: ArchConfig, tokens, embeds):
    if embeds is not None:  # modality frontends supply embeddings directly
        x = embeds.astype(cfg.compute_dtype)
    elif cfg.frontend == "audio_codes":
        # tokens: (B, S, n_q) EnCodec codes; embeddings summed over codebooks
        emb = params["embed"].astype(cfg.compute_dtype)
        x = sum(emb[q][tokens[..., q]] for q in range(cfg.n_codebooks))
    else:
        x = lookup(params["embed"].astype(cfg.compute_dtype), tokens, ("batch", "seq", "embed"))
    if cfg.scale_embed:
        x = x * jnp.asarray(jnp.sqrt(cfg.d_model), cfg.compute_dtype)
    return shard(x, ("batch", "seq", "embed"))


def _logits(params, cfg: ArchConfig, h: Array) -> Array:
    if cfg.frontend == "audio_codes":
        logits = h @ params["heads"].astype(cfg.compute_dtype)
        logits = logits.reshape(*h.shape[:-1], cfg.n_codebooks, cfg.vocab_size)
    elif cfg.tie_embeddings:
        logits = h @ params["embed"].astype(cfg.compute_dtype).T
    else:
        logits = h @ params["lm_head"].astype(cfg.compute_dtype)
    logits = softcap(logits.astype(jnp.float32), cfg.final_softcap)
    return shard(logits, ("batch", "seq", "vocab"))


def forward(
    params: Dict[str, Any],
    cfg: ArchConfig,
    tokens: Optional[Array] = None,
    embeds: Optional[Array] = None,
    positions: Optional[Array] = None,
    caches: Optional[Dict[str, Any]] = None,
    cache_len: Optional[Array] = None,
    block_tables: Optional[Array] = None,
    chunked_prefill: bool = False,
) -> ModelOutput:
    x = _embed_inputs(params, cfg, tokens, embeds)
    b, s, _ = x.shape

    if positions is None:
        off = 0
        if cache_len is not None:
            # scalar (whole-batch) or (B,) per-slot decode positions; the
            # speculative verify rides the vector form — lane (slot, j)
            # passes cache_len = pos + j and gets RoPE position pos + j here,
            # exactly what the sequential decode of that token would use
            off = cache_len[:, None] if jnp.ndim(cache_len) == 1 else cache_len
        base = jnp.arange(s, dtype=jnp.int32)[None, :] + off
        positions = jnp.broadcast_to(base, (b, s))
        if cfg.mrope:
            positions = jnp.broadcast_to(positions[None], (3, b, s))

    have_cache = caches is not None

    def body(carry, xs):
        x, aux = carry
        layer_params = xs[0]
        layer_caches = xs[1] if have_cache else None
        new_caches = {}
        for pos, spec in enumerate(cfg.pattern):
            name = f"pos{pos}"
            cache = layer_caches[name] if have_cache else None
            x, nc, a = _apply_block(
                layer_params[name], x, cfg, spec, positions, cache, cache_len,
                block_tables=block_tables, chunked_prefill=chunked_prefill,
            )
            if have_cache:
                new_caches[name] = nc if nc is not None else cache
            aux = aux + a
        return (x, aux), (new_caches if have_cache else None)

    body_fn = body
    if cfg.remat:
        policy = (
            jax.checkpoint_policies.dots_saveable
            if getattr(cfg, "remat_policy", "nothing") == "dots"
            else jax.checkpoint_policies.nothing_saveable
        )
        body_fn = jax.checkpoint(body, policy=policy)

    xs = (params["blocks"], caches) if have_cache else (params["blocks"],)
    (x, aux), new_caches = jax.lax.scan(body_fn, (x, jnp.asarray(0.0, jnp.float32)), xs)

    h = rms_norm(x, params["final_norm"], cfg.rms_eps)
    logits = _logits(params, cfg, h)
    return ModelOutput(
        logits=logits,
        hidden=h,
        caches=new_caches,
        aux={"moe_aux": aux / max(cfg.n_layers, 1)},
    )
