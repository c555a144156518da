"""The serving engine: bucketed, jit-cached embedding forward passes.

``ServeEngine`` wraps the SSL encoder+projector (``repro.train.ssl.embed``)
behind a per-bucket compile cache: inputs are padded to the request bucket
(``repro.serve.buckets``), each bucket compiles exactly once, and ``warmup``
pre-compiles the whole ladder so no request pays a trace.  Parameters come
either in-memory or from a ``repro.checkpoint`` directory (the training
loop's own format — the round trip is pinned by tests).  Under a mesh the
forward runs data-parallel inside ``shard_map`` (batch sharded over the
``data`` axis, params replicated) — the same execution regime as
``train/ssl.make_sharded_ssl_train_step``, minus the gradients.  Passing
``model_axis`` additionally feature-shards the forward (tp mode): the
projector's output layer splits over the ``feature`` logical axis exactly as
``train/ssl.ssl_param_specs`` shards it for tp training, each device computes
its (n, d/M) feature block, and the decorr engine's
``all_to_all_features`` exchange re-assembles full-width rows — so one
serving replica can span M devices (``fabric.FabricConfig(tp=M)``).  The
last projector layer is a pure affine map (no activation), so the
column-sharded forward is numerically identical to the single-device one.

``LMServeEngine`` is the token-model counterpart: it consumes the
prefill/decode step factories from ``repro.train.serve`` and caches their
jitted forms across requests, so repeated generate calls of one shape
compile once.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.checkpoint.checkpointer import latest_step, restore_checkpoint
from repro.serve.buckets import BucketPolicy, bucket_for, bucket_sizes
from repro.train.ssl import SSLModelConfig, embed, init_ssl_params

Array = jax.Array


class ServeEngine:
    """Embedding forward with a bounded per-bucket compile cache."""

    def __init__(
        self,
        model_cfg: SSLModelConfig,
        params,
        *,
        policy: BucketPolicy = BucketPolicy(),
        mesh: Optional[Mesh] = None,
        data_axis: str = "data",
        model_axis: Optional[str] = None,
        dtype=jnp.float32,
    ):
        self.model_cfg = model_cfg
        self.params = params
        self.policy = policy.validate()
        self.mesh = mesh
        self.data_axis = data_axis
        self.model_axis = model_axis
        self.dtype = dtype
        self._tp_specs = None
        if model_axis is not None and mesh is None:
            raise ValueError("model_axis (tp mode) needs a mesh carrying that axis")
        if mesh is not None:
            dp = int(mesh.shape[data_axis])
            mp = int(mesh.shape[model_axis]) if model_axis is not None else 1
            if policy.align % (dp * mp):
                # tp buckets split over BOTH axes: the all_to_all exchange
                # turns (n/dp, d/mp) shards into (n/(dp*mp), d) rows
                raise ValueError(
                    f"BucketPolicy.align={policy.align} must be a multiple of the "
                    f"mesh extent ({dp}x{mp}={dp * mp}) so every bucket shards evenly"
                )
            if model_axis is not None:
                if self.d % mp:
                    raise ValueError(
                        f"embedding width d={self.d} must split evenly over the "
                        f"{model_axis!r} axis ({mp} devices)"
                    )
                self._tp_specs = self._make_tp_specs()
                # place params once (projector output layer feature-sharded,
                # everything else replicated) so encode never re-shards
                self.params = jax.tree_util.tree_map(
                    lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
                    self.params, self._tp_specs,
                )
        self._compiled: Dict[int, callable] = {}
        # per-executable attribution; services attach obs.perf (None keeps
        # encode() fully async — no block_until_ready on the hot path)
        self.perf = None

    # -- construction -------------------------------------------------------

    @classmethod
    def from_checkpoint(
        cls,
        ckpt_dir: str,
        model_cfg: SSLModelConfig,
        *,
        step: Optional[int] = None,
        **kw,
    ) -> "ServeEngine":
        """Load encoder+projector params saved by the training loop.

        Training checkpoints a ``TrainState`` whose params live under the
        ``params`` key; a bare params tree (e.g. an exported snapshot) is
        accepted too.  ``step=None`` takes the newest committed step.
        """
        if step is None:
            step = latest_step(ckpt_dir)
            if step is None:
                raise FileNotFoundError(f"no committed checkpoint under {ckpt_dir}")
        template = init_ssl_params(jax.random.PRNGKey(0), model_cfg)
        try:
            params = restore_checkpoint(ckpt_dir, step, template)
        except KeyError:
            # TrainState layout: restore just the params subtree by wrapping
            # the template the way the train loop nests it.
            from repro.train.train_state import TrainState

            state = restore_checkpoint(
                ckpt_dir, step, TrainState(0, template, None, None)
            )
            params = state.params
        return cls(model_cfg, params, **kw)

    # -- compile cache ------------------------------------------------------

    @property
    def d(self) -> int:
        """Embedding width (the projector's output dimension)."""
        return int(self.model_cfg.projector_widths[-1])

    def _make_tp_specs(self):
        """Param placement for tp mode, mirroring ``train/ssl.ssl_param_specs``:
        everything replicated except the projector's output layer, which
        splits over the ``feature`` logical axis (mapped onto
        ``self.model_axis``)."""
        import repro.parallel.sharding as shd

        rules = {"feature": (self.model_axis,)}
        with shd.sharding_context(self.mesh, rules):
            w_spec = shd.logical_to_spec((None, "feature"))
            b_spec = shd.logical_to_spec(("feature",))
        specs = {
            "backbone": [{"w": P(), "b": P()} for _ in self.params["backbone"]],
            "projector": [{"w": P(), "b": P()} for _ in self.params["projector"]],
        }
        specs["projector"][-1] = {"w": w_spec, "b": b_spec}
        return specs

    def _embed_fn(self, bucket: int):
        fn = self._compiled.get(bucket)
        if fn is not None:
            if self.perf is not None:
                self.perf.cache_hit(f"embed_b{bucket}")
            return fn
        if self.perf is not None:
            self.perf.cache_miss(f"embed_b{bucket}")
        if self.mesh is None:
            fn = jax.jit(embed)
        elif self.model_axis is None:
            sharded = shard_map(
                embed,
                mesh=self.mesh,
                in_specs=(P(), P(self.data_axis)),
                out_specs=P(self.data_axis),
            )
            fn = jax.jit(sharded)
        else:
            # tp: each device computes its (n/dp, d/mp) feature block of the
            # projector output, then the decorr engine's exchange transposes
            # feature shards into full-width row shards — the output lands
            # batch-sharded over BOTH mesh axes
            from repro.decorr.modes import all_to_all_features

            model_axis = self.model_axis

            def tp_embed(p, x):
                """Feature-sharded forward + all_to_all row re-assembly."""
                return all_to_all_features(embed(p, x), model_axis)

            sharded = shard_map(
                tp_embed,
                mesh=self.mesh,
                in_specs=(self._tp_specs, P(self.data_axis)),
                out_specs=P((self.data_axis, self.model_axis)),
            )
            fn = jax.jit(sharded)
        self._compiled[bucket] = fn
        return fn

    def warmup(self) -> Tuple[int, ...]:
        """Pre-compile every bucket (AOT) so no request pays a trace."""
        import time as _time

        for b in bucket_sizes(self.policy):
            shape = jax.ShapeDtypeStruct((b, self.model_cfg.input_dim), self.dtype)
            fn = self._embed_fn(b)
            t0 = _time.perf_counter()
            compiled = fn.lower(self.params, shape).compile()
            self._compiled[b] = compiled
            if self.perf is not None:
                name = f"embed_b{b}"
                self.perf.record_compile(name, _time.perf_counter() - t0)
                self.perf.attach_compiled(name, compiled)
        return bucket_sizes(self.policy)

    def compiled_buckets(self) -> Tuple[int, ...]:
        """Batch sizes with a compiled executable, ascending."""
        return tuple(sorted(self._compiled))

    # -- serving forward ----------------------------------------------------

    def encode(self, x: Array) -> Array:
        """(n, input_dim) -> (n, d): pad to the bucket, run, strip padding.

        n must be <= ``policy.max_batch`` rows (the batcher guarantees it);
        rows are independent through the MLP so zero-padding never leaks into
        real outputs.
        """
        x = jnp.asarray(x, self.dtype)
        if x.ndim == 1:
            x = x[None, :]
        n = x.shape[0]
        top = bucket_sizes(self.policy)[-1]
        if n > top:
            # coalescing can overshoot max_batch by one multi-row request
            # (and the naive bench feeds arbitrary n): chunk at the largest
            # bucket so every executable stays within the warmed ladder.
            parts = [self.encode(x[i : i + top]) for i in range(0, n, top)]
            return jnp.concatenate(parts, axis=0)
        b = bucket_for(n, self.policy)
        if n < b:
            x = jnp.concatenate([x, jnp.zeros((b - n, x.shape[1]), self.dtype)], axis=0)
        fn = self._embed_fn(b)
        if self.perf is None:
            return self._strip(fn(self.params, x), n)
        # attribution path: block so the wall time covers device execution
        # (the default perf=None path stays fully async)
        t0 = self.perf.start()
        z = fn(self.params, x)
        jax.block_until_ready(z)
        self.perf.observe(f"embed_b{b}", self.perf.elapsed(t0))
        return self._strip(z, n)

    def _strip(self, z: Array, n: int) -> Array:
        """The first ``n`` rows of a bucket's output. Rows sharded over the
        mesh are gathered first: an Explicit-axis mesh gives a slice across
        row shards no sharding."""
        if n == z.shape[0]:
            return z
        if self.mesh is not None:
            z = jax.device_put(z, NamedSharding(self.mesh, P()))
        return z[:n]


# ---------------------------------------------------------------------------
# Token-model serving: prefill/decode factories from repro.train.serve
# ---------------------------------------------------------------------------


class LMServeEngine:
    """Greedy generation with the prefill/decode steps compiled once.

    ``repro.train.serve.greedy_generate`` builds (and jits) its step
    functions per call; this engine owns them across requests, keyed by
    nothing — prefill/decode are shape-polymorphic in batch via retrace, and
    XLA's jit cache bounds the variants to the distinct (batch, prompt_len)
    shapes actually served.
    """

    def __init__(self, arch_cfg):
        from repro.train.serve import make_decode_step, make_prefill_step

        self.cfg = arch_cfg
        self.steps = (
            jax.jit(make_prefill_step(arch_cfg)),
            jax.jit(make_decode_step(arch_cfg)),
        )

    def generate(self, params, prompt_tokens: Array, max_new_tokens: int) -> Array:
        """Whole-request greedy generation (the non-continuous path)."""
        from repro.train.serve import greedy_generate

        return greedy_generate(
            params, self.cfg, prompt_tokens, max_new_tokens, steps=self.steps
        )


# ---------------------------------------------------------------------------
# Continuous batching: decode-step-granular slot scheduling
# ---------------------------------------------------------------------------


class ContinuousLMEngine:
    """Continuous-batching LM engine over a fixed pool of decode slots.

    Instead of whole-request ``generate`` calls (the batch drains only when
    its longest request finishes), the pool's N slots all advance one token
    per ``decode_step`` — with a *vector* ``cache_len``, each slot at its own
    position — and a freed slot admits the next queued request on the very
    next step via ``insert`` (prefill the prompt at batch 1, scatter its
    KV/SSM state into the slot's row of the cache pool).

    Compile discipline mirrors ``ServeEngine``: prompts are right-padded to a
    geometric length ladder (``prompt_bucket_sizes``) so prefill compiles
    once per bucket, decode compiles ONCE for the whole pool, and ``warmup``
    AOT-compiles all of it so no request pays a trace.  Right-padding is only
    numerics-safe for attention patterns (causality masks the pad rows);
    recurrent mixers (SSM/RWKV) fold padding into their state, so those archs
    prefill at the exact prompt length — one compile per distinct length
    actually served.

    The decode step also returns the final hidden state of each slot's new
    token; the service samples the in-flight rows from it for the online
    decorrelation probes (``repro.decorr.probe.slot_probe_rows``).

    Orthogonal extensions over the PR 4 dense engine (each off by default,
    leaving the dense greedy path's compiled graphs untouched):

      * ``paged=True`` — the per-slot dense KV strips become fixed-size token
        pages addressed through block tables (``repro.serve.paging``): decode
        reads/writes gather/scatter over the tables (Pallas kernel on TPU via
        ``kernels/paged_attention``), admission reserves pages OOM-safely,
        retirement returns them and compacts.  SSM/RWKV state stays dense —
        paging is attention-only, dispatched per pattern position.  Greedy
        tokens are bit-identical to the dense engine when NB * page ==
        max_len (the engine rounds max_len up to a page multiple).
      * ``prefill_chunk=N`` (paged, attention-only patterns) — prompts longer
        than N prefill N tokens per service tick into the batch-1 template,
        interleaved with pool decode, so a long prompt no longer stalls
        in-flight slots for a whole prefill; the finished prompt is scattered
        into its pages like any other insert.
      * ``sampling=True`` — prefill/decode executables return LOGITS instead
        of in-jit argmax; the service draws tokens host-side per request
        (``repro.serve.sampling``: temperature/top-k, per-request PRNG;
        temperature 0 stays bit-identical greedy).
      * ``prefix_cache=True`` (paged) — retired prompts donate their full KV
        pages to a radix tree (``repro.serve.paging.radix``); a warm request
        binds the matched pages into its block table read-only (refcounted,
        reservation charges only the unshared tail), copy-on-writes the
        boundary page when the hit ends mid-page, and resumes chunked
        prefill at the hit — skipping the shared prefix's FLOPs entirely.
        Forces ``chunk_all`` (every prompt runs the chunked-prefill
        executable, warm or cold, resuming on the same chunk grid), which is
        what keeps tokens bit-identical to unshared paging: the hit is
        quantized DOWN to a chunk boundary (and to ``prompt_len - 1``), so a
        warm prefill replays the exact executables on the exact values the
        cold run would produce from that boundary on.
      * ``speculative=True`` (paged, greedy, attention-only) — each tick a
        per-slot n-gram drafter (``repro.serve.spec``) proposes up to
        ``draft_k`` tokens and ONE lane-batched verify forward (the decode
        executable at batch ``n_slots * (draft_k + 1)``) scores all draft
        positions at once; the longest draft prefix matching the model's own
        argmax is accepted, advancing a slot several tokens per tick.
        Speculative writes land on pinned scratch pages
        (``PagedKVManager.spec_begin``) so a rejected draft leaves no trace
        and speculation can never OOM an admitted slot; an accepted span
        commits by SWAPPING scratch into the block table — no device copy.
    """

    def __init__(
        self,
        arch_cfg,
        params,
        *,
        n_slots: int = 8,
        max_len: int = 128,
        max_prompt_len: Optional[int] = None,
        prompt_align: int = 8,
        reset_on_retire: bool = True,
        paged: bool = False,
        page_size: Optional[int] = None,
        total_pages: Optional[int] = None,
        prefill_chunk: Optional[int] = None,
        sampling: bool = False,
        compact_on_retire: bool = True,
        prefix_cache: bool = False,
        chunk_all: bool = False,
        speculative: bool = False,
        draft_k: int = 4,
        spec_ngram_max: int = 3,
        spec_ngram_min: int = 1,
    ):
        from repro.models.transformer import init_caches
        from repro.serve.slots import SlotPool
        from repro.train.serve import (
            apply_page_moves,
            insert_slot_state,
            insert_slot_state_paged,
            load_template_from_pages,
            make_chunked_prefill_step,
            make_decode_step,
            make_prefill_at_step,
            make_verify_step,
            reset_slot_state,
            reset_slot_state_paged,
        )

        if arch_cfg.frontend == "audio_codes":
            raise NotImplementedError(
                "continuous batching serves flat token streams; audio-code "
                "models ((B, S, n_q) tokens) go through LMServeEngine.generate"
            )
        self.cfg = arch_cfg
        self.params = params
        self.sampling_enabled = bool(sampling)
        self.reset_on_retire = reset_on_retire
        self.compact_on_retire = compact_on_retire
        # right-padded prompt buckets only where causality hides the padding
        self.pad_prompts = all(spec.mixer == "attn" for spec in arch_cfg.pattern)
        # optional flight recorder (repro.obs.FlightRecorder); the service
        # attaches its own so page-table churn lands in the same ring buffer
        # as the scheduler's admit/retire events
        self.recorder = None
        # per-executable attribution (repro.obs.ExecTimer); the service
        # attaches obs.perf when telemetry is enabled
        self.perf = None
        self._warmed_prefill: set = set()

        self.paged = bool(paged)
        self.prefix_cache = bool(prefix_cache)
        # chunk_all: every prompt (even <= one chunk) runs the chunked-prefill
        # executable.  Prefix caching forces it — warm resumption must land on
        # the same chunk grid the cold run used, or tokens drift.
        self.chunk_all = bool(chunk_all) or self.prefix_cache
        if self.prefix_cache and not self.paged:
            raise ValueError("prefix_cache shares KV pages; pass paged=True")
        self.speculative = bool(speculative)
        self.spec_cfg = None
        if self.speculative:
            from repro.serve.spec import SpecConfig

            if not self.paged:
                raise ValueError(
                    "speculative decoding verifies through scratch pages; pass paged=True"
                )
            if self.sampling_enabled:
                raise ValueError(
                    "speculative decoding is greedy-only: acceptance compares the "
                    "draft against argmax outputs (sampling would need rejection "
                    "sampling over the verify logits)"
                )
            if not self.pad_prompts:
                raise ValueError(
                    "speculative decoding needs attention-only patterns: SSM/RWKV "
                    "per-slot state cannot advance k+1 positions independently in "
                    "one forward"
                )
            self.spec_cfg = SpecConfig(
                draft_k=int(draft_k), ngram_max=int(spec_ngram_max),
                ngram_min=int(spec_ngram_min),
            )
        self.pager = None
        if self.paged:
            from repro.kernels.paged_attention.ops import auto_page_size
            from repro.kernels.pallas_utils import next_multiple
            from repro.serve.paging import PagedKVManager

            page = int(
                page_size
                or auto_page_size(n_slots, max_len, arch_cfg.n_kv_heads, arch_cfg.hd)
            )
            # NB * page == max_len keeps the gathered context view the exact
            # shape of the dense cache — that (plus masked rows' probability
            # mass underflowing to 0.0) is what makes paged greedy decode
            # bit-identical to the dense engine
            max_len = next_multiple(max_len, page)
            if self.prefix_cache and not prefill_chunk:
                prefill_chunk = page  # hit grid == page grid: COW only on cap
            self.pager = PagedKVManager(
                arch_cfg, n_slots, max_len, page, total_pages=total_pages,
                prefix_cache=self.prefix_cache,
                prefix_chunk=int(prefill_chunk) if self.prefix_cache else None,
                spec_draft_k=self.spec_cfg.draft_k if self.speculative else 0,
            )
            if self.prefix_cache:
                self.pager.event_sink = self._record

        self.prefill_chunk = int(prefill_chunk) if prefill_chunk else None
        if self.chunk_all and self.prefill_chunk is None:
            raise ValueError(
                "chunk_all rides chunked prefill; pass prefill_chunk (paged)"
            )
        if self.prefill_chunk is not None:
            if not self.paged:
                raise ValueError("prefill_chunk rides the paged machinery; pass paged=True")
            if not self.pad_prompts:
                raise ValueError(
                    "chunked prefill needs attention-only patterns (recurrent "
                    "mixers fold chunk padding into their state)"
                )
            if self.prefill_chunk < 1:
                raise ValueError(f"prefill_chunk must be >= 1, got {prefill_chunk}")

        self.pool = SlotPool(n_slots, max_len)
        max_prompt = int(max_prompt_len or max(max_len // 2, prompt_align))
        if max_prompt >= max_len:
            raise ValueError(f"max_prompt_len={max_prompt} must leave decode room (< max_len={max_len})")
        self._prompt_policy = BucketPolicy(max_batch=max_prompt, align=prompt_align, max_wait_ms=0.0)
        if self.pad_prompts and bucket_sizes(self._prompt_policy)[-1] > max_len:
            # the ladder rounds max_prompt_len UP to the alignment: a padded
            # prefill of the top bucket must still fit the slot's cache rows
            raise ValueError(
                f"padded prompt bucket {bucket_sizes(self._prompt_policy)[-1]} "
                f"(max_prompt_len={max_prompt} rounded up to align={prompt_align}) "
                f"exceeds max_len={max_len}; lower max_prompt_len or raise max_len"
            )
        if self.prefill_chunk is not None:
            tail = -(-max_prompt // self.prefill_chunk) * self.prefill_chunk
            if tail > max_len:
                raise ValueError(
                    f"chunked prefill of a max_prompt_len={max_prompt} prompt pads "
                    f"to {tail} template rows > max_len={max_len}; shrink prefill_chunk"
                )

        self.caches = self.pager.init_caches() if self.paged else init_caches(
            arch_cfg, n_slots, max_len
        )
        self._caches1 = init_caches(arch_cfg, 1, max_len)  # prefill template

        decode = make_decode_step(arch_cfg, return_hidden=True)

        def _pick(logits):
            if sampling:
                return logits  # host-side sampler draws per request
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)

        def _step(params, caches, cache_len, tokens):
            logits, hidden, caches = decode(params, caches, cache_len, tokens=tokens[:, None])
            return _pick(logits), hidden, caches

        def _step_paged(params, caches, cache_len, tokens, block_tables):
            logits, hidden, caches = decode(
                params, caches, cache_len, tokens=tokens[:, None], block_tables=block_tables
            )
            return _pick(logits), hidden, caches

        prefill_at = make_prefill_at_step(arch_cfg)

        def _pre(params, caches1, tokens, true_len):
            logits, hidden, caches1 = prefill_at(params, caches1, tokens, true_len)
            return _pick(logits), hidden, caches1

        # one decode executable for the whole pool; prefill one per bucket
        # (the jit caches below ARE the AOT cache `warmup` fills)
        self._decode = jax.jit(_step_paged if self.paged else _step, donate_argnums=(1,))
        self._prefill = jax.jit(_pre)
        if self.speculative:
            verify = make_verify_step(arch_cfg, return_hidden=True)

            def _verify_paged(params, caches, cache_len, tokens, block_tables,
                              move_src, move_dst):
                # boundary-page copies (one per drafted slot, sentinel
                # identity moves as padding) fused into the verify
                # executable: one device dispatch per tick, not two
                caches = apply_page_moves(caches, move_src, move_dst)
                logits, hidden, caches = verify(
                    params, caches, cache_len, tokens=tokens[:, None],
                    block_tables=block_tables,
                )
                return _pick(logits), hidden, caches

            self._verify = jax.jit(_verify_paged, donate_argnums=(1,))
        if self.paged:
            self._insert = jax.jit(insert_slot_state_paged, donate_argnums=(0,))
            self._reset = jax.jit(reset_slot_state_paged, donate_argnums=(0,))
            self._moves = jax.jit(apply_page_moves, donate_argnums=(0,))
            if self.prefix_cache:
                # warm-template gather (no donation: pool and template live on)
                self._loadtpl = jax.jit(load_template_from_pages)
                # one-deep plan memo from can_admit to admit_slot (same tick,
                # same head-of-line request — no allocation happens between)
                self._plan_stash: Tuple[Optional[int], Optional[object]] = (None, None)
        else:
            self._insert = jax.jit(insert_slot_state, donate_argnums=(0,))
            self._reset = jax.jit(reset_slot_state, donate_argnums=(0,))
        # chunked prefill: ONE in-progress (slot_index, live batch-1 tree) at
        # a time — chunks of different prompts serialize, decode interleaves
        self._chunk_live: Optional[list] = None
        if self.prefill_chunk is not None:
            chunk_step = make_chunked_prefill_step(arch_cfg)

            def _chunk(params, caches1, tokens, offset, last):
                logits, hidden, caches1 = chunk_step(params, caches1, tokens, offset, last)
                return _pick(logits), hidden, caches1

            self._chunk_step = jax.jit(_chunk)

    # -- admission-side shape policy ----------------------------------------

    def prompt_bucket_sizes(self) -> Tuple[int, ...]:
        """Prompt-padding bucket ladder, ascending."""
        return bucket_sizes(self._prompt_policy)

    @property
    def max_prompt_len(self) -> int:
        """Largest admissible prompt length (the top bucket)."""
        return self.prompt_bucket_sizes()[-1]

    def _prompt_bucket(self, n: int) -> int:
        return bucket_for(n, self._prompt_policy) if self.pad_prompts else n

    def validate_request(self, prompt_len: int, max_new_tokens: int):
        """Submit-time admission check: reject (never hang) what cannot be
        scheduled — empty prompts, prompts beyond the largest bucket, and
        requests that cannot fit the slot's cache rows (or, paged, could not
        get their pages even from an empty pool)."""
        if prompt_len < 1:
            raise ValueError("empty prompt: prompt_len must be >= 1")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if prompt_len > self.max_prompt_len:
            raise ValueError(
                f"prompt_len={prompt_len} exceeds the largest prompt bucket "
                f"({self.max_prompt_len}); rejecting instead of queueing unservable work"
            )
        # rows actually written: the final emitted token never lands in the
        # cache, so a request that exactly fills it is admissible
        rows = prompt_len + max_new_tokens - 1
        if rows > self.pool.max_len:
            raise ValueError(
                f"prompt_len + max_new_tokens - 1 = {rows} "
                f"exceeds the slot cache ({self.pool.max_len} rows)"
            )
        if self.paged and not self.pager.fits_ever(prompt_len, max_new_tokens):
            raise ValueError(
                f"request needs {self.pager.alloc.pages_for_tokens(rows)} pages "
                f"> the pool's {self.pager.alloc.usable_pages} usable pages; "
                "rejecting instead of queueing unservable work"
            )

    def can_admit(self, request) -> bool:
        """Decode-tick admission check beyond a free slot: paged pools also
        need the request's worst-case page reservation to fit RIGHT NOW
        (deferred, not rejected, otherwise — OOM-safe admission)."""
        if not self.paged:
            return True
        if self.prefix_cache:
            plan = self.pager.plan_prefix(request.tokens, request.prompt_len)
            self._plan_stash = (id(request), plan)
            return self.pager.can_admit(
                request.prompt_len, request.max_new_tokens, plan=plan
            )
        return self.pager.can_admit(request.prompt_len, request.max_new_tokens)

    # -- compile cache -------------------------------------------------------

    def warmup(self, prompt_lens=None) -> Tuple[int, ...]:
        """AOT-compile every prompt-bucket prefill variant, the pool decode
        step, the slot insert/reset (and, paged, the page-move / chunk-step
        executables) — so no admitted request traces.

        Attention-only patterns warm the whole padded bucket ladder.
        Recurrent patterns prefill at exact lengths, so callers that know
        their workload pass ``prompt_lens`` (distinct lengths to warm);
        unknown lengths still compile lazily at admission."""
        if self.pad_prompts:
            buckets = self.prompt_bucket_sizes()
        else:
            buckets = tuple(sorted(set(int(n) for n in prompt_lens or ())) or (1,))
        for length in buckets:
            toks = jnp.zeros((1, length), jnp.int32)
            if self.perf is not None:
                # AOT lower purely for attribution (HLO costs + compile
                # gauge); the executing jit cache below is untouched
                self.perf.attach_jit(
                    f"prefill_b{length}", self._prefill,
                    self.params, self._caches1, toks, np.int32(1),
                )
            _, _, one = self._prefill(self.params, self._caches1, toks, np.int32(1))
            self._warmed_prefill.add(int(length))
        nb = 0 if not self.paged else self.pager.blocks_per_slot
        if self.paged:
            # all-sentinel table rows: warmup writes land on the scratch page
            bt_row = jnp.zeros((nb,), jnp.int32)
            self.caches = self._insert(self.caches, one, np.int32(0), bt_row)
        else:
            self.caches = self._insert(self.caches, one, np.int32(0))
        lens = jnp.zeros((self.pool.n_slots,), jnp.int32)
        toks = jnp.zeros((self.pool.n_slots,), jnp.int32)
        if self.paged:
            bt = jnp.zeros((self.pool.n_slots, nb), jnp.int32)
            if self.perf is not None:
                self.perf.attach_jit(
                    "decode_step", self._decode, self.params, self.caches, lens, toks, bt
                )
            _, _, self.caches = self._decode(self.params, self.caches, lens, toks, bt)
            self.caches = self._reset(self.caches, np.int32(0), bt_row)
            if self.compact_on_retire or self.prefix_cache:
                # compaction AND the prefix COW reuse the same executable
                idx = jnp.zeros((nb,), jnp.int32)
                self.caches = self._moves(self.caches, idx, idx)
            if self.prefix_cache:
                # warm-template gather (all-sentinel row reads scratch rows)
                self._loadtpl(self.caches, self._caches1, bt_row)
            if self.speculative:
                # the verify executable is the SAME jitted decode step at the
                # lane-batched shape n_slots * (draft_k + 1): each lane is a
                # plain one-token decode at its own (cache_len, table row)
                width = self.spec_cfg.draft_k + 1
                vb = self.pool.n_slots * width
                vlens = jnp.zeros((vb,), jnp.int32)
                vtoks = jnp.zeros((vb,), jnp.int32)
                vbt = jnp.zeros((vb, nb), jnp.int32)
                # boundary-page copies ride inside the verify executable,
                # one (sentinel-padded) move per slot
                sidx = jnp.zeros((self.pool.n_slots,), jnp.int32)
                if self.perf is not None:
                    self.perf.attach_jit(
                        "verify_step", self._verify,
                        self.params, self.caches, vlens, vtoks, vbt, sidx, sidx,
                    )
                _, _, self.caches = self._verify(
                    self.params, self.caches, vlens, vtoks, vbt, sidx, sidx
                )
        else:
            if self.perf is not None:
                self.perf.attach_jit(
                    "decode_step", self._decode, self.params, self.caches, lens, toks
                )
            _, _, self.caches = self._decode(self.params, self.caches, lens, toks)
            self.caches = self._reset(self.caches, np.int32(0))
        if self.prefill_chunk is not None:
            ctoks = jnp.zeros((1, self.prefill_chunk), jnp.int32)
            if self.perf is not None:
                self.perf.attach_jit(
                    "chunk_prefill", self._chunk_step,
                    self.params, self._caches1, ctoks, np.int32(0), np.int32(0),
                )
            self._chunk_step(self.params, self._caches1, ctoks, np.int32(0), np.int32(0))
        return buckets

    # -- slot mechanics ------------------------------------------------------

    def needs_chunking(self, prompt_len: int) -> bool:
        """True when this prompt prefills chunk-at-a-time."""
        if self.prefill_chunk is None:
            return False
        return self.chunk_all or prompt_len > self.prefill_chunk

    def admit_slot(self, slot) -> int:
        """Post-``pool.admit`` hook: charge the paged reservation (binding +
        pinning any matched prefix pages) and flag chunked prompts as
        still-prefilling.  Returns the prefix-cache hit in rows — chunked
        prefill resumes there (0 cold/unshared)."""
        req = slot.request
        hit = 0
        if self.paged:
            if self.prefix_cache:
                key, plan = self._plan_stash
                if key != id(req):
                    plan = self.pager.plan_prefix(req.tokens, req.prompt_len)
                self._plan_stash = (None, None)
                hit = self.pager.admit(
                    slot.index, req.prompt_len, req.max_new_tokens, plan=plan
                )
            else:
                self.pager.admit(slot.index, req.prompt_len, req.max_new_tokens)
        if self.needs_chunking(req.prompt_len):
            slot.prefill_pos = hit
        if self.speculative:
            from repro.serve.spec import SlotDraft

            slot.draft = SlotDraft(self.spec_cfg, np.asarray(req.tokens).tolist())
        return hit

    def _record(self, kind: str, **fields):
        if self.recorder is not None:
            self.recorder.record(kind, **fields)

    def _scatter_insert(self, slot, one):
        if self.paged:
            added = self.pager.ensure_rows(slot.index, slot.request.prompt_len)
            if added:
                self._record("page_alloc", slot=slot.index, pages=len(added),
                             in_use=self.pager.alloc.in_use)
            if self.prefix_cache:
                # shared prefix blocks are masked to the sentinel: the insert
                # must never rewrite a read-only shared page
                row = self.pager.scatter_row(slot.index)
            else:
                row = self.pager.table_row(slot.index)
            bt_row = jnp.asarray(row)
            self.caches = self._insert(self.caches, one, np.int32(slot.index), bt_row)
            if self.prefix_cache:
                # the pages now hold the final prompt KV: intern the full
                # prompt pages for future warm requests (first writer wins)
                donated = self.pager.donate(slot.index, slot.request.tokens)
                if donated:
                    self._record("page_donate", slot=slot.index, pages=donated)
        else:
            self.caches = self._insert(self.caches, one, np.int32(slot.index))

    def _first_output(self, out, hidden):
        first = np.asarray(out)[0] if self.sampling_enabled else int(out[0])
        return first, np.asarray(hidden, np.float32)

    def insert(self, slot):
        """Prefill an admitted request and scatter its state into the slot.
        Returns (first output, its hidden-state row (1, d_model)) — the
        prefill already emits the request's first token (TTFT point); with
        ``sampling`` the first output is the (V,) logits row the service
        samples from instead of the token id."""
        req = slot.request
        n = req.prompt_len
        length = self._prompt_bucket(n)
        perf = self.perf
        if perf is not None:
            name = f"prefill_b{length}"
            if int(length) in self._warmed_prefill:
                perf.cache_hit(name)
            else:
                perf.cache_miss(name)
                self._warmed_prefill.add(int(length))
            t0 = perf.start()
        padded = np.zeros((1, length), np.int32)
        padded[0, :n] = np.asarray(req.tokens, np.int32)
        out, hidden, one = self._prefill(
            self.params, self._caches1, jnp.asarray(padded), np.int32(n)
        )
        self._scatter_insert(slot, one)
        result = self._first_output(out, hidden)  # np.asarray syncs the device
        if perf is not None:
            perf.observe(f"prefill_b{length}", perf.elapsed(t0))
        return result

    def advance_prefill(self, slot):
        """Run ONE chunk of the slot's incremental prefill.  Returns None
        while the prompt is still streaming in; on the final chunk, scatters
        the finished state into the slot's pages and returns the same
        (first output, hidden row) contract as ``insert``.

        Only one chunked prefill is live at a time (the batch-1 work tree);
        other still-prefilling slots wait their turn while decode proceeds.
        """
        req = slot.request
        n, c = req.prompt_len, self.prefill_chunk
        if self._chunk_live is None:
            tree = self._caches1
            if self.prefix_cache:
                moves = self.pager.cow_moves(slot.index)
                if moves is not None:
                    # copy-on-write of the boundary page BEFORE the template
                    # gather reads it: writes never land on shared pages
                    src, dst = moves
                    self.caches = self._moves(
                        self.caches, jnp.asarray(src), jnp.asarray(dst)
                    )
                    self._record("page_cow", slot=slot.index,
                                 src=int(src[0]), dst=int(dst[0]))
                if slot.prefill_pos > 0:
                    # warm start: seed the batch-1 template with the shared
                    # prefix's KV rows so chunks attend over them unrecomputed
                    row = jnp.asarray(self.pager.table_row(slot.index))
                    tree = self._loadtpl(self.caches, self._caches1, row)
                    self._record("page_share", slot=slot.index,
                                 rows=slot.prefill_pos,
                                 pages=self.pager.alloc.shared_count(slot.index))
            self._chunk_live = [slot.index, tree]
        if self._chunk_live[0] != slot.index:
            return None  # another prompt owns the work tree this tick
        perf = self.perf
        t0 = perf.start() if perf is not None else 0.0
        off = slot.prefill_pos
        take = min(c, n - off)
        padded = np.zeros((1, c), np.int32)
        padded[0, :take] = np.asarray(req.tokens[off : off + take], np.int32)
        out, hidden, tree = self._chunk_step(
            self.params, self._chunk_live[1], jnp.asarray(padded),
            np.int32(off), np.int32(take - 1),
        )
        self._chunk_live[1] = tree
        slot.prefill_pos = off + take
        if slot.prefilling:
            if perf is not None:
                jax.block_until_ready(tree)  # mid-prompt chunks return no host value
                perf.observe("chunk_prefill", perf.elapsed(t0))
            return None
        self._scatter_insert(slot, tree)
        self._chunk_live = None
        result = self._first_output(out, hidden)
        if perf is not None:
            perf.observe("chunk_prefill", perf.elapsed(t0))
        return result

    def prefilling_slot(self):
        """The still-prefilling slot whose chunk should advance this tick:
        the owner of the live work tree, else the oldest waiting one."""
        waiting = [s for s in self.pool.active() if s.prefilling]
        if not waiting:
            return None
        if self._chunk_live is not None:
            for s in waiting:
                if s.index == self._chunk_live[0]:
                    return s
        return waiting[0]

    def decode_step(self) -> Tuple[np.ndarray, np.ndarray]:
        """One batched decode over the whole pool.  Returns (next output per
        slot — (N,) token ids, or (N, V) logits under ``sampling`` — and
        hidden rows (N, d_model)); free-slot and still-prefilling lanes are
        garbage the caller must mask by ``pool.decoding_indices()``."""
        perf = self.perf
        t0 = perf.start() if perf is not None else 0.0
        lens = jnp.asarray(self.pool.cache_lens())
        toks = jnp.asarray(self.pool.last_tokens())
        if self.paged:
            for i in self.pool.decoding_indices():
                # lazy page growth: bind the write target's page (cannot
                # fail — admission reserved the worst case)
                added = self.pager.ensure_rows(i, self.pool[i].pos + 1)
                if added:
                    self._record("page_alloc", slot=i, pages=len(added),
                                 in_use=self.pager.alloc.in_use)
            tables = self.pager.block_tables()
            if self.prefix_cache:
                # still-prefilling slots decode at lane position 0, and the
                # paged write path unconditionally scatters each lane's k/v at
                # block_tables[slot, 0] row 0.  Unshared, those tables are
                # still empty (the write lands on the sentinel); with prefix
                # pages bound at admission it would CORRUPT a shared page —
                # mask every non-decoding lane's row to the sentinel.
                decoding = set(self.pool.decoding_indices())
                for i in range(self.pool.n_slots):
                    if i not in decoding:
                        tables[i, :] = 0  # SENTINEL
            bt = jnp.asarray(tables)
            out, hidden, self.caches = self._decode(self.params, self.caches, lens, toks, bt)
        else:
            out, hidden, self.caches = self._decode(self.params, self.caches, lens, toks)
        result = (np.asarray(out), np.asarray(hidden, np.float32))  # host sync
        if perf is not None:
            perf.observe("decode_step", perf.elapsed(t0))
        return result

    # -- speculative decoding -------------------------------------------------

    def spec_verify(self, drafts):
        """One lane-batched speculative verify over the whole pool.

        ``drafts`` is a list of ``(slot_index, draft_tokens)`` covering every
        decoding slot this tick (``draft_tokens`` may be empty: that slot
        rides lane 0 only, which is exactly its plain decode step).  Lane
        ``(s, j)`` of the fixed ``n_slots * (draft_k + 1)`` batch decodes
        slot ``s`` at ``cache_len = pos + j`` with input token ``last_token``
        (j = 0) or ``draft[j - 1]`` — per-lane math identical to the pool
        decode step, which is what keeps greedy outputs bit-identical to
        sequential decode.  Drafted slots read/write through scratch-mapped
        table rows (``PagedKVManager.spec_begin``); unused lanes are masked
        like free pool lanes (cache_len 0, sentinel rows).

        Returns ``(out, hidden, tickets)``: ``(n_slots, draft_k + 1)`` token
        ids, ``(n_slots, draft_k + 1, d_model)`` hidden rows, and the per-slot
        scratch tickets the caller must settle via ``spec_commit`` (always —
        lane 0's write is real even when the whole draft is rejected) or
        ``spec_rollback`` (error/abort paths only).
        """
        width = self.spec_cfg.draft_k + 1
        nb = self.pager.blocks_per_slot
        n = self.pool.n_slots
        lens = np.zeros((n * width,), np.int32)
        toks = np.zeros((n * width,), np.int32)
        tables = np.zeros((n * width, nb), np.int32)  # sentinel-masked lanes
        tickets = {}
        copies = []
        for slot_index, draft in drafts:
            s = self.pool[slot_index]
            k_eff = len(draft)
            if k_eff:
                ticket, moves = self.pager.spec_begin(slot_index, s.pos, k_eff)
                tickets[slot_index] = ticket
                copies.extend(moves)
                row = ticket.row
            else:
                # undrafted slot: plain decode through its REAL table row
                added = self.pager.ensure_rows(slot_index, s.pos + 1)
                if added:
                    self._record("page_alloc", slot=slot_index, pages=len(added),
                                 in_use=self.pager.alloc.in_use)
                row = self.pager.table_row(slot_index)
            base = slot_index * width
            for j in range(k_eff + 1):
                lens[base + j] = s.pos + j
                toks[base + j] = s.last_token if j == 0 else draft[j - 1]
                tables[base + j] = row
        perf = self.perf
        t0 = perf.start() if perf is not None else 0.0
        # boundary-page copies, one lane per slot (zeros are sentinel ->
        # sentinel identity no-ops), fused into the verify executable
        src = np.zeros((n,), np.int32)
        dst = np.zeros((n,), np.int32)
        for i, (a, b) in enumerate(copies):
            src[i], dst[i] = a, b
        try:
            out, hidden, self.caches = self._verify(
                self.params, self.caches, jnp.asarray(lens), jnp.asarray(toks),
                jnp.asarray(tables), jnp.asarray(src), jnp.asarray(dst),
            )
        except Exception:
            # a failed device step must not leak the scratch inventory
            for ticket in tickets.values():
                self.pager.spec_rollback(ticket)
            raise
        result = (
            np.asarray(out).reshape(n, width),
            np.asarray(hidden, np.float32).reshape(n, width, -1),
            tickets,
        )
        if perf is not None:
            perf.observe("verify_step", perf.elapsed(t0))
        return result

    def spec_commit(self, ticket, n_written: int):
        """Promote ``n_written`` verified rows into the slot's block table
        (pure table swap — no device copy on the accept path)."""
        self.pager.spec_commit(ticket, n_written)

    def spec_rollback(self, ticket):
        """Discard a speculative window, restoring table state exactly."""
        self.pager.spec_rollback(ticket)

    def abort_slot(self, index: int):
        """Host-side-only cleanup for a slot whose device step failed: drop
        any in-progress chunked prefill it owns and hand back its pages +
        reservation.  No device ops — the device may be wedged, and a stale
        ``_chunk_live`` would otherwise wedge every later chunked prefill
        on a reused slot index."""
        if self._chunk_live is not None and self._chunk_live[0] == index:
            self._chunk_live = None
        if self.paged:
            before = self.pager.alloc.in_use
            self.pager.release(index)
            self._record("page_free", slot=index, abort=True,
                         pages=before - self.pager.alloc.in_use,
                         in_use=self.pager.alloc.in_use)

    def release(self, index: int):
        """Retire a slot: zero its cache rows (hygiene; decode masks them),
        return its pages + reservation, and compact the page pool
        (copy-on-retire: the highest in-use pages relocate into the freed
        low holes, keeping the live frontier tight)."""
        if self._chunk_live is not None and self._chunk_live[0] == index:
            self._chunk_live = None
        if self.paged:
            if self.reset_on_retire:
                # under prefix caching, pages other owners still map (shared
                # prefixes, donated pages) are masked out of the zeroing
                row = (self.pager.reset_row(index) if self.prefix_cache
                       else self.pager.table_row(index))
                self.caches = self._reset(self.caches, np.int32(index), jnp.asarray(row))
            before = self.pager.alloc.in_use
            self.pager.release(index)
            self._record("page_free", slot=index,
                         pages=before - self.pager.alloc.in_use,
                         in_use=self.pager.alloc.in_use)
            if self.compact_on_retire:
                src, dst = self.pager.plan_compaction()
                if src.size:
                    self._record("page_compact", moves=int((src != dst).sum()))
                    self.caches = self._moves(
                        self.caches, jnp.asarray(src), jnp.asarray(dst)
                    )
            return
        if self.reset_on_retire:
            self.caches = self._reset(self.caches, np.int32(index))
