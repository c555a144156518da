"""repro.tune subsystem tests: candidate legality, cache round-trip +
schema invalidation, dispatch precedence, tuner guarantees, and numerical
equivalence of tuned vs default configs against the kernel oracles."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tune
from repro.kernels.pallas_utils import LANE, SUBLANE
from repro.kernels.sumvec_fft import kernel as fkernel
from repro.kernels.sumvec_fft import ops as fops
from repro.kernels.sumvec_fft import ref as fref
from repro.kernels.xcorr_offdiag import kernel as xkernel
from repro.kernels.xcorr_offdiag import ref as xref
from repro.tune import cache as tcache
from repro.tune import dispatch as tdispatch
from repro.tune import space as tspace

SHAPES = {
    "xcorr_offdiag": (24, 200),
    "cmatmul": (40, 24, 72),
    "pmatmul": (40, 24, 72),
    "ctwiddle": (24, 200),
    "spectral_gram": (24, 48, 8),
    "spectral_gram_vjp": (24, 48, 8),
    "sumvec_fft_plan": (101,),
    "grouped_block_plan": (24, 48),
    "paged_attention": (4, 48, 2, 16),
}


def _views(n, d, seed=0):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    return jax.random.normal(k1, (n, d)), jax.random.normal(k2, (n, d))


# ---------------------------------------------------------------------------
# Candidate enumeration
# ---------------------------------------------------------------------------


class TestSpace:
    @pytest.mark.parametrize("kernel", tspace.KERNELS)
    def test_candidates_nonempty_and_legal(self, kernel):
        shape = SHAPES[kernel]
        cands = tspace.candidates(kernel, shape)
        assert cands
        for cfg in cands:
            assert tspace.is_legal(kernel, shape, cfg), (kernel, cfg)
            assert tspace.vmem_bytes(kernel, shape, cfg) <= tspace.VMEM_BUDGET_BYTES

    def test_tile_alignment(self):
        for cfg in tspace.candidates("xcorr_offdiag", (64, 512)):
            assert cfg["tile_d"] % LANE == 0
            assert cfg["tile_n"] % SUBLANE == 0
        for cfg in tspace.candidates("pmatmul", (300, 300, 300)):
            assert cfg["tm"] % SUBLANE == 0
            assert cfg["tn"] % LANE == 0 and cfg["tk"] % LANE == 0

    @pytest.mark.parametrize("kernel", tspace.KERNELS)
    def test_default_config_is_candidate(self, kernel):
        shape = SHAPES[kernel]
        canon = tdispatch.canonical_shape(kernel, shape)
        assert tspace.default_config(kernel, canon) in tspace.candidates(kernel, canon)

    def test_vmem_budget_excludes_oversized(self):
        # a 2048^2 f32 scratch alone is 16 MiB — must never be enumerated
        for cfg in tspace.candidates("xcorr_offdiag", (256, 4096)):
            assert cfg["tile_d"] <= 1024

    def test_plan_candidates_prime_are_padded_and_safe(self):
        cands = tspace.candidates("sumvec_fft_plan", (101,))
        padded = [c for c in cands if c["dp"] > 101]
        assert padded, "prime d must get padded fallback plans"
        for c in padded:
            assert c["dp"] >= 2 * 101 - 1  # linear-correlation safe
            assert c["d1"] > 1 and c["d1"] * c["d2"] == c["dp"]

    def test_grouped_block_size_candidates(self):
        bs = tspace.grouped_block_size_candidates(2048)
        assert bs == sorted(bs) and bs[-1] == 2048 and 128 in bs
        assert tspace.grouped_block_size_candidates(24)[-1] == 24

    def test_auto_block_size(self):
        from repro.kernels.grouped_sumvec.ops import auto_block_size

        assert auto_block_size(2048) == 128  # paper's sweet spot
        assert auto_block_size(100) == 100  # below prefer: ungrouped
        assert auto_block_size(192) == 128
        assert auto_block_size(8) == 8


# ---------------------------------------------------------------------------
# Persistent cache
# ---------------------------------------------------------------------------


class TestCache:
    def test_round_trip(self, tmp_path):
        cfg = {"tile_n": 64, "tile_d": 256}
        assert tcache.store(
            "xcorr_offdiag", (64, 256), "float32", "cpu", cfg,
            source="dry", cost={"flops": 1.0}, directory=tmp_path,
        )
        entry = tcache.lookup("xcorr_offdiag", (64, 256), "float32", "cpu", directory=tmp_path)
        assert entry["config"] == cfg
        assert entry["source"] == "dry"
        # different backend / shape / dtype are distinct keys
        assert tcache.lookup("xcorr_offdiag", (64, 256), "float32", "tpu", directory=tmp_path) is None
        assert tcache.lookup("xcorr_offdiag", (64, 512), "float32", "cpu", directory=tmp_path) is None

    def test_schema_version_invalidates(self, tmp_path):
        cfg = {"tile_n": 64, "tile_d": 256}
        tcache.store("xcorr_offdiag", (64, 256), "float32", "cpu", cfg, directory=tmp_path)
        path = tmp_path / "cpu.json"
        data = json.loads(path.read_text())
        data["schema"] = tcache.SCHEMA_VERSION + 1
        path.write_text(json.dumps(data))
        assert tcache.lookup("xcorr_offdiag", (64, 256), "float32", "cpu", directory=tmp_path) is None

    def test_corrupt_file_is_a_miss(self, tmp_path):
        (tmp_path / "cpu.json").write_text("{not json")
        assert tcache.lookup("x", (1,), "float32", "cpu", directory=tmp_path) is None
        # and store still recovers the file
        assert tcache.store("x", (8, 128), "float32", "cpu", {"tn": 8}, directory=tmp_path)

    def test_concurrent_stores_keep_all_entries(self, tmp_path):
        # the flock around read-modify-write must prevent lost updates
        import threading

        def work(i):
            tcache.store("pmatmul", (8 * i, 128, 128), "float32", "cpu", {"tm": 8}, directory=tmp_path)

        threads = [threading.Thread(target=work, args=(i,)) for i in range(1, 9)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert len(tcache.load_all("cpu", directory=tmp_path)) == 8


# ---------------------------------------------------------------------------
# Dispatch precedence + memoization
# ---------------------------------------------------------------------------


class TestDispatch:
    def test_cache_hit_skips_search(self, monkeypatch):
        calls = {"n": 0}
        real = tdispatch._analytic_search

        def counting(kernel, shape):
            calls["n"] += 1
            return real(kernel, shape)

        monkeypatch.setattr(tdispatch, "_analytic_search", counting)
        tdispatch.clear_memory_cache()
        a = tune.best_config("xcorr_offdiag", (56, 408))
        b = tune.best_config("xcorr_offdiag", (56, 408))
        assert a == b and calls["n"] == 1
        # logically different shape, same canonical padding -> still one search
        tune.best_config("xcorr_offdiag", (51, 400))
        assert calls["n"] == 1

    def test_disk_cache_consulted(self, monkeypatch, tmp_path):
        monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path))
        tdispatch.clear_memory_cache()
        canon = tune.canonical_shape("xcorr_offdiag", (16, 384))
        pinned = {"tile_n": 8, "tile_d": 128}
        tcache.store("xcorr_offdiag", canon, "float32", jax.default_backend(), pinned, source="dry")
        assert tune.best_config("xcorr_offdiag", (16, 384)) == pinned

    def test_override_beats_cache(self, monkeypatch, tmp_path):
        monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path))
        tdispatch.clear_memory_cache()
        canon = tune.canonical_shape("xcorr_offdiag", (16, 384))
        tcache.store(
            "xcorr_offdiag", canon, "float32", jax.default_backend(),
            {"tile_n": 8, "tile_d": 128}, source="dry",
        )
        with tune.override("xcorr_offdiag", tile_d=256):
            cfg = tune.best_config("xcorr_offdiag", (16, 384))
            assert cfg["tile_d"] == 256  # the override
            assert cfg["tile_n"] == 16  # merged from the default, not the cache
        assert tune.best_config("xcorr_offdiag", (16, 384))["tile_d"] == 128

    def test_illegal_cached_entry_falls_back(self, monkeypatch, tmp_path):
        monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path))
        tdispatch.clear_memory_cache()
        canon = tune.canonical_shape("xcorr_offdiag", (16, 384))
        tcache.store(
            "xcorr_offdiag", canon, "float32", jax.default_backend(),
            {"tile_n": 3, "tile_d": 100}, source="dry",  # violates alignment
        )
        cfg = tune.best_config("xcorr_offdiag", (16, 384))
        assert tspace.is_legal("xcorr_offdiag", canon, cfg)

    def test_cached_entry_with_wrong_keys_is_a_miss(self, monkeypatch, tmp_path):
        # a schema-valid entry whose config lacks the kernel's keys (hand
        # edit, or a future key rename without a schema bump) must degrade
        # to a miss, not KeyError out of the first kernel call
        monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path))
        tdispatch.clear_memory_cache()
        canon = tune.canonical_shape("xcorr_offdiag", (24, 200))
        tcache.store("xcorr_offdiag", canon, "float32", jax.default_backend(), {"tm": 128})
        cfg = tune.best_config("xcorr_offdiag", (24, 200))
        assert tspace.is_legal("xcorr_offdiag", canon, cfg)

    def test_no_legal_candidates_falls_back_to_default(self):
        # spectral_gram's whole feature rows of a 128-row tile alone bust the
        # VMEM budget at d = 2**17 — there is no "legal" candidate, and
        # dispatch still answers with the default tile (the grouped R_sum
        # routes such shapes to the jnp FFT: grouped_sumvec.ops.fits).
        shape = (128, 2**17, 128)
        assert tspace.candidates("spectral_gram", shape) == []
        cfg = tune.best_config("spectral_gram", shape)
        assert cfg == tspace.default_config("spectral_gram", tune.canonical_shape("spectral_gram", shape))

    def test_best_impl(self):
        assert tune.best_impl("r_sum", backend="tpu") == "pallas"
        assert tune.best_impl("r_sum", backend="cpu") == "jnp"
        with tune.override("r_sum", impl="pallas"):
            assert tune.best_impl("r_sum", backend="cpu") == "pallas"


# ---------------------------------------------------------------------------
# Tuner (dry mode): determinism + never-worse-than-default guarantee
# ---------------------------------------------------------------------------


class TestTuner:
    def test_dry_mode_guards_default(self, monkeypatch, tmp_path):
        monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path))
        res = tune.tune("pmatmul", (24, 40, 24), mode="dry", max_candidates=4)
        default = res.candidate_for(res.default)
        best = res.candidate_for(res.best)
        assert best.cost["flops"] <= default.cost["flops"]
        assert best.cost["hbm_bytes"] <= default.cost["hbm_bytes"]

    def test_dry_mode_deterministic_and_persists(self, monkeypatch, tmp_path):
        monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path))
        r1 = tune.tune("xcorr_offdiag", (16, 128), mode="dry", max_candidates=4)
        r2 = tune.tune("xcorr_offdiag", (16, 128), mode="dry", max_candidates=4)
        assert r1.best == r2.best
        entry = tcache.lookup(
            "xcorr_offdiag", r1.shape, "float32", jax.default_backend()
        )
        assert entry is not None and entry["config"] == r1.best
        # ... and dispatch serves the tuned entry from then on
        tdispatch.clear_memory_cache()
        assert tune.best_config("xcorr_offdiag", (16, 128)) == r1.best

    def test_measure_mode_times_each_candidate_once(self):
        res = tune.tune("pmatmul", (16, 16, 16), mode="measure", persist=False,
                        max_candidates=2, repeats=1)
        assert all(c.time_us is not None and c.time_us > 0 for c in res.candidates)

    def test_analytic_mode_instant(self):
        res = tune.tune("cmatmul", (40, 24, 72), mode="analytic", persist=False)
        assert res.best in [c.config for c in res.candidates]

    def test_analytic_rank_avoids_degenerate_tiles(self):
        # m = 520: tm = 8 has zero padding but 65 grid rows; the roofline
        # ranking must not let padding-free flops pick the degenerate tile
        cfg = tune.best_config("cmatmul", (520, 64, 64))
        assert cfg["tm"] >= 64, cfg


# ---------------------------------------------------------------------------
# Numerical equivalence: tuned/default/any-legal configs agree with oracles
# ---------------------------------------------------------------------------


class TestNumericalEquivalence:
    def test_xcorr_tiles_match_oracle(self):
        n, d = 24, 72
        z1, z2 = _views(n, d, seed=1)
        want = xref.off_diagonal_sq_sum_ref(z1, z2)
        canon = tune.canonical_shape("xcorr_offdiag", (n, d))
        tuned = tune.best_config("xcorr_offdiag", (n, d))
        default = tune.default_config("xcorr_offdiag", canon)
        for cfg in (tuned, default, {"tile_n": 8, "tile_d": 128}):
            got = xkernel.off_diagonal_sq_sum_raw(
                z1, z2, tile_d=cfg["tile_d"], tile_n=cfg["tile_n"]
            )
            np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)

    def test_cmatmul_tiles_match_numpy(self):
        m, k, n = 24, 40, 24
        ar, ai = _views(m, k, seed=2)
        br, bi = _views(k, n, seed=3)
        a = np.asarray(ar) + 1j * np.asarray(ai)
        b = np.asarray(br) + 1j * np.asarray(bi)
        want = a @ b
        for cfg in ({"tm": 8, "tn": 128, "tk": 128}, {"tm": 32, "tn": 128, "tk": 128}):
            cr, ci = fkernel._cmatmul_raw(ar, ai, br, bi, **cfg)
            np.testing.assert_allclose(np.asarray(cr) + 1j * np.asarray(ci), want, atol=1e-4)

    def test_r_sum_grouped_impl_consistent_when_b_exceeds_d(self):
        # b > d pads d up to b (the matrix-oracle semantics); the loss value
        # must not depend on which backend the impl dispatch picked.
        from repro.core import regularizers as regs

        z1, z2 = _views(8, 24, seed=7)
        a = regs.r_sum_grouped(z1, z2, 32, scale=8.0, impl="jnp")
        b = regs.r_sum_grouped(z1, z2, 32, scale=8.0, impl="pallas")
        np.testing.assert_allclose(a, b, rtol=1e-5)

    def test_partial_plan_override_is_completed(self):
        # pinning dp alone must not hand back an inconsistent (dp, d1, d2)
        with tune.override("sumvec_fft_plan", dp=48):
            plan = fops.fft_plan(24)
        assert (plan.dp, plan.d1, plan.d2) == (48, 6, 8)
        with tune.override("sumvec_fft_plan", d1=4, d2=6):
            assert fops.fft_plan(24).dp == 24
        # one factor alone: completed against the default dp
        with tune.override("sumvec_fft_plan", d1=16):
            plan = fops.fft_plan(2048)
        assert (plan.dp, plan.d1, plan.d2) == (2048, 16, 128)
        # dp plus one factor: the pinned factor must survive
        with tune.override("sumvec_fft_plan", dp=48, d1=4):
            plan = fops.fft_plan(24)
        assert (plan.dp, plan.d1, plan.d2) == (48, 4, 12)

    def test_unsatisfiable_plan_override_raises_valueerror(self):
        with tune.override("sumvec_fft_plan", d1=5):  # 5 does not divide 24
            with pytest.raises(ValueError):
                fops.fft_plan(24)
        with tune.override("sumvec_fft_plan", dp=30):  # 24 < 30 < 2*24 - 1
            with pytest.raises(ValueError):
                fops.fft_plan(24)
        with tune.override("sumvec_fft_plan", dp=48, d1=4, d2=6):  # 4*6 != 48
            with pytest.raises(ValueError):
                fops.fft_plan(24)

    def test_unknown_impl_rejected(self):
        from repro.core import regularizers as regs

        z1, z2 = _views(4, 8)
        with pytest.raises(ValueError):
            regs.r_sum(z1, z2, impl="palas")
        with pytest.raises(ValueError):
            regs.r_sum_grouped(z1, z2, 4, impl="Pallas")

    def test_invalid_fftplan_raises_not_asserts(self):
        # a plan violating its invariants must raise even under python -O
        with pytest.raises(ValueError):
            fops.FFTPlan(d=100, dp=150, d1=10, d2=15)  # aliased fold
        with pytest.raises(ValueError):
            fops.FFTPlan(d=24, dp=24, d1=5, d2=5)  # d1*d2 != dp

    def test_invalid_q_rejected(self):
        # q outside {1, 2} would otherwise compute sum-of-squares on the jnp
        # route but sum-of-abs on the pallas route — reject it outright
        from repro.core import regularizers as regs

        z1, z2 = _views(4, 8)
        for impl in ("jnp", "pallas"):
            with pytest.raises(ValueError):
                regs.r_sum(z1, z2, q=3, impl=impl)
            with pytest.raises(ValueError):
                regs.r_sum_grouped(z1, z2, 4, q=0, impl=impl)

    def test_padded_plan_equals_exact_plan(self):
        # composite d: both the exact plan and a padded fallback must agree
        n, d = 8, 24
        z1, z2 = _views(n, d, seed=4)
        exact = fops.FFTPlan(d=d, dp=24, d1=4, d2=6)
        padded = fops.FFTPlan(d=d, dp=48, d1=6, d2=8)
        for q in (1, 2):
            want = fref.r_sum_ref(z1, z2, q=q, scale=float(n))
            for plan in (exact, padded):
                got = fops.r_sum_fourstep(z1, z2, q=q, scale=float(n), plan=plan)
                np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-3)
        np.testing.assert_allclose(
            fops.sumvec_fourstep(z1, z2, scale=float(n), plan=padded),
            fref.sumvec_ref(z1, z2, scale=float(n)),
            atol=1e-4,
        )


# ---------------------------------------------------------------------------
# Regression: prime / near-prime d no longer degrades to the O(d^2) DFT
# ---------------------------------------------------------------------------


class TestPrimeDRegression:
    def test_choose_factors_still_exact(self):
        assert fops.choose_factors(101) == (1, 101)
        assert fops.choose_factors(24) == (4, 6)

    @pytest.mark.parametrize("d", [101, 127])
    def test_plan_pads_prime_d(self, d):
        plan = fops.fft_plan(d)
        assert plan.padded and plan.dp >= 2 * d - 1
        assert plan.d1 > 1 and plan.d2 < d  # genuinely balanced, not (1, dp)

    @pytest.mark.parametrize("q", [1, 2])
    def test_prime_d_matches_oracle(self, q):
        n, d = 6, 101
        z1, z2 = _views(n, d, seed=5)
        got = fops.r_sum_fourstep(z1, z2, q=q, scale=float(n))
        want = fref.r_sum_ref(z1, z2, q=q, scale=float(n))
        np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-3)

    def test_prime_d_sumvec_matches_oracle(self):
        n, d = 6, 101
        z1, z2 = _views(n, d, seed=6)
        np.testing.assert_allclose(
            fops.sumvec_fourstep(z1, z2, scale=float(n)),
            fref.sumvec_ref(z1, z2, scale=float(n)),
            atol=1e-4,
        )


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


class TestCLI:
    def test_analytic_pretune_writes_cache(self, monkeypatch, tmp_path, capsys):
        from repro.tune import cli

        monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path))
        rc = cli.main(["--analytic", "--shape", "8x32", "--cache-dir", str(tmp_path)])
        assert rc == 0
        entries = tcache.load_all(jax.default_backend(), directory=tmp_path)
        assert any(k.startswith("sumvec_fft_plan|") for k in entries)
        assert any(k.startswith("xcorr_offdiag|") for k in entries)
        out = capsys.readouterr().out
        assert "tuned" in out


# ---------------------------------------------------------------------------
# grouped_block_plan: the block size b searched as a plan config
# ---------------------------------------------------------------------------


class TestGroupedBlockPlan:
    def test_space_enumerates_every_legal_b(self):
        shape = (64, 48)
        cands = tspace.candidates("grouped_block_plan", shape)
        assert [c["b"] for c in cands] == tspace.grouped_block_size_candidates(48)
        # default mirrors auto_block_size: largest legal b <= 128
        assert tspace.default_config("grouped_block_plan", shape) == {"b": 48}
        assert tspace.default_config("grouped_block_plan", (64, 2048)) == {"b": 128}
        assert not tspace.is_legal("grouped_block_plan", shape, {"b": 1})
        assert not tspace.is_legal("grouped_block_plan", shape, {"b": 96})

    def test_dry_tune_compiles_real_pipeline(self):
        res = tune.tune(
            "grouped_block_plan", (16, 16), mode="dry",
            max_candidates=2, persist=False,
        )
        assert res.best["b"] in tspace.grouped_block_size_candidates(16)
        for c in res.candidates:
            assert c.cost["flops"] > 0  # compiled, not just modelled

    def test_jobs_for_searches_b_when_unpinned(self):
        from repro.tune.cli import jobs_for

        plans, jobs = jobs_for(16, 16, mode="analytic", persist=False)
        assert [p.kernel for p in plans] == ["sumvec_fft_plan", "grouped_block_plan"]
        b = plans[-1].best["b"]
        assert b in tspace.grouped_block_size_candidates(16)
        # the searched winner drives the derived grouped shapes
        assert ("spectral_gram", (16, 16, b)) in jobs
        # a caller-pinned b skips the search entirely (b is loss-defining)
        plans_pinned, _ = jobs_for(16, 16, block_size=8, mode="analytic", persist=False)
        assert [p.kernel for p in plans_pinned] == ["sumvec_fft_plan"]
