"""Per-kernel validation: shape/dtype sweeps, allclose vs the ref.py
pure-jnp oracles, and gradient agreement (interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import regularizers as regs
from repro.kernels.grouped_sumvec import ops as gops, ref as gref
from repro.kernels.sumvec_fft import ops as fops, ref as fref
from repro.kernels.xcorr_offdiag import ops as xops, ref as xref


def _views(n, d, dtype=jnp.float32, seed=0):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    return (
        jax.random.normal(k1, (n, d)).astype(dtype),
        jax.random.normal(k2, (n, d)).astype(dtype),
    )


GROUPED_CASES = [
    (8, 16, 4, 1), (8, 16, 4, 2), (16, 40, 8, 2), (16, 40, 7, 1),
    (4, 64, 16, 2), (32, 24, 24, 2), (5, 33, 8, 2),
]

# b divides d, b does not (a zero-padded last block), b = d (ungrouped), and a
# batch past one 128-row tile (the last tile masked)
PARITY_CASES = [
    (16, 64, 16, 1), (16, 64, 16, 2), (12, 40, 16, 1), (12, 40, 16, 2),
    (8, 24, 24, 1), (8, 24, 24, 2), (136, 256, 128, 2),
]


class TestGroupedSumvecKernel:
    @pytest.mark.parametrize("n,d,b,q", GROUPED_CASES)
    def test_matches_oracle(self, n, d, b, q):
        z1, z2 = _views(n, d)
        got = gops.r_sum_kernel(z1, z2, block_size=b, q=q, scale=n)
        want = gref.r_sum_grouped_ref(z1, z2, b, q=q, scale=n)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_dtypes(self, dtype):
        z1, z2 = _views(8, 32, dtype)
        got = gops.r_sum_kernel(z1, z2, block_size=8, q=2, scale=8)
        want = gref.r_sum_grouped_ref(z1.astype(jnp.float32), z2.astype(jnp.float32), 8, q=2, scale=8)
        tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
        np.testing.assert_allclose(got, want, rtol=tol, atol=tol)

    @pytest.mark.parametrize("q", [1, 2])
    def test_grads_match_pure_jnp(self, q):
        n, d, b = 8, 24, 8
        z1, z2 = _views(n, d, seed=3)
        gk = jax.grad(lambda a, c: gops.r_sum_kernel(a, c, block_size=b, q=q, scale=n), argnums=(0, 1))(z1, z2)
        gj = jax.grad(lambda a, c: regs.r_sum_grouped(a, c, b, q=q, scale=n), argnums=(0, 1))(z1, z2)
        np.testing.assert_allclose(gk[0], gj[0], atol=1e-4)
        np.testing.assert_allclose(gk[1], gj[1], atol=1e-4)

    def test_block_covering_d_matches_ungrouped(self):
        z1, z2 = _views(8, 16)
        got = gops.r_sum_kernel(z1, z2, block_size=None, q=2, scale=8)
        want = gref.r_sum_ref(z1, z2, q=2, scale=8)
        np.testing.assert_allclose(got, want, rtol=1e-4)

    @pytest.mark.parametrize("n,d,b,q", PARITY_CASES)
    def test_value_and_grads_match_jnp_and_matrix_routes(self, n, d, b, q):
        """The Pallas pipeline against the jnp FFT route (``core/sumvec``) and
        against Eq. (13) on the explicit matrix C, values and gradients."""
        z1, z2 = _views(n, d, seed=12)
        pallas = lambda a, c: gops.r_sum_kernel(a, c, block_size=b, q=q, scale=n)
        jnp_fft = lambda a, c: regs.r_sum_grouped(a, c, b, q=q, scale=n, impl="jnp")
        matrix = lambda a, c: regs.r_sum_grouped_from_matrix(
            regs.cross_correlation_matrix(a, c, scale=n), b, q=q)
        got, got_g = jax.value_and_grad(pallas, argnums=(0, 1))(z1, z2)
        for route in (jnp_fft, matrix):
            want, want_g = jax.value_and_grad(route, argnums=(0, 1))(z1, z2)
            np.testing.assert_allclose(got, want, rtol=1e-4)
            for g, w in zip(got_g, want_g):
                np.testing.assert_allclose(g, w, atol=1e-4 * float(jnp.max(jnp.abs(w))))

    @pytest.mark.parametrize("q", [1, 2])
    def test_one_view_twice(self, q):
        """VICReg's R of one view: ``z2=None`` (the route ``r_sum_grouped``
        takes for ``z1 is z2``) against the jnp route on (z, z)."""
        n, d, b = 12, 40, 16
        z, _ = _views(n, d, seed=13)
        want, want_g = jax.value_and_grad(
            lambda a: regs.r_sum_grouped(a, a, b, q=q, scale=n, impl="jnp"))(z)
        for fn in (lambda a: gops.r_sum_kernel(a, None, block_size=b, q=q, scale=n),
                   lambda a: regs.r_sum_grouped(a, a, b, q=q, scale=n, impl="pallas")):
            got, got_g = jax.value_and_grad(fn)(z)
            np.testing.assert_allclose(got, want, rtol=1e-4)
            np.testing.assert_allclose(got_g, want_g, atol=1e-4 * float(jnp.max(jnp.abs(want_g))))

    @pytest.mark.parametrize("n,d,b,same", [(16, 64, 16, False), (12, 40, 16, False), (12, 40, 16, True)])
    def test_frequency_accumulator_pallas_matches_jnp(self, n, d, b, same):
        """``modes.frequency_accumulator``, the statistic the ``global`` and
        ``tp`` modes psum: the Pallas route against the jnp route, value and
        the gradient of a fixed linear read-out of it."""
        from repro.decorr import modes

        z1, z2 = _views(n, d, seed=14)
        if same:
            z2 = z1
        wr, wi = _views(-(-d // b), (-(-d // b)) * (b // 2 + 1), seed=15)
        shape = (-(-d // b), -(-d // b), b // 2 + 1)

        def read(impl):
            def fn(a, c):
                g = modes.frequency_accumulator(a, a if same else c, b, impl=impl)
                return jnp.sum(g.real * wr.reshape(shape)) + jnp.sum(g.imag * wi.reshape(shape)), g
            return fn

        (got, got_acc), got_g = jax.value_and_grad(read("pallas"), argnums=(0, 1), has_aux=True)(z1, z2)
        (want, want_acc), want_g = jax.value_and_grad(read("jnp"), argnums=(0, 1), has_aux=True)(z1, z2)
        np.testing.assert_allclose(got_acc, want_acc, atol=1e-4 * float(jnp.max(jnp.abs(want_acc))))
        np.testing.assert_allclose(got, want, rtol=1e-4)
        for g, w in zip(got_g, want_g):
            np.testing.assert_allclose(g, w, atol=1e-4 * float(jnp.max(jnp.abs(w))))


FOURSTEP_CASES = [(4, 12), (8, 24), (16, 36), (8, 64), (3, 25)]


class TestFourStepKernel:
    @pytest.mark.parametrize("n,d", FOURSTEP_CASES)
    def test_spectrum_layout(self, n, d):
        d1, d2 = fops.choose_factors(d)
        x = jax.random.normal(jax.random.PRNGKey(0), (n, d))
        fr, fi = fops.four_step_fft(x, d1, d2)
        ours = (fr + 1j * fi).transpose(0, 2, 1).reshape(n, d)
        np.testing.assert_allclose(ours, fref.spectrum_ref(x), atol=1e-3)

    @pytest.mark.parametrize("n,d", FOURSTEP_CASES)
    @pytest.mark.parametrize("q", [1, 2])
    def test_r_sum_matches_oracle(self, n, d, q):
        z1, z2 = _views(n, d, seed=1)
        got = fops.r_sum_fourstep(z1, z2, q=q, scale=n)
        want = fref.r_sum_ref(z1, z2, q=q, scale=n)
        np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-3)

    def test_sumvec_values(self):
        z1, z2 = _views(8, 40, seed=2)
        np.testing.assert_allclose(
            fops.sumvec_fourstep(z1, z2, scale=8),
            fref.sumvec_ref(z1, z2, scale=8),
            atol=1e-4,
        )

    def test_grads_match_pure_jnp(self):
        n, d = 8, 24
        z1, z2 = _views(n, d, seed=4)
        gk = jax.grad(lambda a, b: fops.r_sum_fourstep(a, b, q=2, scale=n), argnums=(0, 1))(z1, z2)
        gj = jax.grad(lambda a, b: regs.r_sum(a, b, q=2, scale=n), argnums=(0, 1))(z1, z2)
        np.testing.assert_allclose(gk[0], gj[0], atol=1e-4)
        np.testing.assert_allclose(gk[1], gj[1], atol=1e-4)

    def test_ifft_roundtrip(self):
        d1, d2 = 4, 6
        s = jax.random.normal(jax.random.PRNGKey(5), (1, 24))
        fr, fi = fops.four_step_fft(s, d1, d2)
        back = fops.four_step_ifft(fr[0], fi[0], d1, d2)
        np.testing.assert_allclose(back.reshape(-1), s[0], atol=1e-5)


XCORR_CASES = [(8, 16), (16, 40), (64, 16), (24, 128), (7, 33)]


class TestXCorrKernel:
    @pytest.mark.parametrize("n,d", XCORR_CASES)
    def test_matches_oracle(self, n, d):
        z1, z2 = _views(n, d, seed=6)
        got = xops.off_diagonal_sq_sum(z1, z2, scale=n)
        want = xref.off_diagonal_sq_sum_ref(z1, z2, scale=n)
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("n,d", [(8, 16), (32, 8)])
    def test_grads_both_gram_branches(self, n, d):
        z1, z2 = _views(n, d, seed=7)
        gk = jax.grad(lambda a, b: xops.off_diagonal_sq_sum(a, b, scale=n), argnums=(0, 1))(z1, z2)
        gr = jax.grad(lambda a, b: xref.off_diagonal_sq_sum_ref(a, b, scale=n), argnums=(0, 1))(z1, z2)
        np.testing.assert_allclose(gk[0], gr[0], atol=1e-4)
        np.testing.assert_allclose(gk[1], gr[1], atol=1e-4)

    def test_gram_forward(self):
        z1, z2 = _views(16, 48, seed=8)
        np.testing.assert_allclose(
            xops.r_off_gram(z1, z2, scale=16.0),
            xref.off_diagonal_sq_sum_ref(z1, z2, scale=16.0),
            rtol=1e-4,
        )

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_dtypes(self, dtype):
        z1, z2 = _views(16, 32, dtype, seed=9)
        got = xops.off_diagonal_sq_sum(z1, z2, scale=16.0)
        assert bool(jnp.isfinite(got))


class TestKernelLossIntegration:
    def test_bt_loss_with_kernels(self):
        from repro.core import losses as L

        z1, z2 = _views(16, 32, seed=10)
        cfg_k = L.DecorrConfig(style="bt", reg="sum", block_size=8, q=2, use_kernel=True, permute=False)
        cfg_j = L.DecorrConfig(style="bt", reg="sum", block_size=8, q=2, use_kernel=False, permute=False)
        lk, _ = L.barlow_twins_loss(z1, z2, cfg_k)
        lj, _ = L.barlow_twins_loss(z1, z2, cfg_j)
        np.testing.assert_allclose(lk, lj, rtol=1e-4)

    def test_bt_loss_baseline_kernel(self):
        from repro.core import losses as L

        z1, z2 = _views(16, 32, seed=11)
        cfg_k = L.DecorrConfig(style="bt", reg="off", use_kernel=True)
        cfg_j = L.DecorrConfig(style="bt", reg="off", use_kernel=False)
        lk, _ = L.barlow_twins_loss(z1, z2, cfg_k)
        lj, _ = L.barlow_twins_loss(z1, z2, cfg_j)
        np.testing.assert_allclose(lk, lj, rtol=1e-4)
