"""Unit tests for the paper's core algebra (Eq. 5-12)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import sumvec as sv
from repro.core import regularizers as regs


def _views(n=16, d=24, seed=0):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    return jax.random.normal(k1, (n, d)), jax.random.normal(k2, (n, d))


class TestInvolution:
    def test_definition(self):
        x = jnp.arange(6.0)
        out = sv.involution(x)
        # [x]_{(d-i) mod d}
        np.testing.assert_allclose(out, jnp.asarray([0.0, 5, 4, 3, 2, 1]))

    def test_involution_is_self_inverse(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (11,))
        np.testing.assert_allclose(sv.involution(sv.involution(x)), x)

    def test_fourier_conjugation(self):
        # F(inv(x)) == conj(F(x))  (the identity Eq. 11 relies on)
        x = jax.random.normal(jax.random.PRNGKey(1), (16,))
        lhs = jnp.fft.fft(sv.involution(x))
        rhs = jnp.conj(jnp.fft.fft(x))
        np.testing.assert_allclose(lhs, rhs, atol=1e-5)


class TestCircularOps:
    def test_convolution_theorem(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (12,))
        y = jax.random.normal(jax.random.PRNGKey(1), (12,))
        direct = sv.circular_convolve(x, y)
        via_fft = jnp.fft.ifft(jnp.fft.fft(x) * jnp.fft.fft(y)).real
        np.testing.assert_allclose(direct, via_fft, atol=1e-5)

    def test_circular_correlation_identity(self):
        # inv(x) * y == circular correlation (Appendix A)
        x = jax.random.normal(jax.random.PRNGKey(2), (10,))
        y = jax.random.normal(jax.random.PRNGKey(3), (10,))
        lhs = sv.circular_convolve(sv.involution(x), y)
        rhs = sv.circular_correlate_naive(x[None], y[None])[0]
        np.testing.assert_allclose(lhs, rhs, atol=1e-5)


class TestSumvec:
    @pytest.mark.parametrize("d", [8, 13, 24, 64])
    def test_fft_equals_matrix_route(self, d):
        z1, z2 = _views(d=d)
        c = regs.cross_correlation_matrix(z1, z2, scale=16)
        fft = sv.sumvec_fft(z1, z2, scale=16.0)
        mat = sv.sumvec_from_matrix(c)
        np.testing.assert_allclose(fft, mat, atol=1e-4)

    def test_direct_equals_fft(self):
        z1, z2 = _views()
        np.testing.assert_allclose(
            sv.sumvec_direct(z1, z2), sv.sumvec_fft(z1, z2), atol=1e-3
        )

    def test_zeroth_is_trace(self):
        z1, z2 = _views()
        c = regs.cross_correlation_matrix(z1, z2)
        svec = sv.sumvec_from_matrix(c)
        np.testing.assert_allclose(svec[0], jnp.trace(c), rtol=1e-5)

    def test_components_partition_matrix(self):
        # every element of C appears in exactly one component (paper §4.1)
        z1, z2 = _views(d=8)
        c = regs.cross_correlation_matrix(z1, z2)
        svec = sv.sumvec_from_matrix(c)
        np.testing.assert_allclose(jnp.sum(svec), jnp.sum(c), rtol=1e-4)


class TestGrouped:
    @pytest.mark.parametrize("b", [4, 7, 8, 24])
    def test_grouped_fft_equals_matrix_blocks(self, b):
        z1, z2 = _views(d=24)
        c = regs.cross_correlation_matrix(z1, z2, scale=16)
        got = sv.grouped_sumvec_fft(z1, z2, b, scale=16.0)
        want = sv.grouped_sumvec_from_matrix(c, b)
        np.testing.assert_allclose(got, want, atol=1e-4)

    def test_padding_contributes_zero(self):
        # d=10 with b=4 pads 2 dummy features; they must not change block
        # sums that exclude them
        z1, z2 = _views(d=10)
        g = sv.grouped_sumvec_fft(z1, z2, 4)
        assert g.shape == (3, 3, 4)
        assert bool(jnp.all(jnp.isfinite(g)))


class TestParseval:
    @pytest.mark.parametrize("d", [8, 9, 16, 33])
    def test_sq_sum_and_zeroth(self, d):
        s = jax.random.normal(jax.random.PRNGKey(0), (d,))
        g = jnp.fft.rfft(s)
        sq, s0 = sv.sq_sum_and_zeroth_from_freq(g, d)
        np.testing.assert_allclose(sq, jnp.sum(s**2), rtol=1e-5)
        np.testing.assert_allclose(s0, s[0], atol=1e-5)


class TestRfftVjp:
    @pytest.mark.parametrize("d", [8, 9, 16, 33])
    def test_matches_jax_rfft_transpose(self, d):
        x = jax.random.normal(jax.random.PRNGKey(0), (3, d))
        k1, k2 = jax.random.split(jax.random.PRNGKey(1))
        t = jax.lax.complex(
            jax.random.normal(k1, (3, d // 2 + 1)), jax.random.normal(k2, (3, d // 2 + 1))
        )
        want = jax.vjp(lambda a: jnp.fft.rfft(a, axis=-1), x)[1](t)[0]
        got = jax.vjp(sv.rfft, x)[1](t)[0]
        np.testing.assert_allclose(got, want, atol=1e-4)
