"""Scheduling policy tests (paper Algorithm 1)."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.policies import adaptive_probs, long_term_probs, uniform_probs


def arr(x):
    return jnp.asarray(x)


class TestUniform:
    def test_all_available(self):
        p = uniform_probs(None, None, arr([True, True, True, True]))
        np.testing.assert_allclose(p, [0.25] * 4)

    def test_some_unavailable(self):
        p = uniform_probs(None, None, arr([True, False, True, False]))
        np.testing.assert_allclose(p, [0.5, 0.0, 0.5, 0.0])

    def test_none_available(self):
        p = uniform_probs(None, None, arr([False, False]))
        np.testing.assert_allclose(p, [0.0, 0.0])


class TestLongTerm:
    def test_eq6_normalization(self):
        """Eq. (6): r_i = q_lim,i / sum q_lim,j."""
        q = arr([0.2, 0.3, 0.5])
        p = long_term_probs(q, None, arr([True] * 3))
        np.testing.assert_allclose(p, [0.2, 0.3, 0.5], rtol=1e-6)

    def test_mask_renormalizes(self):
        q = arr([0.2, 0.3, 0.5])
        p = long_term_probs(q, None, arr([True, False, True]))
        np.testing.assert_allclose(p, [0.2 / 0.7, 0.0, 0.5 / 0.7], rtol=1e-6)

    def test_richer_device_preferred(self):
        q = arr([0.1, 0.6])
        p = long_term_probs(q, None, arr([True, True]))
        assert p[1] > p[0]


class TestAdaptive:
    def test_critical_devices_downweighted(self):
        """Alg. 1 line 25: PM1 devices scaled by z = alpha/N."""
        q = arr([0.25, 0.25, 0.25, 0.25])
        pm = arr([1, 2, 3, 2])  # device 0 critical
        p = adaptive_probs(q, pm, arr([True] * 4))
        # alpha = 1 critical device, N = 4 -> z = 1/4; x0 = 0.25 * 0.25.
        expected = np.array([0.0625, 0.25, 0.25, 0.25])
        expected /= expected.sum()
        np.testing.assert_allclose(p, expected, rtol=1e-5)
        assert p[0] < p[1]

    def test_no_critical_reduces_to_long_term(self):
        q = arr([0.2, 0.3, 0.5])
        pm = arr([2, 3, 2])
        p = adaptive_probs(q, pm, arr([True] * 3))
        np.testing.assert_allclose(p, [0.2, 0.3, 0.5], rtol=1e-5)

    def test_all_critical_reduces_to_long_term(self):
        """If every device is PM1, the z-scaling cancels after renorm."""
        q = arr([0.2, 0.8])
        pm = arr([1, 1])
        p = adaptive_probs(q, pm, arr([True, True]))
        np.testing.assert_allclose(p, [0.2, 0.8], rtol=1e-5)

    def test_explicit_alpha(self):
        q = arr([0.5, 0.5])
        pm = arr([1, 3])
        p = adaptive_probs(q, pm, arr([True, True]), alpha=2.0)
        # z = 2/2 = 1 -> no down-weighting.
        np.testing.assert_allclose(p, [0.5, 0.5], rtol=1e-5)

    def test_probability_simplex(self):
        q = arr([0.3, 0.1, 0.6])
        pm = arr([1, 1, 2])
        p = adaptive_probs(q, pm, arr([True, True, False]))
        assert float(jnp.sum(p)) == pytest.approx(1.0, abs=1e-6)
        assert float(p[2]) == 0.0


@pytest.mark.parametrize("fn", [uniform_probs, long_term_probs, adaptive_probs])
def test_host_arrays_stay_on_the_host(fn):
    """The serving router routes every admission: on host arrays the
    policies compute in NumPy (no device dispatch, no device->host read)
    and agree with the traced jax.numpy path."""
    q = np.asarray([0.3, 0.1, 0.6], np.float32)
    pm = np.asarray([1, 2, 1])
    avail = np.asarray([True, True, False])
    host = fn(q, pm, avail)
    assert isinstance(host, np.ndarray)
    np.testing.assert_allclose(host, fn(arr(q), arr(pm), arr(avail)), rtol=1e-6)
