"""round_div: the int8 quantizer's rounding is numpy's on every backend.

A TPU's f32 division can miss the correctly rounded quotient by an ulp.
The CPU's division is exact, so these tests stand in for the chip by
pushing the quotient guess off by up to two ulps, on inputs built to sit
on and next to every half-integer, and require numpy's answer anyway.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import quantize


def _host(x, s):
    return np.round(x / s)


def _cases():
    rng = np.random.default_rng(5)
    s = rng.uniform(1e-3, 10, (256, 1)).astype(np.float32)
    k = rng.integers(-127, 127, (256, 512)).astype(np.float32)
    out = []
    for base in (k + 0.5, k):                    # half-integers, integers
        x = (base * s).astype(np.float32)
        for step in (-2, -1, 0, 1, 2):
            y = x
            for _ in range(abs(step)):
                y = np.nextafter(y, np.float32(np.inf if step > 0
                                               else -np.inf))
            out.append((y.astype(np.float32), s))
    x = rng.standard_normal((256, 512)).astype(np.float32) * 1e-6
    m = np.abs(x).max(1, keepdims=True)
    out.append((x, (m * np.float32(1 / 127)).astype(np.float32)))
    out.append((np.zeros((8, 512), np.float32), np.ones((8, 1), np.float32)))
    return out


@pytest.mark.parametrize("ulps", [0, 1, -1, 2, -2])
def test_round_div_matches_numpy_with_inexact_division(ulps, monkeypatch):
    def guess(a, s):
        t = a / s
        for _ in range(abs(ulps)):
            t = jnp.nextafter(t, jnp.float32(np.inf if ulps > 0 else 0))
        return t
    monkeypatch.setattr(quantize, "_quotient_guess", guess)
    f = jax.jit(quantize.round_div)
    for x, s in _cases():
        np.testing.assert_array_equal(np.asarray(f(x, s)), _host(x, s))
