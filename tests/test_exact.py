"""ops/exact.py: the arithmetic that keeps chip and CPU placements
bit-identical. The chip side is checked by chip_smoke.py; here the CPU
side and the correction logic are pinned against numpy's IEEE results."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from open_simulator_tpu.ops import exact

N = 1 << 16


def _bits(x):
    return np.asarray(x, np.float32).view(np.int32)


def _pairs(kind, rng):
    if kind == "int":
        return (rng.randint(0, 2_000_000, N).astype(np.float32),
                rng.randint(1, 70_000, N).astype(np.float32))
    if kind == "signed":
        return (rng.uniform(-200, 200, N).astype(np.float32),
                rng.uniform(0.01, 200, N).astype(np.float32)
                * rng.choice([-1, 1], N).astype(np.float32))
    return ((2.0 ** rng.uniform(-45, 45, N)).astype(np.float32),
            (2.0 ** rng.uniform(-45, 45, N)).astype(np.float32))


@pytest.mark.parametrize("kind", ["int", "signed", "wide"])
def test_div_is_correctly_rounded(kind):
    a, b = _pairs(kind, np.random.RandomState(0))
    got = jax.jit(exact.div)(a, b)
    np.testing.assert_array_equal(_bits(got), _bits(a / b))


@pytest.mark.parametrize("kind", ["int", "signed", "wide"])
def test_div_corrects_an_estimate_two_ulps_off(kind):
    """The TPU's divide lands up to two ulps from the IEEE quotient; the
    correction must recover it from any such estimate."""
    rng = np.random.RandomState(1)
    a, b = _pairs(kind, rng)
    ref = (a / b).astype(np.float32)
    off = (_bits(ref) + rng.randint(-2, 3, N).astype(np.int32)).view(np.float32)
    off = np.where(ref == 0, ref, off)
    got = jax.jit(exact._round_quotient)(a, b, off)
    np.testing.assert_array_equal(_bits(got), _bits(ref))


def test_mul_rounds_before_the_add():
    """XLA's CPU backend fuses a*b + c into one FMA; the TPU rounds a*b
    first. mul must give the TPU's (IEEE) answer on the CPU."""
    rng = np.random.RandomState(2)
    a = rng.uniform(0, 200, N).astype(np.float32)
    b = rng.uniform(0, 3, N).astype(np.float32)
    c = rng.uniform(-50, 50, N).astype(np.float32)
    separate = (a * b).astype(np.float32) + c
    fused = jax.jit(lambda a, b, c: a * b + c)(a, b, c)
    assert np.any(_bits(fused) != _bits(separate)), (
        "XLA CPU stopped fusing a*b + c: mul may no longer be needed")
    got = jax.jit(lambda a, b, c: exact.mul(a, b) + c)(a, b, c)
    np.testing.assert_array_equal(_bits(got), _bits(separate))


def test_log_table_is_the_float64_log_rounded_once():
    """One table on every backend: kube-scheduler's float64 log rounded to
    f32 — within one ulp of XLA's f32 log, bit-equal to it mostly."""
    table = jax.jit(lambda i: exact.log_table(5000)[i])(jnp.arange(2, 5000))
    want = np.log(np.arange(2, 5000, dtype=np.float64)).astype(np.float32)
    np.testing.assert_array_equal(_bits(table), _bits(want))
    xla = jnp.log(jnp.arange(2, 5000, dtype=jnp.float32))
    assert np.max(np.abs(_bits(table).astype(np.int64)
                         - _bits(xla).astype(np.int64))) <= 1
