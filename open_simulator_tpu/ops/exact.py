"""Arithmetic that gives the same bits on every backend.

Placements are argmaxes over float scores, so a one-ulp difference in a
score part can move a pod. IEEE add, subtract and multiply round the
same way on the TPU and the CPU; matmuls, division, logarithms and a
product that feeds a sum do not:

* a TPU matmul at default precision rounds f32 operands to bf16, exact
  for integers only up to 256 — `mm` runs at HIGHEST precision;
* the TPU's f32 divide is a refined reciprocal that lands an ulp or two
  off in about a third of cases — `div` corrects it to the correctly
  rounded quotient, which the CPU already returns, using only add,
  subtract and multiply;
* `jnp.log` differs in most last bits — `log_table` is a trace-time
  constant of float64 logs rounded to f32 (kube-scheduler's own
  float64 `math.Log`) for the small integer arguments the engine needs;
* XLA's CPU backend fuses a product into the add that consumes it (one
  rounding, an FMA) where the TPU rounds the product first — `mul`
  rounds it first on both. Score math uses it for every inexact product
  that is added to or subtracted from.

On the CPU `mm` and `div` return what the plain operation returns;
`mul` returns what the TPU returns.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def mm(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Exact f32 matmul for count aggregations."""
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def mul(a, b):
    """a * b rounded before any add that consumes it: LLVM fuses a
    multiply only into its sole user, and p + (p - p) == p for finite p."""
    p = a * b
    return p + (p - p)


def _two_sum(a, b):
    """s + t == a + b exactly (Knuth), t the rounding error of s."""
    s = a + b
    bv = s - a
    av = s - bv
    return s, (a - av) + (b - bv)


def _split(a):
    """a == hi + lo with 12-bit halves (Veltkamp, 2**12 + 1)."""
    c = mul(a, jnp.float32(4097.0))
    hi = c - (c - a)
    return hi, a - hi


def _two_prod(a, b):
    """p + e == a * b exactly (Dekker), p the rounded product."""
    p = mul(a, b)
    ah, al = _split(a)
    bh, bl = _split(b)
    return p, ((ah * bh - p) + ah * bl + al * bh) + al * bl


def _sign3(x, y, z):
    """Exact sign of x + y + z: grow the non-overlapping expansion of the
    sum (Shewchuk) and read its largest nonzero component."""
    s, r = _two_sum(x, y)
    q, h1 = _two_sum(z, r)
    q, h2 = _two_sum(q, s)
    top = jnp.where(q != 0, q, jnp.where(h2 != 0, h2, h1))
    return jnp.sign(top)


def _above_mid(a, b, q, nb):
    """sign(a - m*b) for m the midpoint of q and its neighbour nb: the
    midpoint's product is q*b + (nb - q)/2 * b, the second term exact."""
    p, e = _two_prod(q, b)
    h = (nb - q) * jnp.float32(0.5) * b
    return _sign3(a - p, -e, -h)   # a - p is exact: p is within ulps of a


def _neighbours(q):
    bits = jax.lax.bitcast_convert_type(q, jnp.int32)
    up = jax.lax.bitcast_convert_type(bits + 1, jnp.float32)
    dn = jax.lax.bitcast_convert_type(bits - 1, jnp.float32)
    return up, dn


def div(a, b):
    """a / b, correctly rounded on every backend (finite b != 0; zero,
    huge and subnormal quotients come from the backend's divide)."""
    a = jnp.asarray(a, jnp.float32)
    b = jnp.asarray(b, jnp.float32)
    return _round_quotient(a, b, a / b)


def _round_quotient(a, b, q0):
    """The correctly rounded a / b from an estimate q0 within two ulps."""
    aa, bb = jnp.abs(a), jnp.abs(b)
    q = jnp.abs(q0)
    for _ in range(2):
        up, dn = _neighbours(q)
        q = jnp.where(_above_mid(aa, bb, q, up) > 0, up,
                      jnp.where(_above_mid(aa, bb, q, dn) < 0, dn, q))
    tiny, huge = np.float32(2.0 ** -100), np.float32(2.0 ** 100)
    plain = ((q0 == 0) | ~(jnp.abs(q0) > tiny) | ~(jnp.abs(q0) < huge)
             | ~(aa < huge) | ~(bb < huge) | ~(bb > tiny))
    return jnp.where(plain, q0, jnp.sign(a) * jnp.sign(b) * q)


@functools.lru_cache(maxsize=16)
def _log_values(n: int) -> np.ndarray:
    with np.errstate(divide="ignore"):   # log(0) = -inf, never read
        return np.log(np.arange(n, dtype=np.float64)).astype(np.float32)


def log_table(n: int) -> jnp.ndarray:
    """[n] f32 with log(k) at k: the float64 log rounded once to f32 — a
    trace-time constant, so every backend reads the same bits."""
    return jnp.asarray(_log_values(int(n)))
