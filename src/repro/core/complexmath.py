"""Split-complex arithmetic on (re, im) pairs.

The paper works with separate real and imaginary planes because the Tensix
compute engine has no complex type (Section 4).  The same choice is right on
TPU: Pallas/Mosaic have no complex registers, and split planes keep the
(8, 128) lane layout dense for both the VPU and the MXU.  Every FFT in this
repo therefore operates on a ``SplitComplex`` pair of same-shape float arrays.
"""
from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp
import numpy as np


class SplitComplex(NamedTuple):
    """A complex tensor stored as two same-shape real tensors."""

    re: jnp.ndarray
    im: jnp.ndarray

    @property
    def shape(self):
        return self.re.shape

    @property
    def dtype(self):
        return self.re.dtype

    def astype(self, dtype) -> "SplitComplex":
        return SplitComplex(self.re.astype(dtype), self.im.astype(dtype))


def from_complex(z) -> SplitComplex:
    z = jnp.asarray(z)
    return SplitComplex(jnp.real(z), jnp.imag(z))


def to_complex(z: SplitComplex):
    return z.re + 1j * z.im


def from_real(x) -> SplitComplex:
    x = jnp.asarray(x)
    return SplitComplex(x, jnp.zeros_like(x))


def add(a: SplitComplex, b: SplitComplex) -> SplitComplex:
    return SplitComplex(a.re + b.re, a.im + b.im)


def sub(a: SplitComplex, b: SplitComplex) -> SplitComplex:
    return SplitComplex(a.re - b.re, a.im - b.im)


def mul(a: SplitComplex, b: SplitComplex) -> SplitComplex:
    """4-multiply complex product (paper's Listing 1.1 f0/f1 structure)."""
    return SplitComplex(a.re * b.re - a.im * b.im, a.re * b.im + a.im * b.re)


def mul3(a: SplitComplex, b: SplitComplex) -> SplitComplex:
    """Karatsuba 3-multiply complex product.

    Beyond-paper micro-optimisation: one fewer multiply per element at the
    cost of two extra adds — a win when multiplier throughput, not adder
    throughput, limits the VPU.
    """
    k1 = a.re * (b.re + b.im)
    k2 = b.im * (a.re + a.im)
    k3 = b.re * (a.im - a.re)
    return SplitComplex(k1 - k2, k1 + k3)


def conj(a: SplitComplex) -> SplitComplex:
    return SplitComplex(a.re, -a.im)


def scale(a: SplitComplex, s) -> SplitComplex:
    return SplitComplex(a.re * s, a.im * s)


def allclose(a: SplitComplex, b: SplitComplex, **kw) -> bool:
    return bool(np.allclose(a.re, b.re, **kw) and np.allclose(a.im, b.im, **kw))
