"""One-level Bailey four-step GEMM passes, shared by the 3-D brick kernel
(:mod:`repro.kernels.fft3d_fused`), the fused spectral convolution
(:mod:`repro.kernels.fftconv_fused`) and the :mod:`repro.tt.trace` cost
model.

A length ``n = n1 * n2`` FFT becomes dense DFT-matrix matmuls for both
factors plus a pointwise inter-factor twiddle, fed by host-built float64
tables; ``n1 == 1`` (below the leaf size) means a single dense DFT.  The
factor split reshapes the transformed axis inside the kernel, which Mosaic
refuses when that axis is the lane axis (``unsupported shape cast``), so
the 2-D kernels on the TPU main path (:mod:`repro.kernels.fft2d_gemm`,
:mod:`repro.kernels.rfft2d_fused`) run dense passes instead.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.fft1d import _best_split

# below this length a single dense DFT matmul beats the four-step's extra
# twiddle/reshape traffic (mirrors resolve_algo's naive-leaf region)
FOURSTEP_LEAF = 256

_HIGHEST = jax.lax.Precision.HIGHEST


def fourstep_factors(n: int):
    """(n1, n2) with n = n1 * n2: the one-level four-step split (mirrored
    by the :mod:`repro.tt.trace` cost model).  n1 == 1 means a single
    dense DFT matmul."""
    n1 = 1 if n <= FOURSTEP_LEAF else _best_split(n)
    return n1, n // n1


def fourstep_tables_np(n: int, inverse: bool, factors=None):
    """Host-built float64 tables for one four-step pass of length n, cast
    by the caller: DFT matrices for both factors plus the inter-factor
    twiddle ``T[k1, j2] = exp(sign * 2*pi*i * k1*j2 / n)`` — composed from
    the (lru-cached) builders in :mod:`repro.core.twiddle`.  No 1/n
    scaling — the inverse kernels fold one 1/N at the end.  An explicit
    ``factors`` pair overrides :func:`fourstep_factors` (the 3-D kernel's
    leaf crossover sits one octave lower)."""
    from repro.core.twiddle import _dft_matrix_np, _fourstep_twiddle_np
    n1, n2 = fourstep_factors(n) if factors is None else factors
    assert n1 * n2 == n, (n, n1, n2)
    sign = 1.0 if inverse else -1.0
    w1r, w1i = _dft_matrix_np(n1, sign)
    w2r, w2i = _dft_matrix_np(n2, sign)
    twr, twi = _fourstep_twiddle_np(n1, n2, sign)
    return (w1r, w1i, w2r, w2i, twr, twi)


def _ein(spec, a, b):
    return jnp.einsum(spec, a, b, precision=_HIGHEST)


def fft_last_fourstep(re, im, tabs, n1: int, n2: int):
    """Length-(n1*n2) FFT of the last axis via one four-step level.

    The n1-factor DFT contracts along axis -2 as a *left* multiply
    (einsum), so no transpose is materialised for it; only the four-step
    output reordering transposes the two small factor axes.
    """
    w1r, w1i, w2r, w2i, twr, twi = tabs
    b = re.shape[:-1]
    re = re.reshape(*b, n1, n2)
    im = im.reshape(*b, n1, n2)
    if n1 > 1:
        spec = "ka,...an->...kn"
        yr = _ein(spec, w1r, re) - _ein(spec, w1i, im)
        yi = _ein(spec, w1i, re) + _ein(spec, w1r, im)
        re, im = yr * twr - yi * twi, yr * twi + yi * twr
    mm = lambda p, q: jnp.matmul(p, q, precision=_HIGHEST)
    zr = mm(re, w2r) - mm(im, w2i)
    zi = mm(re, w2i) + mm(im, w2r)
    # output ordering X[k2*n1 + k1] = Z[k1, k2]
    zr = jnp.swapaxes(zr, -1, -2).reshape(*b, n1 * n2)
    zi = jnp.swapaxes(zi, -1, -2).reshape(*b, n1 * n2)
    return zr, zi


def fft_col_fourstep(re, im, tabs, n1: int, n2: int):
    """Length-(n1*n2) FFT along axis -2 of an (..., H, C) tile — the column
    pass — as left-side DFT contractions, absorbing the tile transpose."""
    w1r, w1i, w2r, w2i, twr, twi = tabs
    b = re.shape[:-2]
    c = re.shape[-1]
    re = re.reshape(*b, n1, n2, c)
    im = im.reshape(*b, n1, n2, c)
    if n1 > 1:
        spec = "ka,...anc->...knc"
        yr = _ein(spec, w1r, re) - _ein(spec, w1i, im)
        yi = _ein(spec, w1i, re) + _ein(spec, w1r, im)
        twr = twr[..., None]
        twi = twi[..., None]
        re, im = yr * twr - yi * twi, yr * twi + yi * twr
    spec = "kb,...nbc->...nkc"
    zr = _ein(spec, w2r, re) - _ein(spec, w2i, im)
    zi = _ein(spec, w2i, re) + _ein(spec, w2r, im)
    zr = jnp.swapaxes(zr, -3, -2).reshape(*b, n1 * n2, c)
    zi = jnp.swapaxes(zi, -3, -2).reshape(*b, n1 * n2, c)
    return zr, zi
