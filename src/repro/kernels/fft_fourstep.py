"""Four-step (Bailey) FFT Pallas kernel — the MXU formulation.

N = n1*n2: column DFTs as a (n1,n1) complex matmul, pointwise twiddle, row
DFTs as a (n2,n2) complex matmul, output transpose.  Every FLOP except the
twiddle multiply lands on the MXU; matmul operand dims are chosen MXU-aligned
(n1, n2 multiples of 128 whenever N allows).

This is the beyond-paper headline (DESIGN.md §2): the paper found the Tensix
matrix and vector units interchangeable for FFT; on TPU the MXU is ~50x the
VPU for f32 MACs, so reformulating the butterflies as dense DFT matmuls
converts a movement-bound kernel into a compute-dense one.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.complexmath import SplitComplex
from repro.core import twiddle as tw
from .fft2d_gemm import mxu_dot


def _split_n(n: int) -> tuple:
    """Factor n = n1*n2 with n1 <= n2, both as close to sqrt(n) and as
    MXU-friendly (multiples of 128, else powers of two) as possible."""
    best = None
    for n1 in range(1, int(np.sqrt(n)) + 1):
        if n % n1 == 0:
            best = n1
    n1 = best
    return n1, n // n1


def _cmatmul(ar, ai, br, bi, *, left: bool):
    """Complex matmul via 4 real matmuls; left: W@A else A@W."""
    dot = mxu_dot
    if left:
        return (dot(br, ar) - dot(bi, ai), dot(br, ai) + dot(bi, ar))
    return (dot(ar, br) - dot(ai, bi), dot(ar, bi) + dot(ai, br))


def _fourstep_kernel(w1r_ref, w1i_ref, w2r_ref, w2i_ref, tr_ref, ti_ref,
                     xre_ref, xim_ref, ore_ref, oim_ref,
                     *, n1: int, n2: int, inverse: bool):
    b = xre_ref.shape[0]
    n = n1 * n2
    # (1) column DFTs: fold batch into the contraction's RHS free dim so the
    # whole tile is ONE (n1 x n1) @ (n1 x b*n2) MXU matmul per plane.
    ar = xre_ref[...].reshape(b, n1, n2).transpose(1, 0, 2).reshape(n1, b * n2)
    ai = xim_ref[...].reshape(b, n1, n2).transpose(1, 0, 2).reshape(n1, b * n2)
    br_, bi_ = _cmatmul(ar, ai, w1r_ref[...], w1i_ref[...], left=True)
    br_ = br_.reshape(n1, b, n2).transpose(1, 0, 2)      # (b, n1, n2)
    bi_ = bi_.reshape(n1, b, n2).transpose(1, 0, 2)
    # (2) pointwise twiddle T[k1, n2]
    tr_v = tr_ref[...]
    ti_v = ti_ref[...]
    cr = br_ * tr_v - bi_ * ti_v
    ci = br_ * ti_v + bi_ * tr_v
    # (3) row DFTs: (b*n1, n2) @ (n2, n2)
    cr2 = cr.reshape(b * n1, n2)
    ci2 = ci.reshape(b * n1, n2)
    dr, di = _cmatmul(cr2, ci2, w2r_ref[...], w2i_ref[...], left=False)
    dr = dr.reshape(b, n1, n2).transpose(0, 2, 1).reshape(b, n)
    di = di.reshape(b, n1, n2).transpose(0, 2, 1).reshape(b, n)
    if inverse:
        s = jnp.asarray(1.0 / n, dr.dtype)
        dr, di = dr * s, di * s
    ore_ref[...] = dr.astype(ore_ref.dtype)
    oim_ref[...] = di.astype(oim_ref.dtype)


def fft_fourstep_pallas(x: SplitComplex, *, inverse: bool = False,
                        block_batch: int = 4, n1: int = None,
                        interpret: bool = True) -> SplitComplex:
    """Batched four-step FFT along the last axis: (batch, n) planes."""
    batch, n = x.re.shape
    if n1 is None:
        n1, n2 = _split_n(n)
    else:
        n2 = n // n1
    assert n1 * n2 == n and n1 > 1, (n, n1)
    bb = min(block_batch, batch)
    assert batch % bb == 0, (batch, bb)

    w1 = tw.dft_matrix(n1, inverse=inverse, dtype=x.dtype)
    w2 = tw.dft_matrix(n2, inverse=inverse, dtype=x.dtype)
    t = tw.fourstep_twiddle(n1, n2, inverse=inverse, dtype=x.dtype)

    grid = (batch // bb,)
    data_spec = pl.BlockSpec((bb, n), lambda i: (i, 0))
    w1_spec = pl.BlockSpec((n1, n1), lambda i: (0, 0))
    w2_spec = pl.BlockSpec((n2, n2), lambda i: (0, 0))
    t_spec = pl.BlockSpec((n1, n2), lambda i: (0, 0))

    kernel = functools.partial(_fourstep_kernel, n1=n1, n2=n2, inverse=inverse)
    out_shape = [jax.ShapeDtypeStruct((batch, n), x.dtype)] * 2
    ore, oim = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[w1_spec, w1_spec, w2_spec, w2_spec, t_spec, t_spec,
                  data_spec, data_spec],
        out_specs=[data_spec, data_spec],
        out_shape=out_shape,
        interpret=interpret,
    )(w1.re, w1.im, w2.re, w2.im, t.re, t.im, x.re, x.im)
    return SplitComplex(ore, oim)
