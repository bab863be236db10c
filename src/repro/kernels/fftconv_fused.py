"""Fused spectral-convolution Pallas kernel: rfft -> pointwise multiply ->
irfft in ONE VMEM-resident pass.

The unfused ``fftconv`` path is three registry calls — ``rfft(x)``,
``cm.mul``, ``irfft`` — which ships the full half spectrum to HBM twice
per convolution (once out of the forward transform, once back into the
inverse).  On decoupled-data-movement hardware that round-trip *is* the
cost: the pointwise multiply is a rounding error next to the plane
traffic.  This kernel keeps the spectrum in VMEM, runs BOTH transforms
at the packed half length m/2 (four-step FLOPs are superlinear —
``F(m) ~ m * 2*sqrt(m)`` — so half-length passes are also the cheapest
real-input schedule, the same reason ``rfft`` beats a zero-imaginary
full FFT), and folds the whole spectral section into one multiply-add:

- **Even/odd packing per row** — each real row's even/odd samples become
  the re/im planes of one length-m/2 complex row (the classic rfft pack,
  all within a row: no cross-row coupling, no row-count constraint), and
  one forward four-step pass of length m/2 runs per row, producing the
  packed spectrum ``Z``.
- **Packed-domain filter operands** — the Hermitian untangle
  (``X[k] = A[k] Z[k] + B[k] conj(Z[(m/2-k) % (m/2)])`` with
  ``A = (1 - i w^k)/2``, ``B = (1 + i w^k)/2``, ``w = exp(-2*pi*i/m)``),
  the pointwise multiply ``Y = X * K`` against the filter half spectrum,
  and the packed-irfft pre-tangle
  (``Z'[k] = C[k] Y[k] + D[k] conj(Y[m/2-k])`` with
  ``C = (1 + i w^{-k})/2``, ``D = (1 - i w^{-k})/2``) compose — all
  three are elementwise in ``Z`` and its conjugate-reverse — into

      ``Z'[k] = E[k] Z[k] + F[k] conj(Z[(m/2-k) % (m/2)])``

  where ``E = C P + D conj(rev Q)``, ``F = C Q + D conj(rev P)``,
  ``P = K A``, ``Q = K B``.  E and F depend only on the FILTER and the
  twiddles, so :func:`pack_filter` builds them outside the kernel — in
  float64 numpy for concrete filters (cached per filter identity: the
  SSM/Hyena serving pattern pays the pack once), in-graph for traced
  training parameters — and the kernel's entire spectral section is the
  one complex multiply-add above.  E/F are the filter spectrum, linearly
  re-packaged into the packed domain; no information is added or lost.
- **Packed half-length inverse** — one m/2-point inverse four-step pass
  turns ``Z'`` back into the packed time sequence, and the even/odd
  interleave of its re/im planes writes the real row out.

Both FFT passes are one level of Bailey four-step — dense DFT-matrix
matmuls fed by host-built tables passed as operands (12 arrays: forward
+ inverse tables at length m/2).  Per call the kernel moves one real
plane in, the packed filter pair in, and one real plane out — versus the
unfused path's six planes (real in, spectrum out, spectrum + filter in,
product out, product in, real out).

Layout contract: ``x`` is (batch, R, m) real; the packed filter pair is
either (R, m/2) — one filter per row, shared across the batch grid (the
SSM/Hyena channel-bank pattern, staged once per grid step) — or
(batch, R, m/2) for per-batch filter banks.  ``m`` is the pre-padded
power-of-two FFT length; causal padding/truncation happens upstream in
:func:`repro.core.fftconv.fft_conv`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.complexmath import SplitComplex
from repro.kernels.fourstep import (fft_last_fourstep, fourstep_factors,
                                    fourstep_tables_np)


def conv_tables(m: int, dtype):
    """The 12 table operands for one fused conv call: forward + inverse
    four-step tables for the packed length m/2 (the untangle / filter /
    pre-tangle twiddles are all folded into the packed filter operands —
    see :func:`pack_filter`), cast to the working dtype."""
    hm = m // 2
    tabs = fourstep_tables_np(hm, False) + fourstep_tables_np(hm, True)
    return [jnp.asarray(t, dtype) for t in tabs]


# -- packed-domain filter operands ------------------------------------------

_PACK_CACHE = {}   # (lead shape, m, dtype) -> (kf.re, kf.im, packed pair)


def clear_pack_cache() -> None:
    """Drop every cached packed filter pair (called alongside the plan
    registry's spectrum cache — packed operands derive from spectra)."""
    _PACK_CACHE.clear()


def _pack_coeffs(m: int):
    """The four twiddle coefficient vectors of the packed-domain collapse
    (float64): untangle A/B at k = 0..m/2, pre-tangle C/D at
    k = 0..m/2-1."""
    hm = m // 2
    w = np.exp(-2j * np.pi * np.arange(hm + 1) / m)
    a = (1.0 - 1j * w) / 2.0
    b = (1.0 + 1j * w) / 2.0
    c = (1.0 + 1j * np.conj(w[:hm])) / 2.0
    d = (1.0 - 1j * np.conj(w[:hm])) / 2.0
    return a, b, c, d


def _pack_filter_np(kre, kri, m: int, dtype):
    """Concrete filters: build E/F in float64 and cast once."""
    hm = m // 2
    kc = np.asarray(kre, np.float64) + 1j * np.asarray(kri, np.float64)
    # the C2R convention ignores the DC/Nyquist imaginary parts; zero them
    # here so residue in the fp32 spectrum cannot alias across the edges
    kc[..., 0] = kc[..., 0].real
    kc[..., hm] = kc[..., hm].real
    a, b, c, d = _pack_coeffs(m)
    p, q = kc * a, kc * b
    e = c * p[..., :hm] + d * np.conj(q[..., :0:-1])
    f = c * q[..., :hm] + d * np.conj(p[..., :0:-1])
    return (SplitComplex(jnp.asarray(e.real, dtype),
                         jnp.asarray(e.imag, dtype)),
            SplitComplex(jnp.asarray(f.real, dtype),
                         jnp.asarray(f.imag, dtype)))


def _pack_filter_traced(kf: SplitComplex, m: int, dtype):
    """Traced filters (jit-time training parameters): the same E/F build
    as jnp ops — part of the traced graph, recomputed per step because
    the filter itself changes per step."""
    hm = m // 2
    a, b, c, d = _pack_coeffs(m)
    ar, ai, br, bi, cr, ci, dr, di = [
        jnp.asarray(v, dtype) for co in (a, b, c, d)
        for v in (co.real, co.imag)]
    # zero the DC/Nyquist imaginary parts (C2R convention)
    mask = np.ones(hm + 1, np.float64)
    mask[0] = mask[hm] = 0.0
    kr = kf.re.astype(dtype)
    ki = kf.im.astype(dtype) * jnp.asarray(mask, dtype)
    pr, pi = kr * ar - ki * ai, kr * ai + ki * ar
    qr, qi = kr * br - ki * bi, kr * bi + ki * br
    rev = lambda t: jnp.flip(t[..., 1:], -1)          # indices m/2 .. 1
    prr, pri, qrr, qri = rev(pr), rev(pi), rev(qr), rev(qi)
    er = cr * pr[..., :hm] - ci * pi[..., :hm] + dr * qrr + di * qri
    ei = cr * pi[..., :hm] + ci * pr[..., :hm] + di * qrr - dr * qri
    fr = cr * qr[..., :hm] - ci * qi[..., :hm] + dr * prr + di * pri
    fi = cr * qi[..., :hm] + ci * qr[..., :hm] + di * prr - dr * pri
    return SplitComplex(er, ei), SplitComplex(fr, fi)


def pack_filter(kf: SplitComplex, m: int, dtype):
    """Fold the Hermitian untangle, the pointwise filter multiply and the
    packed-irfft pre-tangle into the packed-domain filter pair (E, F)
    with ``Z'[k] = E[k] Z[k] + F[k] conj(Z[(m/2-k) % (m/2)])``.

    kf is the filter half spectrum (..., m/2+1); returns two
    SplitComplex of (..., m/2).  Concrete filters build in float64 and
    are cached per filter identity (one entry per lead-shape/length key,
    the same policy as the plan registry's spectrum cache); traced
    filters build in-graph."""
    if isinstance(kf.re, jax.core.Tracer) or isinstance(kf.im,
                                                        jax.core.Tracer):
        return _pack_filter_traced(kf, m, dtype)
    key = (kf.re.shape[:-1], m, jnp.dtype(dtype).name)
    ent = _PACK_CACHE.get(key)
    if ent is not None and ent[0] is kf.re and ent[1] is kf.im:
        return ent[2]
    ef = _pack_filter_np(kf.re, kf.im, m, dtype)
    _PACK_CACHE[key] = (kf.re, kf.im, ef)
    return ef


# -- the kernel --------------------------------------------------------------

def _check_len(m: int):
    if m & (m - 1) or m < 4:
        raise ValueError("the fused conv kernel needs a power-of-two FFT "
                         f"length >= 4, got {m}")


def _fftconv_kernel(w1rf, w1if, w2rf, w2if, twrf, twif,
                    w1rb, w1ib, w2rb, w2ib, twrb, twib,
                    er_ref, ei_ref, fr_ref, fi_ref, x_ref, o_ref, *,
                    m: int, n1: int, n2: int, shared: bool):
    """One batch tile: packed forward FFT, the packed-domain filter
    multiply-add, packed inverse FFT — the spectrum never leaves VMEM."""
    x = x_ref[...]                               # (bb, r, m) real
    bb, r = x.shape[0], x.shape[1]
    re = x[..., 0::2]                            # even/odd samples -> one
    im = x[..., 1::2]                            # complex row: (bb, r, m/2)
    tf = (w1rf[...], w1if[...], w2rf[...], w2if[...], twrf[...], twif[...])
    zr, zi = fft_last_fourstep(re, im, tf, n1, n2)
    # the whole spectral section: Z' = E Z + F conj(Z[(m/2-k) % (m/2)]).
    # The conjugate-reverse index is a flip with DC fixed — one concat.
    zcr = jnp.concatenate([zr[..., :1], jnp.flip(zr[..., 1:], -1)], -1)
    zci = jnp.concatenate([zi[..., :1], jnp.flip(zi[..., 1:], -1)], -1)
    er, ei = er_ref[...], ei_ref[...]
    fr, fi = fr_ref[...], fi_ref[...]
    if shared:                                   # (r, m/2) -> broadcast bb
        er, ei, fr, fi = er[None], ei[None], fr[None], fi[None]
    z2r = er * zr - ei * zi + fr * zcr + fi * zci
    z2i = er * zi + ei * zr + fi * zcr - fr * zci
    tb = (w1rb[...], w1ib[...], w2rb[...], w2ib[...], twrb[...], twib[...])
    z2r, z2i = fft_last_fourstep(z2r, z2i, tb, n1, n2)
    out = jnp.stack([z2r, z2i], 3).reshape(bb, r, m)  # even/odd interleave
    o_ref[...] = out * jnp.asarray(2.0 / m, out.dtype)


def fftconv_fused_pallas(x: jnp.ndarray, ef, *,
                         block_batch: int = 1,
                         interpret: bool = True) -> jnp.ndarray:
    """Batched fused FFT convolution: x of (batch, r, m) real circularly
    convolved with the packed filter pair ef = (E, F) from
    :func:`pack_filter` — each (r, m/2) (shared bank) or (batch, r, m/2)
    (per-batch banks) -> (batch, r, m) real."""
    batch, r, m = x.shape
    _check_len(m)
    hm = m // 2
    e, f = ef
    shared = e.re.ndim == 2
    want = (r, hm) if shared else (batch, r, hm)
    assert e.re.shape == want and f.re.shape == want, (e.re.shape, want)
    bb = min(block_batch, batch)
    assert batch % bb == 0, (batch, bb)
    ops = conv_tables(m, x.dtype)
    n1, n2 = fourstep_factors(hm)
    kernel = functools.partial(_fftconv_kernel, m=m, n1=n1, n2=n2,
                               shared=shared)
    grid = (batch // bb,)
    tspecs = [pl.BlockSpec(t.shape, lambda i, nd=t.ndim: (0,) * nd)
              for t in ops]
    if shared:
        ef_spec = pl.BlockSpec((r, hm), lambda i: (0, 0))
    else:
        ef_spec = pl.BlockSpec((bb, r, hm), lambda i: (i, 0, 0))
    io_spec = pl.BlockSpec((bb, r, m), lambda i: (i, 0, 0))
    return pl.pallas_call(
        kernel, grid=grid,
        in_specs=tspecs + [ef_spec] * 4 + [io_spec],
        out_specs=io_spec,
        out_shape=jax.ShapeDtypeStruct((batch, r, m), x.dtype),
        interpret=interpret)(*ops, e.re, e.im, f.re, f.im, x)
