"""The plain reference, and the control that must fail the comparison.

Reference: float64 ``numpy.fft`` of the same operations on the same
inputs.  It imports nothing of the program under test.

Control: the same operations as dense DFT matrix products computed at
``Precision.HIGH`` -- three bf16 passes per float32 product, the step below
the ``HIGHEST`` (six-pass) precision the configurations state.  The passes
are spelled out (hi/lo bf16 split, products accumulated in float32), so the
control reads the same on a CPU as on the chip.

Operators: the spectral multipliers the cells apply between a forward and
an inverse transform.  They are made here in float64 and rounded once to
float32; the program and the reference are given the same rounded values.
"""
from __future__ import annotations

import numpy as np


# -- operators ---------------------------------------------------------------

def fresnel(shape, a: float) -> np.ndarray:
    """Fresnel (paraxial angular-spectrum) transfer function over the full
    (H, W) spectrum: exp(-i*pi*a*(fy^2 + fx^2)), frequencies in cycles per
    sample, ``a`` = wavelength * distance / pixel^2.  Unit modulus."""
    h, w = shape
    fy = np.fft.fftfreq(h)[:, None]
    fx = np.fft.fftfreq(w)[None, :]
    return np.exp(-1j * np.pi * a * (fy ** 2 + fx ** 2))


def gaussian_blur(shape, sigma: float) -> np.ndarray:
    """Gaussian blur of ``sigma`` pixels over the (H, W/2+1) half
    spectrum: real and even in the row frequency."""
    h, w = shape
    fy = np.fft.fftfreq(h)[:, None]
    fx = np.fft.rfftfreq(w)[None, :]
    return np.exp(-2.0 * np.pi ** 2 * sigma ** 2 * (fy ** 2 + fx ** 2))


OPERATORS = {"fresnel": fresnel, "gaussian_blur": gaussian_blur}


def operator(name: str, shape, param: float) -> np.ndarray:
    """The operator rounded to float32 parts (complex64 or float32)."""
    op = OPERATORS[name](shape, param)
    return op.astype(np.complex64 if np.iscomplexobj(op) else np.float32)


# -- float64 reference -------------------------------------------------------

def fft2(z) -> np.ndarray:
    return np.fft.fft2(np.asarray(z, np.complex128))


def rfft2(x) -> np.ndarray:
    return np.fft.rfft2(np.asarray(x, np.float64))


def propagate(z, h) -> np.ndarray:
    """c2c round trip through a full-spectrum multiplier."""
    return np.fft.ifft2(fft2(z) * np.asarray(h, np.complex128))


def filter_real(x, g) -> np.ndarray:
    """r2c -> half-spectrum multiplier -> c2r round trip."""
    x = np.asarray(x, np.float64)
    return np.fft.irfft2(rfft2(x) * np.asarray(g, np.float64),
                         s=x.shape[-2:])


def rel_l2(got, ref) -> float:
    """Relative L2 error of ``got`` against ``ref`` (a finite value, or inf
    for a result with a non-finite entry or the wrong shape)."""
    got = np.asarray(got)
    ref = np.asarray(ref)
    if got.shape != ref.shape or not np.all(np.isfinite(got)):
        return float("inf")
    got = got.astype(np.complex128 if np.iscomplexobj(got) else np.float64)
    return float(np.linalg.norm(got - ref) / np.linalg.norm(ref))


# -- control: the same operations at Precision.HIGH --------------------------

def dft_matrix(n: int, *, inverse: bool = False):
    """(re, im) float32 planes of the n-point DFT matrix (symmetric);
    the inverse carries the 1/n."""
    k = np.arange(n)
    ang = 2 * np.pi * (np.outer(k, k) % n) / n
    sign = 1.0 if inverse else -1.0
    scale = 1.0 / n if inverse else 1.0
    return ((np.cos(ang) * scale).astype(np.float32),
            (sign * np.sin(ang) * scale).astype(np.float32))


def c2r_matrix(w: int):
    """(C, S) float32 (W/2+1, W): x = Re(Y) @ C + Im(Y) @ S is the c2r
    transform of the last axis (numpy's irfft, imaginary parts of the DC
    and Nyquist bins ignored)."""
    k = np.arange(w // 2 + 1)[:, None]
    n = np.arange(w)[None, :]
    wt = np.where((k == 0) | (k == w // 2), 1.0, 2.0) / w
    ang = 2 * np.pi * ((k * n) % w) / w
    return ((wt * np.cos(ang)).astype(np.float32),
            (-wt * np.sin(ang)).astype(np.float32))


def _high(a, b, spec: str):
    """float32 einsum at Precision.HIGH: hi*hi + hi*lo + lo*hi in bf16
    with float32 accumulation.  ``reduce_precision`` makes the hi part: a
    float32 -> bf16 -> float32 round trip would be folded away by XLA on
    the TPU (excess precision), leaving lo = 0 and one bf16 pass."""
    import jax
    import jax.numpy as jnp

    def split(t):
        hi = jax.lax.reduce_precision(t, exponent_bits=8, mantissa_bits=7)
        return hi.astype(jnp.bfloat16), (t - hi).astype(jnp.bfloat16)

    ah, al = split(a)
    bh, bl = split(b)
    dot = lambda p, q: jnp.einsum(spec, p, q,
                                  preferred_element_type=jnp.float32)
    return dot(ah, bh) + dot(ah, bl) + dot(al, bh)


def _cmul(ar, ai, br, bi, spec):
    return (_high(ar, br, spec) - _high(ai, bi, spec),
            _high(ar, bi, spec) + _high(ai, br, spec))


ROWS = "...hw,wk->...hk"      # transform the last axis
COLS = "kh,...hw->...kw"      # transform the second-to-last axis


def control_fft2(zr, zi, fh, fw):
    """c2c 2-D DFT of (zr, zi) with DFT matrices ``fh`` (H) and ``fw`` (W),
    each a (re, im) pair."""
    yr, yi = _cmul(zr, zi, fw[0], fw[1], ROWS)
    return _cmul(fh[0], fh[1], yr, yi, COLS)


def control_rfft2(x, fh, fw_half):
    """r2c 2-D DFT: ``fw_half`` is the first W/2+1 columns of the W-point
    DFT matrix."""
    yr, yi = _high(x, fw_half[0], ROWS), _high(x, fw_half[1], ROWS)
    return _cmul(fh[0], fh[1], yr, yi, COLS)


def control_irfft2(yr, yi, gh, c2r):
    """c2r 2-D inverse: ``gh`` the inverse H-point DFT matrix, ``c2r`` the
    (C, S) pair of :func:`c2r_matrix`."""
    zr, zi = _cmul(gh[0], gh[1], yr, yi, COLS)
    return _high(zr, c2r[0], ROWS) + _high(zi, c2r[1], ROWS)


def control_tables(shape, *, real: bool):
    """The DFT tables the control needs for ``shape`` (host arrays)."""
    h, w = shape
    fh, gh = dft_matrix(h), dft_matrix(h, inverse=True)
    if not real:
        return {"fh": fh, "gh": gh, "fw": dft_matrix(w),
                "gw": dft_matrix(w, inverse=True)}
    fw = dft_matrix(w)
    return {"fh": fh, "gh": gh,
            "fw_half": (fw[0][:, :w // 2 + 1].copy(),
                        fw[1][:, :w // 2 + 1].copy()),
            "c2r": c2r_matrix(w)}


def control_propagate(zr, zi, hr, hi, t):
    """:func:`propagate` at Precision.HIGH."""
    import jax.numpy as jnp
    yr, yi = control_fft2(zr, zi, t["fh"], t["fw"])
    yr, yi = yr * hr - yi * hi, yr * hi + yi * hr
    out = control_fft2(yr, yi, t["gh"], t["gw"])
    return jnp.stack(out)


def control_filter(x, g, t):
    """:func:`filter_real` at Precision.HIGH."""
    yr, yi = control_rfft2(x, t["fh"], t["fw_half"])
    return control_irfft2(yr * g, yi * g, t["gh"], t["c2r"])


def control_forward(z, t, *, real: bool):
    """A forward transform at Precision.HIGH: (re, im) stacked."""
    import jax.numpy as jnp
    if real:
        return jnp.stack(control_rfft2(z, t["fh"], t["fw_half"]))
    return jnp.stack(control_fft2(z[0], z[1], t["fh"], t["fw"]))
