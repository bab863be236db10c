"""Single-device 2-D / 3-D FFTs (the paper's Section 5 workload, one chip).

Two execution paths behind the plan registry's ``backend`` switch:

- ``backend="jnp"`` — row-column decomposition: FFT the last axis, global
  transpose, FFT again.  The explicit transpose mirrors the paper's global
  transpose between the two 1-D passes; XLA lowers it to an in-HBM relayout.
- ``backend="pallas"`` — the GEMM-formulated fused kernel
  (:mod:`repro.kernels.fft2d_gemm`, ``algo="fused"``): both 1-D passes run
  as DFT matmuls inside one kernel — a radix-r VPU combine and 128-point
  products for fp32 axes of 256–1024, dense passes otherwise — so the
  global transpose never reaches HBM.  ``algo="fused_stockham"`` keeps the previous
  Stockham-stage fused kernel (:mod:`repro.kernels.fft2d_fused`) as the
  explicit-algo oracle, and ``algo="row_col"`` the transpose-based
  two-kernel pipeline as the measured baseline.

``fft2`` and ``fft3`` with ``algo="auto"`` route through
:func:`repro.core.plan.get_plan` so the (shape, dtype, direction, backend)
decision — and any autotune result — is resolved once and reused; 3-D
pallas keys resolve to the fused pencil-in-VMEM kernel
(:mod:`repro.kernels.fft3d_fused`).  The distributed version (all_to_all
pencil transpose) lives in :mod:`repro.dist.pencil`.
"""
from __future__ import annotations

import jax.numpy as jnp

from . import complexmath as cm
from .complexmath import SplitComplex
from . import fft1d


def _swap(x: SplitComplex, a: int, b: int) -> SplitComplex:
    return SplitComplex(jnp.swapaxes(x.re, a, b), jnp.swapaxes(x.im, a, b))


def _fft2_direct(x: SplitComplex, *, inverse: bool = False,
                 algo: str = "auto", backend: str = "jnp",
                 block_batch: int = None,
                 variant: str = "plain") -> SplitComplex:
    """Execute a resolved 2-D plan config (no registry lookup).

    ``block_batch`` means images-per-tile for the fused kernels and the 1-D
    kernel's row tile for the row_col baseline (defaults 1 and 8);
    ``variant`` selects the GEMM kernel's precision path ("plain" or the
    bf16 "compensated" one).
    """
    if backend == "pallas":
        from repro.kernels import ops as kops
        if algo not in ("auto", "fused", "fused_stockham", "row_col"):
            raise ValueError(f'algo={algo!r} has no pallas 2-D path; use '
                             '"fused", "fused_stockham" or "row_col" '
                             '(or backend="jnp")')
        if algo in ("auto", "fused"):
            return kops.fft2d_gemm(x, inverse=inverse,
                                   block_batch=block_batch or 1,
                                   variant=variant)
        if algo == "fused_stockham":
            # the explicit-algo oracle: the pre-GEMM Stockham fused kernel
            return kops.fft2d_fused(x, inverse=inverse,
                                    block_batch=block_batch or 1)
        # transpose-based baseline on the same backend: two 1-D kernel
        # passes with an explicit global (HBM) transpose between them
        bb = block_batch or 8
        y = kops.fft_stockham(x, inverse=inverse, block_batch=bb)
        y = _swap(y, -1, -2)
        y = kops.fft_stockham(y, inverse=inverse, block_batch=bb)
        return _swap(y, -1, -2)
    if algo in ("fused", "fused_stockham"):
        raise ValueError(f'algo={algo!r} requires backend="pallas" '
                         '(the fused kernels have no jnp equivalent)')
    row_algo = "auto" if algo in ("auto", "row_col") else algo
    y = fft1d.fft(x, inverse=inverse, algo=row_algo)   # FFT each row
    y = _swap(y, -1, -2)                               # global transpose
    y = fft1d.fft(y, inverse=inverse, algo=row_algo)   # FFT each column
    return _swap(y, -1, -2)


def fft2(x: SplitComplex, *, inverse: bool = False, algo: str = "auto",
         backend: str = "jnp") -> SplitComplex:
    """2-D FFT over the last two axes, routed through the plan registry."""
    if len(x.shape) < 2:
        raise ValueError(f"fft2 needs at least 2 axes, got shape {x.shape}")
    if algo == "auto":
        from . import plan as _plan
        return _plan.get_plan(x.shape[-2:], dtype=x.dtype, inverse=inverse,
                              backend=backend)(x)
    return _fft2_direct(x, inverse=inverse, algo=algo, backend=backend)


def _fft3_direct(x: SplitComplex, *, inverse: bool = False,
                 algo: str = "auto", backend: str = "jnp",
                 block_batch: int = None,
                 variant: str = "plain") -> SplitComplex:
    """Execute a resolved 3-D plan config (no registry lookup)."""
    if backend == "pallas":
        from repro.kernels import ops as kops
        if algo not in ("auto", "fused", "row_col"):
            raise ValueError(f'algo={algo!r} has no pallas 3-D path; use '
                             '"fused" or "row_col" (or backend="jnp")')
        if algo in ("auto", "fused"):
            return kops.fft3d_fused(x, inverse=inverse,
                                    block_batch=block_batch or 1,
                                    variant=variant)
        # transpose-based baseline: three 1-D kernel passes with explicit
        # global (HBM) relayouts between them
        bb = block_batch or 8
        y = kops.fft_stockham(x, inverse=inverse, block_batch=bb)
        y = _swap(y, -1, -2)
        y = kops.fft_stockham(y, inverse=inverse, block_batch=bb)
        y = _swap(y, -1, -2)
        y = _swap(y, -1, -3)
        y = kops.fft_stockham(y, inverse=inverse, block_batch=bb)
        return _swap(y, -1, -3)
    if algo == "fused":
        raise ValueError('algo="fused" requires backend="pallas" '
                         '(the fused 3-D kernel has no jnp equivalent)')
    pass_algo = "auto" if algo in ("auto", "row_col") else algo
    y = fft1d.fft(x, inverse=inverse, algo=pass_algo)
    y = _swap(y, -1, -2)
    y = fft1d.fft(y, inverse=inverse, algo=pass_algo)
    y = _swap(y, -1, -2)
    y = _swap(y, -1, -3)
    y = fft1d.fft(y, inverse=inverse, algo=pass_algo)
    return _swap(y, -1, -3)


def fft3(x: SplitComplex, *, inverse: bool = False, algo: str = "auto",
         backend: str = "jnp") -> SplitComplex:
    """3-D FFT over the last three axes, routed through the plan registry.

    ``algo="auto"`` resolves the (d, h, w) key once per shape — pallas
    keys select the fused pencil-in-VMEM kernel
    (:mod:`repro.kernels.fft3d_fused`) and demote to jnp with a
    registry-visible reason when the shape has no kernel path — exactly
    the plumbing :func:`fft2` has always had (previously ``fft3`` took no
    ``backend`` and bypassed the registry entirely, so no 3-D caller
    could reach a kernel or see a demote reason).
    """
    if len(x.shape) < 3:
        raise ValueError(f"fft3 needs at least 3 axes, got shape {x.shape}")
    if algo == "auto":
        from . import plan as _plan
        return _plan.get_plan(x.shape[-3:], dtype=x.dtype, inverse=inverse,
                              backend=backend)(x)
    return _fft3_direct(x, inverse=inverse, algo=algo, backend=backend)


def rfft2(x: jnp.ndarray, *, algo: str = "auto",
          backend: str = "jnp") -> SplitComplex:
    """Real-input 2-D FFT: rfft rows (half spectrum), full FFT columns.

    Beyond-paper: halves the row-pass FLOPs and — in the distributed
    version — the transpose all_to_all bytes.  ``algo="auto"`` routes
    through the registry's rfft-kind (h, w) key: on ``backend="jnp"`` the
    row-pass inner algo is resolved once per shape and the column pass
    composes with the (h,)-key c2c plan; ``backend="pallas"`` selects the
    fused real-input kernel (:mod:`repro.kernels.rfft2d_fused`) — one
    kernel, half the complex fused kernel's HBM traffic — demoting to jnp
    with a registry-visible reason when the shape has no kernel path.
    """
    if algo == "auto":
        from . import plan as _plan
        return _plan.get_plan(x.shape[-2:], dtype=x.dtype, kind="rfft",
                              backend=backend)(x)
    if algo == "fused":
        if backend != "pallas":
            raise ValueError('algo="fused" requires backend="pallas" '
                             '(the fused rfft kernel has no jnp equivalent)')
        from repro.kernels import ops as kops
        return kops.rfft2d_fused(x)
    return _rfft2_direct(x, row_algo=algo, col_algo=algo, backend=backend)


def _rfft2_direct(x: jnp.ndarray, *, row_algo: str, col_algo: str = "auto",
                  backend: str = "jnp") -> SplitComplex:
    """Execute a resolved rfft2 config.  ``row_algo`` is the inner complex
    algo of the packed row rfft (explicit, never "auto"); the column pass
    is an ordinary c2c transform that may route through its own plan key.
    ``backend="pallas"`` runs both passes on the 1-D kernels where the
    algo has one (:func:`repro.core.fft1d._fft_inner`).
    """
    y = fft1d._rfft_direct(x, algo=row_algo,
                           backend=backend)            # (..., H, W/2+1)
    y = _swap(y, -1, -2)
    y = fft1d._fft_inner(y, algo=col_algo, backend=backend)
    return _swap(y, -1, -2)


def irfft2(xf: SplitComplex, s=None, *, algo: str = "auto",
           backend: str = "jnp") -> jnp.ndarray:
    """Inverse real 2-D FFT from the (..., H, W/2+1) half spectrum.

    ``s=(h, w)`` follows ``numpy.fft.irfft2``: the spectrum is truncated or
    trailing-zero-padded to h rows and w//2+1 bins, then transformed with
    an output width of ``w``.  Odd widths follow numpy's odd-``s``
    semantics on the direct (jnp) path — the registry's rfft keys and the
    fused kernel cover even widths.  The fit happens before plan dispatch,
    so every path sees the same spectrum.
    """
    if s is not None:
        h, w = (int(d) for d in s)
        if h < 1 or w < 1:
            raise ValueError(f"irfft2 output shape must be positive, "
                             f"got s={s}")
        xf = _fit_spectrum2(xf, h, w)
    else:
        w = 2 * (xf.shape[-1] - 1)
    h = xf.shape[-2]
    if w % 2:                     # odd width: numpy semantics, direct path
        if algo == "fused":
            raise ValueError(f"the fused rfft kernel needs an even output "
                             f"width, got s={s}")
        return _irfft2_direct(xf, row_algo=algo, col_algo=algo, w=w,
                              backend=backend)
    if algo == "fused":
        if backend != "pallas":
            raise ValueError('algo="fused" requires backend="pallas" '
                             '(the fused rfft kernel has no jnp equivalent)')
        from repro.kernels import ops as kops
        return kops.irfft2d_fused(xf)
    if algo == "auto":
        from . import plan as _plan
        return _plan.get_plan((h, w), dtype=xf.dtype, inverse=True,
                              kind="rfft", backend=backend)(xf)
    return _irfft2_direct(xf, row_algo=algo, col_algo=algo, w=w,
                          backend=backend)


def _fit_spectrum2(xf: SplitComplex, h: int, w: int) -> SplitComplex:
    """Truncate / zero-pad a 2-D half spectrum to (h, w//2+1) — numpy's
    ``ifft(a, n=h)`` trailing-fit on axis -2, then the 1-D half-spectrum
    fit on the last axis."""
    rows = xf.shape[-2]
    if rows > h:
        xf = SplitComplex(xf.re[..., :h, :], xf.im[..., :h, :])
    elif rows < h:
        pad = [(0, 0)] * (xf.re.ndim - 2) + [(0, h - rows), (0, 0)]
        xf = SplitComplex(jnp.pad(xf.re, pad), jnp.pad(xf.im, pad))
    return fft1d._fit_half_spectrum(xf, w)


def _irfft2_direct(xf: SplitComplex, *, row_algo: str,
                   col_algo: str = "auto", w: int = None,
                   backend: str = "jnp") -> jnp.ndarray:
    y = _swap(xf, -1, -2)
    y = fft1d._fft_inner(y, inverse=True, algo=col_algo, backend=backend)
    y = _swap(y, -1, -2)
    n = w if w is not None else 2 * (xf.shape[-1] - 1)
    return fft1d._irfft_direct(y, n, algo=row_algo, backend=backend)
