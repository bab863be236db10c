"""Fused 3-D FFT Pallas kernel: pencil-in-VMEM four-step GEMM passes.

The 3-D transform is where the row-column schedule's transposes hurt most:
three 1-D passes separated by two global relayouts, each a full-volume HBM
round-trip (the distributed version in :mod:`repro.dist.pencil` pays them
as all_to_alls).  This kernel keeps an entire (block_batch, D, H, W)
sub-volume resident in VMEM and runs all three passes on it back to back —
per volume exactly one HBM read and one HBM write of each split-complex
plane, zero relayouts:

- **W pass** — :func:`repro.kernels.fourstep.fft_last_fourstep` over
  the contiguous last axis (every (d, h) pencil at once).
- **H pass** — :func:`~repro.kernels.fourstep.fft_col_fourstep` along
  axis -2: a *left-side* DFT contraction, so the W-H transpose is absorbed
  into the matmul operand order.
- **D pass** — the same left-side contraction with the (H, W) plane
  flattened into the pencil axis: reshaping (bb, D, H, W) to
  (bb, D, H*W) makes D the contracted axis of ``fft_col_fourstep`` and the
  D-H-W relayout disappears the same way.

Each pass is one level of Bailey four-step (:mod:`repro.kernels.fourstep`)
— dense DFT-matrix matmuls with a pointwise inter-factor twiddle, single
dense DFT below the leaf — fed by host-built tables.  The 2-D kernel's
(:mod:`repro.kernels.fft2d_gemm`) precision-compensated bf16 variant
(split tables + fp32 accumulation, bf16 resident tile) is also available
here: a 128^3 fp32 brick busts 16 MiB VMEM, the bf16 one fits.  The
four-step reshapes make Mosaic refuse this kernel for the TPU
(``unsupported shape cast``); it runs in interpret mode only.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.complexmath import SplitComplex
from repro.core.fft1d import _best_split
from .fourstep import fourstep_tables_np, fft_last_fourstep, fft_col_fourstep
from .fft2d_gemm import VARIANTS, cast_tables

# The fused brick runs three memory-bound passes back to back, so its
# dense-leaf crossover sits one octave below the 2-D kernel's: at 256 a
# (16, 16) split does 16x fewer MACs per axis and the brick stays
# cache-resident between passes, which measures ~1.5x over the dense
# leaf on small-depth bricks; at <= 128 the dense matmul still wins
# (skinny-factor GEMMs run far below peak).
FOURSTEP_LEAF3 = 128


def fourstep_factors3(n: int):
    """(n1, n2) for one axis of the fused 3-D kernel (n1 == 1 means a
    single dense DFT matmul) — mirrored by repro.tt.trace's
    ``_gemm3d_stage`` so model and kernel count the same tables/flops."""
    n1 = 1 if n <= FOURSTEP_LEAF3 else _best_split(n)
    return n1, n // n1


def _check_dims3(d: int, h: int, w: int):
    for n in (d, h, w):
        if n & (n - 1) or n < 2:
            raise ValueError("the fused 3-D kernel needs power-of-two "
                             f"dims >= 2, got {(d, h, w)}")


def gemm_tables3(d: int, h: int, w: int, inverse: bool, dtype, variant: str):
    """The 18 kernel table operands (6 per axis: W, H, then D)."""
    tabs = (fourstep_tables_np(w, inverse, fourstep_factors3(w))
            + fourstep_tables_np(h, inverse, fourstep_factors3(h))
            + fourstep_tables_np(d, inverse, fourstep_factors3(d)))
    return cast_tables(tabs, dtype, variant)


def _unsplit(tabs, compensated: bool):
    """Rebuild fp32 tables from split hi/lo pairs (plain tables pass)."""
    if compensated:
        return tuple(t[0].astype(jnp.float32) + t[1].astype(jnp.float32)
                     for t in tabs)
    return tuple(tabs)


def _fft3d_kernel(*refs, d: int, h: int, w: int, facs, inverse: bool,
                  compensated: bool):
    """One batch tile: W, H and D four-step GEMM passes, all VMEM-resident
    (both transposes absorbed into left-side contractions)."""
    tw_w = _unsplit([r[...] for r in refs[:6]], compensated)
    tw_h = _unsplit([r[...] for r in refs[6:12]], compensated)
    tw_d = _unsplit([r[...] for r in refs[12:18]], compensated)
    (n1w, n2w), (n1h, n2h), (n1d, n2d) = facs
    xre_ref, xim_ref, ore_ref, oim_ref = refs[18:]
    re = xre_ref[...]                            # (bb, d, h, w)
    im = xim_ref[...]
    dt = re.dtype
    rnd = (lambda q: q.astype(dt).astype(jnp.float32)) if compensated \
        else (lambda q: q)
    if compensated:
        re, im = re.astype(jnp.float32), im.astype(jnp.float32)
    re, im = fft_last_fourstep(re, im, tw_w, n1w, n2w)       # W pass
    re, im = rnd(re), rnd(im)
    re, im = fft_col_fourstep(re, im, tw_h, n1h, n2h)        # H pass
    re, im = rnd(re), rnd(im)
    bb = re.shape[0]
    re = re.reshape(bb, d, h * w)                # D becomes the column axis
    im = im.reshape(bb, d, h * w)
    re, im = fft_col_fourstep(re, im, tw_d, n1d, n2d)        # D pass
    re = re.reshape(bb, d, h, w)
    im = im.reshape(bb, d, h, w)
    if inverse:
        scale = jnp.asarray(1.0 / (d * h * w), re.dtype)
        re, im = re * scale, im * scale
    ore_ref[...] = re.astype(dt)
    oim_ref[...] = im.astype(dt)


def fft3d_fused_pallas(x: SplitComplex, *, inverse: bool = False,
                       block_batch: int = 1, variant: str = "plain",
                       interpret: bool = True) -> SplitComplex:
    """Batched 3-D FFT over the last three axes: x.re/x.im of
    (batch, d, h, w)."""
    assert variant in VARIANTS, variant
    batch, d, h, w = x.re.shape
    _check_dims3(d, h, w)
    bb = min(block_batch, batch)
    assert batch % bb == 0, (batch, bb)
    ops = gemm_tables3(d, h, w, inverse, x.dtype, variant)
    facs = (fourstep_factors3(w), fourstep_factors3(h),
            fourstep_factors3(d))
    kernel = functools.partial(_fft3d_kernel, d=d, h=h, w=w, facs=facs,
                               inverse=inverse,
                               compensated=variant == "compensated")
    grid = (batch // bb,)
    data_spec = pl.BlockSpec((bb, d, h, w), lambda i: (i, 0, 0, 0))
    tspecs = [pl.BlockSpec(t.shape, lambda i, nd=t.ndim: (0,) * nd)
              for t in ops]
    out_shape = [jax.ShapeDtypeStruct((batch, d, h, w), x.dtype)] * 2
    ore, oim = pl.pallas_call(
        kernel, grid=grid,
        in_specs=tspecs + [data_spec, data_spec],
        out_specs=[data_spec, data_spec], out_shape=out_shape,
        interpret=interpret)(*ops, x.re, x.im)
    return SplitComplex(ore, oim)
