"""Fused real-input 2-D FFT Pallas kernel (rfft2 / irfft2).

The complex fused kernel (:mod:`repro.kernels.fft2d_gemm`) already keeps
the §5 global transpose off HBM; this kernel additionally exploits the
input being *real*, which halves HBM plane traffic and the column pass:

- **Real row pass** — the (H, W) real tile times a (W, W/2) cosine table
  and a (W, W/2) sine table gives bins 0..W/2-1 of every row.  Bins 0 and
  W/2 of a real row are both real, so the sine table's column 0 (all
  zero) is replaced by the Nyquist kernel ``(-1)^j``: the Nyquist bin
  rides in the imaginary lane of bin 0 and the half spectrum stays W/2
  lanes wide.
- **Half-width column pass** — ``F_H`` times the (H, W/2) packed half
  spectrum as *left-side* matmuls, so the tile transpose of the
  row-column schedule never materialises.  The four real products
  ``A = Fr·Pr, B = Fi·Pi, C = Fr·Pi, D = Fi·Pr`` give bins 1..W/2-1 as
  ``(A - B, C + D)``, and in lane 0 they hold the DC column ``(A, D)``
  and the Nyquist column ``(C, B)`` separately: no unpacking pass.

The inverse twin runs the row pass first: each row's half spectrum times
the Hermitian-weighted (W/2, W) cosine/sine tables (weights 1, 2, ..., 2)
plus the Nyquist column times ``(-1)^j`` gives a complex (H, W) plane;
the real part of ``F_H^{-1}`` times it is the output.  Dropping the
imaginary parts of the DC and Nyquist bins — the C2R convention — falls
out of taking that real part.  ``s=`` truncate/pad fits happen upstream
in :func:`repro.core.fft2d.irfft2`, which hands this kernel an already
fitted spectrum.

Both passes are dense DFT-matrix matmuls in the compilable form of
:mod:`repro.kernels.fft2d_gemm` (2-D matmuls on ROW_TILE slabs, fp32 at
``Precision.HIGHEST``), fed by host-built float64 tables.  Per image the
kernel moves one real plane in and one (H, W/2+1) half spectrum out.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.complexmath import SplitComplex
from .fft2d_gemm import (_check_dims, compiler_params, fit_block_batch,
                         for_row_tiles, lane_chunks, nbytes, row_tile,
                         slab_dot, table_dot, table_spec, vmem_need)


def _angles(n_in: int, n_out: int, n: int) -> np.ndarray:
    """2*pi*(j*k mod n)/n on an (n_in, n_out) grid, reduced mod n first so
    the float64 angle stays small."""
    jk = np.outer(np.arange(n_in), np.arange(n_out)) % n
    return 2.0 * np.pi * jk / n


def row_tables_np(w: int, inverse: bool):
    """The real-axis tables.  Forward: (W, W/2) ``cos`` and ``-sin`` with
    the Nyquist kernel ``(-1)^j`` in the sine table's column 0.  Inverse:
    (W/2, W) Hermitian-weighted ``c_k cos`` and ``c_k sin`` (c_0 = 1,
    c_k = 2 otherwise; the Nyquist bin enters separately)."""
    hw = w // 2
    if inverse:
        ang = _angles(hw, w, w)
        c = np.full((hw, 1), 2.0)
        c[0] = 1.0
        return c * np.cos(ang), c * np.sin(ang)
    ang = _angles(w, hw, w)
    cr, ci = np.cos(ang), -np.sin(ang)
    ci[:, 0] = 1.0 - 2.0 * (np.arange(w) % 2)
    return cr, ci


def col_tables_np(h: int, inverse: bool):
    """Dense ``F_H`` (re, im), the sign set by the direction."""
    ang = _angles(h, h, h)
    return np.cos(ang), (1.0 if inverse else -1.0) * np.sin(ang)


def _tables(h: int, w: int, inverse: bool, dtype):
    return [jnp.asarray(t, dtype)
            for t in row_tables_np(w, inverse) + col_tables_np(h, inverse)]


def _rfft2d_kernel(cr, ci, fhr, fhi, x_ref, ore_ref, oim_ref, pre, pim, *,
                   h: int, w: int, bb: int):
    """One batch tile: packed real row pass into the scratch planes, then
    the half-width column pass with DC/Nyquist split out of lane 0."""
    hw = w // 2
    dt = ore_ref.dtype

    def image(b, carry):
        def row_pass(rows):
            a = x_ref[b, rows, :]
            for cols in lane_chunks(hw):
                pre[rows, cols] = table_dot(a, cr, cols).astype(pre.dtype)
                pim[rows, cols] = table_dot(a, ci, cols).astype(pim.dtype)

        def col_pass(rows):
            for cols in lane_chunks(hw):
                p, q = pre[:, cols], pim[:, cols]
                a_, b_ = slab_dot(fhr, rows, p), slab_dot(fhi, rows, q)
                c_, d_ = slab_dot(fhr, rows, q), slab_dot(fhi, rows, p)
                yr, yi = a_ - b_, c_ + d_
                if cols.start == 0:
                    # lane 0 holds DC (A, D) and Nyquist (C, B) apart
                    lane = jax.lax.broadcasted_iota(jnp.int32, yr.shape, 1)
                    yr = jnp.where(lane == 0, a_, yr)
                    yi = jnp.where(lane == 0, d_, yi)
                    ore_ref[b, rows, pl.ds(hw, 1)] = c_[:, :1].astype(dt)
                    oim_ref[b, rows, pl.ds(hw, 1)] = b_[:, :1].astype(dt)
                ore_ref[b, rows, cols] = yr.astype(dt)
                oim_ref[b, rows, cols] = yi.astype(dt)

        for_row_tiles(h, row_pass)
        for_row_tiles(h, col_pass)
        return carry

    jax.lax.fori_loop(0, bb, image, 0)


def _irfft2d_kernel(er, ei, ghr, ghi, xre_ref, xim_ref, o_ref, ure, uim, *,
                    h: int, w: int, bb: int):
    """Inverse twin: Hermitian-weighted row pass (complex (H, W) plane into
    the scratch), then the real part of the inverse column pass."""
    hw = w // 2
    dt = o_ref.dtype
    scale = 1.0 / (h * w)

    def image(b, carry):
        def row_pass(rows):
            a = xre_ref[b, rows, pl.ds(0, hw)]
            c = xim_ref[b, rows, pl.ds(0, hw)]
            nr = xre_ref[b, rows, pl.ds(hw, 1)].astype(jnp.float32)
            ni = xim_ref[b, rows, pl.ds(hw, 1)].astype(jnp.float32)
            for cols in lane_chunks(w):
                lane = jax.lax.broadcasted_iota(
                    jnp.int32, (a.shape[0], cols.size), 1)
                alt = jnp.where(lane % 2 == 0, 1.0, -1.0)  # Nyquist kernel
                ure[rows, cols] = (table_dot(a, er, cols)
                                   - table_dot(c, ei, cols)
                                   + nr * alt).astype(ure.dtype)
                uim[rows, cols] = (table_dot(a, ei, cols)
                                   + table_dot(c, er, cols)
                                   + ni * alt).astype(uim.dtype)

        def col_pass(rows):
            for cols in lane_chunks(w):
                y = slab_dot(ghr, rows, ure[:, cols]) \
                    - slab_dot(ghi, rows, uim[:, cols])
                o_ref[b, rows, cols] = (y * scale).astype(dt)

        for_row_tiles(h, row_pass)
        for_row_tiles(h, col_pass)
        return carry

    jax.lax.fori_loop(0, bb, image, 0)


def _call(kernel, ops, ins, outs, scratch, *, h, w, block_batch,
          interpret):
    """pallas_call plumbing shared by both directions: whole tables
    single-buffered, blocks of images tiled over the batch grid."""
    batch = ins[0].shape[0]
    per_image = nbytes(*[jax.ShapeDtypeStruct(a.shape[1:], a.dtype)
                         for a in ins + outs])
    need = lambda bb: vmem_need(blocks=bb * per_image, tables=nbytes(*ops),
                                scratch=nbytes(*scratch),
                                slab=row_tile(h) * max(h, w) * 4)
    bb = fit_block_batch(min(block_batch, batch), batch, need)
    spec = lambda a: pl.BlockSpec((bb,) + a.shape[1:], lambda i: (i, 0, 0))
    return pl.pallas_call(
        functools.partial(kernel, h=h, w=w, bb=bb),
        grid=(batch // bb,),
        in_specs=[table_spec(t) for t in ops] + [spec(a) for a in ins],
        out_specs=[spec(o) for o in outs], out_shape=outs,
        scratch_shapes=[pltpu.VMEM(s.shape, s.dtype) for s in scratch],
        compiler_params=compiler_params(need(bb)),
        interpret=interpret)(*ops, *ins)


def rfft2d_fused_pallas(x: jnp.ndarray, *, block_batch: int = 1,
                        interpret: bool = True) -> SplitComplex:
    """Batched real 2-D FFT: x of (batch, h, w) real -> (batch, h, w/2+1)
    half spectra."""
    batch, h, w = x.shape
    _check_dims(h, w)
    half = jax.ShapeDtypeStruct((h, w // 2), x.dtype)
    spec = jax.ShapeDtypeStruct((batch, h, w // 2 + 1), x.dtype)
    ore, oim = _call(_rfft2d_kernel, _tables(h, w, False, x.dtype), [x],
                     [spec, spec], [half, half], h=h, w=w,
                     block_batch=block_batch, interpret=interpret)
    return SplitComplex(ore, oim)


def irfft2d_fused_pallas(xf: SplitComplex, *, block_batch: int = 1,
                         interpret: bool = True) -> jnp.ndarray:
    """Batched inverse real 2-D FFT: (batch, h, w/2+1) half spectra ->
    (batch, h, w) real, w = 2 * (bins - 1)."""
    batch, h, bins = xf.re.shape
    w = 2 * (bins - 1)
    _check_dims(h, w)
    plane = jax.ShapeDtypeStruct((h, w), xf.dtype)
    (out,) = _call(_irfft2d_kernel, _tables(h, w, True, xf.dtype),
                   [xf.re, xf.im],
                   [jax.ShapeDtypeStruct((batch, h, w), xf.dtype)],
                   [plane, plane], h=h, w=w, block_batch=block_batch,
                   interpret=interpret)
    return out
