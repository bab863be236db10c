"""GEMM-formulated fused complex 2-D FFT Pallas kernel.

The Tensix compute engine — like the TPU MXU — is matmul-native, so both
1-D passes of the 2-D DFT ``Y = F_H · X · F_W`` run as DFT-matrix matmuls
fed by host-built float64 tables, inside one kernel that holds a block of
images in VMEM.  Per image the kernel moves exactly one HBM read + one HBM
write of each split-complex plane — the §5 global transpose stays off HBM.
Every in-kernel operation is a 2-D matmul, an elementwise op, a 128-aligned
slice or a sublane-strided store, the forms Mosaic compiles; the one-level
four-step split of :mod:`repro.kernels.fourstep` reshapes the lane axis
inside the kernel, which Mosaic refuses.  Each image takes one of two
bodies, chosen from the shape, dtype and variant alone
(:func:`axis_splits`):

- **Split passes** (fp32 plain; both dims in {128, 256, 512, 1024}, one of
  them above 128).  A length ``n = r·128`` pass (:func:`pass_split`), with
  input ``j = j1·128 + j2`` and output ``k = k1 + r·k2``, runs as a
  radix-``r`` butterfly over the ``r`` 128-lane chunks ``j1`` on the VPU
  (exact ±1, ±i and (±1±i)/√2 constants), an elementwise twiddle
  ``W_n^(j2·k1)`` and a 128-point DFT product on the MXU: 128 MACs per
  output point instead of ``n``.  The product is taken transposed,
  ``F_128 · vᵀ``, so the output index lands on the sublane axis and the
  stride-``r`` interleave of ``k1 + r·k2`` is a sublane-strided store.
  The row pass so writes the plane transposed, in 128-row groups, into the
  VMEM scratch (``T[g, k, i] = S[128·g + i, k]``); the column pass reads
  those groups as its 128-lane chunks and stores natural rows.
- **Dense passes** (every other shape, bf16 and the compensated variant).
  Row pass ``S = X · F_W``, one (ROW_TILE, W) slab at a time, into a VMEM
  scratch plane; column pass ``Y = F_H · S``, one (ROW_TILE, W) output slab
  at a time, as a *left-side* contraction, so the tile transpose never
  materialises.  ``n`` MACs per output point.

**Precision.**  fp32 matmuls run at ``Precision.HIGHEST``: the TPU default
for fp32 operands is one bf16 pass, ~1e-3 relative error.

**Precision-compensated bf16 variant** (``variant="compensated"``): a
bf16 tile halves the VMEM working set, but a straight bf16 cast of the
DFT tables costs ~1e-2 relative error.  The compensated variant stores
every table as a **split pair** ``w = hi + lo`` (``hi`` = the bf16
rounding of the float64 table, ``lo`` = the bf16 rounding of the residual
``w - hi``) and runs every product as two bf16 MXU passes, ``x·hi +
x·lo``, with fp32 accumulation — bf16 x bf16 products are exact in fp32.
Only the resident tile (kernel I/O and the inter-pass scratch) stays
bf16.  Error lands at the bf16 *quantisation* floor (~3e-3 relative)
instead of the bf16 *arithmetic* floor, inside the 5e-3 acceptance bound.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.complexmath import SplitComplex

VARIANTS = ("plain", "compensated")

# rows per MXU slab and output lanes per matmul: every matmul operand is
# at most a (256, n) slab, which bounds the kernel's VMEM stack whatever
# the image size
ROW_TILE = 256
LANE_TILE = 256

# scoped-VMEM ceiling handed to Mosaic (v5e has 128 MiB of VMEM per core;
# the default scoped limit is 16 MiB)
VMEM_CAP = 100 << 20


def _check_dims(h: int, w: int):
    for d in (h, w):
        if d & (d - 1) or d < 2:
            raise ValueError("the fused 2-D kernels need power-of-two "
                             f"tile dims >= 2, got {(h, w)}")


def split_table_np(t: np.ndarray, dtype) -> np.ndarray:
    """Stack the ``(hi, lo)`` split of a float64 table in storage dtype:
    ``hi`` is the direct rounding, ``lo`` the rounding of the residual, so
    ``hi + lo`` (accumulated in fp32) recovers the table to ~storage-eps^2
    accuracy from two narrow operands."""
    nd = np.dtype(jnp.dtype(dtype))       # ml_dtypes-backed for bfloat16
    hi = np.asarray(t, np.float64).astype(nd)
    lo = (t - hi.astype(np.float64)).astype(nd)
    return jnp.asarray(np.stack([hi, lo]))


def cast_tables(tabs, dtype, variant: str = "plain"):
    """float64 host tables -> kernel operands: plain-cast, or split-stacked
    ``(2, ...)`` hi/lo pairs for the compensated variant."""
    if variant == "compensated":
        return [split_table_np(t, dtype) for t in tabs]
    return [jnp.asarray(t, dtype) for t in tabs]


def gemm_tables(h: int, w: int, inverse: bool, dtype, variant: str):
    """The 4 kernel table operands — dense DFT matrices ``F_W`` (re, im)
    then ``F_H`` (re, im) — plain-cast or split-stacked per ``variant``."""
    from repro.core.twiddle import _dft_matrix_np
    sign = 1.0 if inverse else -1.0
    return cast_tables(_dft_matrix_np(w, sign) + _dft_matrix_np(h, sign),
                       dtype, variant)


# -- split passes: radix-r VPU combine + 128-point MXU products -------------

SPLIT = 128                 # the MXU product length of a split pass
SPLIT_RADICES = (2, 4, 8)   # the VPU combines written out as butterflies


def pass_split(n: int):
    """``(r, 128)`` where a length-``n`` pass splits into a radix-``r`` VPU
    combine and 128-point MXU products, else ``None`` (a dense pass)."""
    r = n // SPLIT
    return (r, SPLIT) if n == r * SPLIT and r in SPLIT_RADICES else None


def axis_splits(h: int, w: int, dtype, variant: str = "plain"):
    """``(pass_split(h), pass_split(w))`` where an (h, w) plane runs the
    split body, ``(None, None)`` where it runs the dense one.  The split
    body takes fp32 plain planes whose dims are 128 or split, one of them
    above 128; a 128 axis inside it is one 128-point product."""
    dims_ok = all(d == SPLIT or pass_split(d) for d in (h, w))
    if (jnp.dtype(dtype) != jnp.float32 or variant != "plain"
            or not dims_ok or max(h, w) == SPLIT):
        return None, None
    return pass_split(h), pass_split(w)


def _twiddles_np(n: int, sign: float) -> tuple:
    """``W_n^(j2·k1)`` as an (r, 128) float64 (re, im) pair, k1 < r = n/128."""
    k1j2 = np.outer(np.arange(n // SPLIT, dtype=np.float64),
                    np.arange(SPLIT, dtype=np.float64))
    ang = sign * 2.0 * np.pi * k1j2 / n
    return np.cos(ang), np.sin(ang)


def split_tables(h: int, w: int, inverse: bool):
    """The split body's 6 fp32 table operands: ``F_128`` (re, im), then the
    twiddles of the row pass (W axis) and of the column pass (H axis)."""
    from repro.core.twiddle import _dft_matrix_np
    sign = 1.0 if inverse else -1.0
    return cast_tables(_dft_matrix_np(SPLIT, sign) + _twiddles_np(w, sign)
                       + _twiddles_np(h, sign), jnp.float32)


def fft2d_tables(h: int, w: int, inverse: bool, dtype, variant: str):
    """The table operands the kernel takes for an (h, w) plane: the split
    body's (:func:`split_tables`) or the dense DFT matrices."""
    if any(axis_splits(h, w, dtype, variant)):
        return split_tables(h, w, inverse)
    return gemm_tables(h, w, inverse, dtype, variant)


_HALF_SQRT2 = float(np.sqrt(0.5))


def _rotate(z, q: int):
    """``z · e^(iπq/4)`` of a split-complex pair: exact for even ``q`` (±1,
    ±i), one product by 1/√2 per component for odd ``q``."""
    re, im = z
    q %= 8
    if q % 2 == 0:
        return [(re, im), (-im, re), (-re, -im), (im, -re)][q // 2]
    s, d = (re + im) * _HALF_SQRT2, (re - im) * _HALF_SQRT2
    return [(d, s), (-s, d), (-d, -s), (s, -d)][q // 2]


def _small_dft(zs, sign: int):
    """The DFT ``u_k = Σ_j e^(sign·2πi·jk/r) z_j`` of ``r = len(zs)`` (1, 2, 4
    or 8) split-complex planes, natural order in and out: radix-2
    decimation in time, elementwise on the VPU."""
    r = len(zs)
    if r == 1:
        return list(zs)
    ev, od = _small_dft(zs[0::2], sign), _small_dft(zs[1::2], sign)
    out = [None] * r
    for k in range(r // 2):
        (er, ei), (tr, ti) = ev[k], _rotate(od[k], sign * 8 * k // r)
        out[k], out[k + r // 2] = (er + tr, ei + ti), (er - tr, ei - ti)
    return out


def mxu_dot_t(a, b):
    """``a @ bᵀ`` of fp32 operands (the lane axes of both contract) on the
    MXU at ``Precision.HIGHEST``."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


# -- in-kernel building blocks (shared with repro.kernels.rfft2d_fused) -----

def mxu_dot(a, b):
    """``a @ b`` on the MXU with fp32 accumulation; fp32 operands run at
    ``Precision.HIGHEST`` (multi-pass), bf16 ones in a single pass."""
    f32 = a.dtype == jnp.float32 or b.dtype == jnp.float32
    prec = jax.lax.Precision.HIGHEST if f32 else jax.lax.Precision.DEFAULT
    return jnp.dot(a, b, precision=prec, preferred_element_type=jnp.float32)


def table_dot(a, t_ref, cols, compensated: bool = False):
    """``a @ T[:, cols]`` with T read from its ref; a compensated split
    pair runs as two exact bf16 passes."""
    if compensated:
        return mxu_dot(a, t_ref[0, :, cols]) + mxu_dot(a, t_ref[1, :, cols])
    return mxu_dot(a, t_ref[:, cols])


def slab_dot(t_ref, rows, s, compensated: bool = False):
    """``T[rows] @ s``: one ROW_TILE slab of a left-side table contraction
    (the column pass), split pair as in :func:`table_dot`."""
    if compensated:
        return mxu_dot(t_ref[0, rows, :], s) + mxu_dot(t_ref[1, rows, :], s)
    return mxu_dot(t_ref[rows, :], s)


def row_tile(h: int) -> int:
    return min(h, ROW_TILE)


def for_row_tiles(h: int, body):
    """Run ``body(rows)`` for every ROW_TILE-row slab ``rows`` (a
    ``pl.ds``) of an h-row plane, as a rolled loop."""
    th = row_tile(h)

    def step(r, carry):
        body(pl.ds(pl.multiple_of(r * th, th), th))
        return carry

    jax.lax.fori_loop(0, h // th, step, 0)


def lane_chunks(n: int):
    """Static LANE_TILE-wide lane slices covering n lanes."""
    tn = min(n, LANE_TILE)
    return [pl.ds(c, tn) for c in range(0, n, tn)]


def vmem_need(*, blocks: int, tables: int, scratch: int, slab: int) -> int:
    """VMEM bytes a kernel needs: pipelined blocks double-buffered, tables
    single-buffered, the scratch planes and ~16 live (ROW_TILE, n) fp32
    slab temporaries."""
    return 2 * blocks + tables + scratch + 16 * slab


def fit_block_batch(bb: int, batch: int, need) -> int:
    """The largest divisor of ``batch`` that is <= ``bb`` and whose
    ``need(bb)`` fits VMEM_CAP (never below 1): a larger block of images
    than VMEM holds is a compile failure, not a slower kernel."""
    while bb > 1 and (batch % bb or need(bb) > VMEM_CAP):
        bb -= 1
    return bb


def compiler_params(need: int):
    """Mosaic params with a scoped-VMEM limit of ``need`` plus a quarter,
    within [32 MiB, VMEM_CAP]."""
    limit = min(max(need + need // 4, 32 << 20), VMEM_CAP)
    return pltpu.CompilerParams(dimension_semantics=("arbitrary",),
                                vmem_limit_bytes=int(limit))


def table_spec(t):
    """A whole-table operand: same block every grid step, one buffer."""
    return pl.BlockSpec(t.shape, lambda i, nd=t.ndim: (0,) * nd,
                        pipeline_mode=pl.Buffered(1))


def nbytes(*arrs) -> int:
    return sum(int(np.prod(a.shape)) * jnp.dtype(a.dtype).itemsize
               for a in arrs)


def _fft2d_gemm_kernel(fwr, fwi, fhr, fhi, xre_ref, xim_ref, ore_ref,
                       oim_ref, sre, sim, *, h: int, w: int, bb: int,
                       inverse: bool, compensated: bool):
    """One batch tile: dense-DFT row pass into the scratch planes, then the
    left-side column pass into the output, slab by slab."""
    rdot = functools.partial(table_dot, compensated=compensated)
    ldot = functools.partial(slab_dot, compensated=compensated)
    dt = ore_ref.dtype

    def image(b, carry):
        def row_pass(rows):
            a, c = xre_ref[b, rows, :], xim_ref[b, rows, :]
            for cols in lane_chunks(w):
                sre[rows, cols] = (rdot(a, fwr, cols) - rdot(c, fwi, cols)
                                   ).astype(sre.dtype)
                sim[rows, cols] = (rdot(a, fwi, cols) + rdot(c, fwr, cols)
                                   ).astype(sim.dtype)

        def col_pass(rows):
            for cols in lane_chunks(w):
                s, t = sre[:, cols], sim[:, cols]
                yr = ldot(fhr, rows, s) - ldot(fhi, rows, t)
                yi = ldot(fhr, rows, t) + ldot(fhi, rows, s)
                if inverse:
                    yr, yi = yr * (1.0 / (h * w)), yi * (1.0 / (h * w))
                ore_ref[b, rows, cols] = yr.astype(dt)
                oim_ref[b, rows, cols] = yi.astype(dt)

        for_row_tiles(h, row_pass)
        for_row_tiles(h, col_pass)
        return carry

    jax.lax.fori_loop(0, bb, image, 0)


def _fft2d_split_kernel(fr, fi, twr, twi, thr, thi, xre_ref, xim_ref,
                        ore_ref, oim_ref, tre, tim, ure, uim, *, h: int,
                        w: int, bb: int, inverse: bool):
    """One batch tile of split passes: the row pass writes each 128-row
    group transposed into the scratch planes ``t``; the column pass
    combines those groups 128 columns at a time and stores natural rows
    into the (h, 128) scratch ``u`` (Mosaic's strided store needs a
    memref whose last dim is 128), which it copies to the output."""
    sign = 1 if inverse else -1
    rh, rw = h // SPLIT, w // SPLIT
    dt = ore_ref.dtype

    def split_pass(chunks, tw_re, tw_im):
        """Combine, twiddle and transform r (m, 128) chunks: the k1-th
        result is the (128, m) block of outputs ``k1 + r·k2``."""
        for k1, (ur, ui) in enumerate(_small_dft(chunks, sign)):
            if k1:
                cr, ci = tw_re[k1:k1 + 1, :], tw_im[k1:k1 + 1, :]
                ur, ui = ur * cr - ui * ci, ur * ci + ui * cr
            yield k1, (mxu_dot_t(fr[...], ur) - mxu_dot_t(fi[...], ui),
                       mxu_dot_t(fr[...], ui) + mxu_dot_t(fi[...], ur))

    def image(b, carry):
        def row_group(g, carry):
            rows = pl.ds(pl.multiple_of(g * SPLIT, SPLIT), SPLIT)
            chunks = [(xre_ref[b, rows, c], xim_ref[b, rows, c])
                      for c in (pl.ds(j1 * SPLIT, SPLIT) for j1 in range(rw))]
            for k1, (yr, yi) in split_pass(chunks, twr, twi):
                ks = pl.ds(k1, SPLIT, stride=rw)
                tre[g, ks, :], tim[g, ks, :] = yr, yi
            return carry

        def col_group(c, carry):
            cols = pl.ds(pl.multiple_of(c * SPLIT, SPLIT), SPLIT)
            chunks = [(tre[i1, cols, :], tim[i1, cols, :])
                      for i1 in range(rh)]
            for k1, (yr, yi) in split_pass(chunks, thr, thi):
                if inverse:
                    yr, yi = yr * (1.0 / (h * w)), yi * (1.0 / (h * w))
                ks = pl.ds(k1, SPLIT, stride=rh)
                ure[ks, :], uim[ks, :] = yr, yi
            ore_ref[b, :, cols] = ure[...].astype(dt)
            oim_ref[b, :, cols] = uim[...].astype(dt)
            return carry

        jax.lax.fori_loop(0, rh, row_group, 0)
        jax.lax.fori_loop(0, w // SPLIT, col_group, 0)
        return carry

    jax.lax.fori_loop(0, bb, image, 0)


def fft2d_gemm_pallas(x: SplitComplex, *, inverse: bool = False,
                      block_batch: int = 1, variant: str = "plain",
                      interpret: bool = True) -> SplitComplex:
    """Batched 2-D FFT over the last two axes: x.re/x.im of (batch, h, w)."""
    assert variant in VARIANTS, variant
    batch, h, w = x.re.shape
    _check_dims(h, w)
    split = any(axis_splits(h, w, x.dtype, variant))
    ops = fft2d_tables(h, w, inverse, x.dtype, variant)
    if split:
        scratch = [pltpu.VMEM((h // SPLIT, w, SPLIT), x.dtype)] * 2 + [
            pltpu.VMEM((h, SPLIT), x.dtype)] * 2
    else:
        scratch = [pltpu.VMEM((h, w), x.dtype)] * 2
    plane = jax.ShapeDtypeStruct((h, w), x.dtype)
    need = lambda bb: vmem_need(
        blocks=4 * bb * nbytes(plane), tables=nbytes(*ops),
        scratch=nbytes(*scratch), slab=row_tile(h) * max(h, w) * 4)
    bb = fit_block_batch(min(block_batch, batch), batch, need)
    if split:
        kernel = functools.partial(_fft2d_split_kernel, h=h, w=w, bb=bb,
                                   inverse=inverse)
    else:
        kernel = functools.partial(_fft2d_gemm_kernel, h=h, w=w, bb=bb,
                                   inverse=inverse,
                                   compensated=variant == "compensated")
    data_spec = pl.BlockSpec((bb, h, w), lambda i: (i, 0, 0))
    out_shape = [jax.ShapeDtypeStruct((batch, h, w), x.dtype)] * 2
    ore, oim = pl.pallas_call(
        kernel, grid=(batch // bb,),
        in_specs=[table_spec(t) for t in ops] + [data_spec, data_spec],
        out_specs=[data_spec, data_spec], out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=compiler_params(need(bb)),
        name="fft2d_gemm_inv" if inverse else "fft2d_gemm_fwd",
        interpret=interpret)(*ops, x.re, x.im)
    return SplitComplex(ore, oim)
