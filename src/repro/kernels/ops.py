"""jit'd dispatch wrappers around the Pallas kernels.

Handle leading-batch flattening, batch padding to the block size, dtype
plumbing, and the interpret-mode switch (interpret=True on CPU — the kernels
target TPU; see EXAMPLE.md).  The public entry points mirror
:mod:`repro.core.fft1d` so :class:`repro.core.plan.FFTPlan` can swap backends.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.complexmath import SplitComplex
from . import fft_stockham as _stockham
from . import fft_fourstep as _fourstep
from . import fft_stage as _stage
from . import fft2d_fused as _fused2d
from . import fft2d_gemm as _gemm2d
from . import fft3d_fused as _fused3d
from . import rfft2d_fused as _rfused2d


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _flatten(x: SplitComplex):
    n = x.shape[-1]
    lead = x.shape[:-1]
    batch = 1
    for d in lead:
        batch *= d
    return SplitComplex(x.re.reshape(batch, n), x.im.reshape(batch, n)), lead


def _unflatten(x: SplitComplex, lead) -> SplitComplex:
    n = x.shape[-1]
    return SplitComplex(x.re.reshape(*lead, n), x.im.reshape(*lead, n))


def _pad_batch(x: SplitComplex, bb: int):
    batch = x.shape[0]
    pad = (-batch) % bb
    if pad:
        x = SplitComplex(jnp.pad(x.re, ((0, pad), (0, 0))),
                         jnp.pad(x.im, ((0, pad), (0, 0))))
    return x, batch


@functools.partial(jax.jit, static_argnames=("inverse", "radix",
                                             "block_batch", "interpret"))
def fft_stockham(x: SplitComplex, *, inverse: bool = False, radix: int = 4,
                 block_batch: int = 8, interpret: bool = None) -> SplitComplex:
    if interpret is None:
        interpret = not _on_tpu()
    flat, lead = _flatten(x)
    padded, batch = _pad_batch(flat, block_batch)
    out = _stockham.fft_stockham_pallas(padded, inverse=inverse, radix=radix,
                                        block_batch=block_batch,
                                        interpret=interpret)
    out = SplitComplex(out.re[:batch], out.im[:batch])
    return _unflatten(out, lead)


def _flatten2d(x: SplitComplex):
    h, w = x.shape[-2:]
    lead = x.shape[:-2]
    batch = 1
    for d in lead:
        batch *= d
    return SplitComplex(x.re.reshape(batch, h, w),
                        x.im.reshape(batch, h, w)), lead


def _pad_batch2d(arrs, batch: int, block_batch: int):
    """Pad flattened (batch, h, w) component planes up to the block size.
    Callers guard ``batch > 0`` (an empty batch has nothing to kernel)."""
    bb = min(block_batch, batch)
    pad = (-batch) % bb
    if pad:
        arrs = [jnp.pad(a, ((0, pad), (0, 0), (0, 0))) for a in arrs]
    return arrs, bb


@functools.partial(jax.jit, static_argnames=("inverse", "block_batch",
                                             "interpret"))
def fft2d_fused(x: SplitComplex, *, inverse: bool = False,
                block_batch: int = 1, interpret: bool = None) -> SplitComplex:
    """Fused transpose-free 2-D FFT over the last two axes (any leading
    batch dims); see :mod:`repro.kernels.fft2d_fused`."""
    if interpret is None:
        interpret = not _on_tpu()
    flat, lead = _flatten2d(x)
    h, w = flat.shape[-2:]
    batch = flat.shape[0]
    if batch == 0:
        return x                       # empty batch: nothing to transform
    (re, im), bb = _pad_batch2d([flat.re, flat.im], batch, block_batch)
    out = _fused2d.fft2d_fused_pallas(SplitComplex(re, im), inverse=inverse,
                                      block_batch=bb, interpret=interpret)
    out = SplitComplex(out.re[:batch], out.im[:batch])
    return SplitComplex(out.re.reshape(*lead, h, w),
                        out.im.reshape(*lead, h, w))


@functools.partial(jax.jit, static_argnames=("inverse", "block_batch",
                                             "variant", "interpret"))
def fft2d_gemm(x: SplitComplex, *, inverse: bool = False,
               block_batch: int = 1, variant: str = "plain",
               interpret: bool = None) -> SplitComplex:
    """GEMM-formulated fused 2-D FFT over the last two axes (any leading
    batch dims): DFT matmul passes, transpose absorbed; see
    :mod:`repro.kernels.fft2d_gemm`.  ``variant="compensated"`` runs the
    precision-compensated bf16 path (split tables + fp32 accumulation)."""
    if interpret is None:
        interpret = not _on_tpu()
    flat, lead = _flatten2d(x)
    h, w = flat.shape[-2:]
    batch = flat.shape[0]
    if batch == 0:
        return x                       # empty batch: nothing to transform
    (re, im), bb = _pad_batch2d([flat.re, flat.im], batch, block_batch)
    out = _gemm2d.fft2d_gemm_pallas(SplitComplex(re, im), inverse=inverse,
                                    block_batch=bb, variant=variant,
                                    interpret=interpret)
    return SplitComplex(out.re[:batch].reshape(*lead, h, w),
                        out.im[:batch].reshape(*lead, h, w))


def _flatten3d(x: SplitComplex):
    d, h, w = x.shape[-3:]
    lead = x.shape[:-3]
    batch = 1
    for n in lead:
        batch *= n
    return SplitComplex(x.re.reshape(batch, d, h, w),
                        x.im.reshape(batch, d, h, w)), lead


def _pad_batch3d(arrs, batch: int, block_batch: int):
    """Pad flattened (batch, d, h, w) component planes up to the block
    size.  Callers guard ``batch > 0``."""
    bb = min(block_batch, batch)
    pad = (-batch) % bb
    if pad:
        arrs = [jnp.pad(a, ((0, pad),) + ((0, 0),) * 3) for a in arrs]
    return arrs, bb


@functools.partial(jax.jit, static_argnames=("inverse", "block_batch",
                                             "variant", "interpret"))
def fft3d_fused(x: SplitComplex, *, inverse: bool = False,
                block_batch: int = 1, variant: str = "plain",
                interpret: bool = None) -> SplitComplex:
    """Fused 3-D FFT over the last three axes (any leading batch dims):
    pencil-in-VMEM four-step GEMM passes, both relayouts absorbed; see
    :mod:`repro.kernels.fft3d_fused`."""
    if interpret is None:
        interpret = not _on_tpu()
    flat, lead = _flatten3d(x)
    d, h, w = flat.shape[-3:]
    batch = flat.shape[0]
    if batch == 0:
        return x                       # empty batch: nothing to transform
    (re, im), bb = _pad_batch3d([flat.re, flat.im], batch, block_batch)
    out = _fused3d.fft3d_fused_pallas(SplitComplex(re, im), inverse=inverse,
                                      block_batch=bb, variant=variant,
                                      interpret=interpret)
    return SplitComplex(out.re[:batch].reshape(*lead, d, h, w),
                        out.im[:batch].reshape(*lead, d, h, w))


@functools.partial(jax.jit, static_argnames=("block_batch", "interpret"))
def rfft2d_fused(x: jnp.ndarray, *, block_batch: int = 1,
                 interpret: bool = None) -> SplitComplex:
    """Fused real-input 2-D FFT over the last two axes (any leading batch
    dims): real (..., h, w) -> (..., h, w//2+1) half spectra; see
    :mod:`repro.kernels.rfft2d_fused`."""
    if interpret is None:
        interpret = not _on_tpu()
    h, w = x.shape[-2:]
    lead = x.shape[:-2]
    batch = 1
    for d in lead:
        batch *= d
    if batch == 0:
        empty = jnp.zeros((*lead, h, w // 2 + 1), x.dtype)
        return SplitComplex(empty, empty)
    (flat,), bb = _pad_batch2d([x.reshape(batch, h, w)], batch, block_batch)
    out = _rfused2d.rfft2d_fused_pallas(flat, block_batch=bb,
                                        interpret=interpret)
    return SplitComplex(out.re[:batch].reshape(*lead, h, w // 2 + 1),
                        out.im[:batch].reshape(*lead, h, w // 2 + 1))


@functools.partial(jax.jit, static_argnames=("block_batch", "interpret"))
def irfft2d_fused(xf: SplitComplex, *, block_batch: int = 1,
                  interpret: bool = None) -> jnp.ndarray:
    """Inverse twin of :func:`rfft2d_fused`: (..., h, w/2+1) half spectra ->
    real (..., h, w)."""
    if interpret is None:
        interpret = not _on_tpu()
    h, bins = xf.shape[-2:]
    w = 2 * (bins - 1)
    lead = xf.shape[:-2]
    batch = 1
    for d in lead:
        batch *= d
    if batch == 0:
        return jnp.zeros((*lead, h, w), xf.dtype)
    (re, im), bb = _pad_batch2d([xf.re.reshape(batch, h, bins),
                                 xf.im.reshape(batch, h, bins)],
                                batch, block_batch)
    out = _rfused2d.irfft2d_fused_pallas(SplitComplex(re, im),
                                         block_batch=bb,
                                         interpret=interpret)
    return out[:batch].reshape(*lead, h, w)


def _fftconv_ref(x3: jnp.ndarray, kf: SplitComplex) -> jnp.ndarray:
    """Differentiable jnp twin of the fused conv core: the same
    rfft -> pointwise multiply -> irfft math at the padded length."""
    from repro.core import complexmath as cm
    from repro.core import fft1d
    m = x3.shape[-1]
    xf = fft1d.rfft(x3)                        # registry-resolved jnp algos:
    return fft1d.irfft(cm.mul(xf, kf), m)      # same VJP as the unfused plan


# pallas_call has no autodiff rules, but the conv core is bilinear in
# (x, kf), so the jnp twin's VJP is exact: forward stays on the fused
# kernel, backward runs the composed jnp transforms.  The packed filter
# pair ef derives linearly from kf (repro.kernels.fftconv_fused
# .pack_filter), so the bwd returns the TOTAL kf gradient through the
# kf slot and zeros for ef — anything nonzero there would double count.
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _fftconv_core(x3, kf, ef, block_batch, interpret):
    from . import fftconv_fused as _fconv
    return _fconv.fftconv_fused_pallas(x3, ef, block_batch=block_batch,
                                       interpret=interpret)


def _fftconv_core_fwd(x3, kf, ef, block_batch, interpret):
    return _fftconv_core(x3, kf, ef, block_batch, interpret), (x3, kf, ef)


def _fftconv_core_bwd(block_batch, interpret, res, g):
    x3, kf, ef = res
    _, vjp = jax.vjp(_fftconv_ref, x3, kf)
    dx, dkf = vjp(g)
    return dx, dkf, jax.tree_util.tree_map(jnp.zeros_like, ef)


_fftconv_core.defvjp(_fftconv_core_fwd, _fftconv_core_bwd)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _fftconv_jit(xb, kfb, efb, block_batch, interpret):
    return _fftconv_core(xb, kfb, efb, block_batch, interpret)


def fftconv_fused(x: jnp.ndarray, kf: SplitComplex, *, block_batch: int = 1,
                  interpret: bool = None) -> jnp.ndarray:
    """Fused FFT convolution over the last axis: real x (..., m) circularly
    convolved per-row with the filter half spectra kf (..., m//2+1) ->
    real of the broadcast shape; see :mod:`repro.kernels.fftconv_fused`.

    The leading dims of x and kf broadcast; the last lead dim becomes the
    kernel's row axis.  A kf whose lead dims broadcast to
    just the row axis — e.g. the SSM channel bank (C, K) against
    (B, C, L) activations — stays a (rows, m//2) shared operand staged
    once per grid step instead of materialising per-batch copies.

    NOT jitted on purpose: the filter packs into its packed-domain
    operands (E, F) at the Python level, so a concrete filter — the
    eager-serving and closure-constant benchmark patterns — packs in
    float64 numpy, cached per filter identity, and enters the traced
    graph as a constant.  Traced filters pack in-graph (the training
    pattern: the filter changes every step, so per-step packing is
    semantically required)."""
    import numpy as np
    from . import fftconv_fused as _fconv
    if interpret is None:
        interpret = not _on_tpu()
    m = x.shape[-1]
    hm = m // 2
    lead = np.broadcast_shapes(x.shape[:-1], kf.re.shape[:-1])
    out_shape = lead + (m,)
    lead = lead if lead else (1,)
    r = lead[-1]
    batch = 1
    for d in lead[:-1]:
        batch *= d
    if batch == 0 or r == 0:
        return jnp.zeros(out_shape, x.dtype)
    xb = jnp.broadcast_to(x, lead + (m,)).reshape(batch, r, m)
    klead = kf.re.shape[:-1]
    # pack in the filter's OWN lead shape (identity-cache-friendly: the
    # broadcast copies below are fresh arrays every call, the caller's
    # filter object is not), then broadcast E/F exactly like kf
    e, f = _fconv.pack_filter(kf, m, x.dtype)

    def _bcast(sc, bins, to2, to3):
        if to2 is not None:
            return SplitComplex(
                jnp.broadcast_to(sc.re, to2 + (bins,)).reshape(r, bins),
                jnp.broadcast_to(sc.im, to2 + (bins,)).reshape(r, bins))
        return SplitComplex(
            jnp.broadcast_to(sc.re, to3 + (bins,)).reshape(batch, r, bins),
            jnp.broadcast_to(sc.im, to3 + (bins,)).reshape(batch, r, bins))

    # shared bank iff the filter's lead dims broadcast to one row axis
    shared = int(np.prod(np.broadcast_shapes(klead, (r,)), dtype=np.int64)) \
        == r
    to2 = np.broadcast_shapes(klead, (r,)) if shared else None
    to3 = None if shared else lead
    kfb = _bcast(kf, hm + 1, to2, to3)
    efb = (_bcast(e, hm, to2, to3), _bcast(f, hm, to2, to3))
    bb = min(block_batch, batch)
    pad = (-batch) % bb
    if pad:
        xb = jnp.pad(xb, ((0, pad), (0, 0), (0, 0)))
        if not shared:
            bpad = ((0, pad), (0, 0), (0, 0))
            padsc = lambda sc: SplitComplex(jnp.pad(sc.re, bpad),
                                            jnp.pad(sc.im, bpad))
            kfb = padsc(kfb)
            efb = (padsc(efb[0]), padsc(efb[1]))
    out = _fftconv_jit(xb, kfb, efb, bb, interpret)
    return out[:batch, :r].reshape(out_shape)


@functools.partial(jax.jit, static_argnames=("inverse", "block_batch", "n1",
                                             "interpret"))
def fft_fourstep(x: SplitComplex, *, inverse: bool = False,
                 block_batch: int = 4, n1: int = None,
                 interpret: bool = None) -> SplitComplex:
    if interpret is None:
        interpret = not _on_tpu()
    flat, lead = _flatten(x)
    padded, batch = _pad_batch(flat, block_batch)
    out = _fourstep.fft_fourstep_pallas(padded, inverse=inverse,
                                        block_batch=block_batch, n1=n1,
                                        interpret=interpret)
    out = SplitComplex(out.re[:batch], out.im[:batch])
    return _unflatten(out, lead)


@functools.partial(jax.jit, static_argnames=("window", "chunk",
                                             "block_batch", "interpret"))
def decode_attention(q, k_cache, v_cache, kv_pos, q_pos, *, window=None,
                     chunk: int = 512, block_batch: int = 8,
                     interpret: bool = None):
    """Flash-decode kernel (see kernels.decode_attention): the TPU fix for
    the copy-bound XLA decode attention measured in EXPERIMENTS.md §Perf D2."""
    from . import decode_attention as _da
    if interpret is None:
        interpret = not _on_tpu()
    b = q.shape[0]
    bb = min(block_batch, b)
    pad = (-b) % bb
    if pad:
        q = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
        k_cache = jnp.pad(k_cache, ((0, pad), (0, 0), (0, 0), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, pad), (0, 0), (0, 0), (0, 0)))
        kv_pos = jnp.pad(kv_pos, ((0, pad), (0, 0)), constant_values=-1)
        q_pos = jnp.pad(q_pos, ((0, pad),))
    out = _da.decode_attention_pallas(q, k_cache, v_cache, kv_pos, q_pos,
                                      window=window, chunk=chunk,
                                      block_batch=bb, interpret=interpret)
    return out[:b]


@functools.partial(jax.jit, static_argnames=("inverse", "block_batch",
                                             "interpret"))
def fft_staged(x: SplitComplex, *, inverse: bool = False,
               block_batch: int = 8, interpret: bool = None) -> SplitComplex:
    """Paper-faithful per-stage kernel chain (the Table 1 baseline)."""
    if interpret is None:
        interpret = not _on_tpu()
    flat, lead = _flatten(x)
    padded, batch = _pad_batch(flat, block_batch)
    out = _stage.fft_staged_pallas(padded, inverse=inverse,
                                   block_batch=block_batch,
                                   interpret=interpret)
    out = SplitComplex(out.re[:batch], out.im[:batch])
    return _unflatten(out, lead)
