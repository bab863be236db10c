"""Distributed pencil FFTs: the paper's Section 5 schedule at multi-device
scale.

The single-chip 2-D FFT in the paper is *local row FFTs -> global transpose
-> local column FFTs*; scaled across devices that global transpose becomes
an ``all_to_all`` over pencils (the slab/pencil decomposition every
distributed FFT library is built on).  What lives here:

- :func:`pfft2`               2-D FFT, rows sharded over one mesh axis.  One
                              all_to_all replaces the HBM transpose; the
                              optional ``chunks=`` schedule splits the row
                              pass so each chunk's all_to_all can overlap the
                              next chunk's compute (the paper's
                              communication-hiding ambition, expressed as a
                              static interleaving XLA is free to pipeline).
- :func:`pfft2_hierarchical`  Two-hop transpose for a (pod, data) mesh: one
                              intra-pod all_to_all then one inter-pod
                              all_to_all, so the scarce pod-to-pod bandwidth
                              only ever carries already-pencilised tiles.
- :func:`pfft3`               3-D FFT over a 2-D process grid (pencil
                              decomposition proper; the paper's future-work
                              case): Z local, then two axis exchanges.
- :func:`pfft1d`              Distributed Bailey four-step for one giant 1-D
                              FFT: column FFTs, twiddle correction, row FFTs
                              with the two inter-step transposes as
                              all_to_alls.  Output stays in the four-step
                              (h, w) layout (flattened, row-sharded); the
                              matching ``inverse=True`` consumes exactly that
                              layout, so roundtrips are exact.
- :func:`prfft2` / :func:`pirfft2`  Real-input 2-D pencil FFT: the row pass
                              is an rfft (half the FLOPs), and the
                              all_to_all ships only the Hermitian-unique
                              half spectrum — the Nyquist column rides in
                              the DC column's imaginary plane (both are
                              real for real input), so exactly W/2 complex
                              pencils cross the wire: **half** of
                              :func:`pfft2`'s exchange bytes, the ROADMAP's
                              "halve the all_to_all bytes" follow-on.
                              Leading axes are a batch of fields.
- :func:`pfilter2`            A spectral operator between the two:
                              ``irfft2(rfft2(x) * g)`` as one program, the
                              operator applied in the packed layout
                              (:func:`shard_half_operator` places it).

Every all_to_all optionally passes through the compressed wire formats of
:mod:`repro.dist.compression` (``compress="bf16"``/``"int8"``), and records
its per-device payload bytes — as priced by
:func:`repro.dist.compression.wire_bytes` — in a module-level wire log
(:func:`reset_wire_log` / :func:`wire_log` / :func:`logged_exchange_bytes`)
so tests and benchmarks can pin *measured* exchange traffic against the
:func:`repro.tt.trace.trace_dist` prediction.  ``verify=True`` on
:func:`pfft2` / :func:`prfft2` / :func:`pirfft2` additionally checksums
every exchange in-graph (global payload energy) and retries the transform
once on mismatch — :class:`ExchangeIntegrityError` on a repeat failure,
never a silent wrong answer (see the exchange-integrity block below).

All local 1-D passes route through the plan registry
(:mod:`repro.core.plan`) via ``algo="auto"``, so the fused/Stockham kernels
and any autotune decisions from the single-chip path are reused per local
shape; ``backend="pallas"`` switches the local passes onto the Pallas
kernels.  Everything operates on :class:`~repro.core.complexmath.SplitComplex`
(separate re/im planes — no complex dtype anywhere, mirroring the Tensix
constraint).  The local passes and the exchanges of the real-input
transforms run under ``jax.named_scope`` names (``pencil.row_rfft``,
``pencil.a2a``, ``pencil.col_fft``, ``pencil.operator``, ``pencil.col_ifft``,
``pencil.row_irfft``), which the compiled program's op metadata carries.
"""
from __future__ import annotations

import collections
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.complexmath import SplitComplex, mul
from repro.core import fft1d
from repro.core import plan as plan_lib

from repro.resilience import faults as _faults

from ._compat import all_to_all, shard_map_unchecked
from .compression import all_to_all_compressed, wire_bytes


# ---------------------------------------------------------------------------
# Wire log: measured exchange traffic
# ---------------------------------------------------------------------------
# Every _a2a records the per-device payload it ships (as priced by
# compression.wire_bytes for its wire format) at trace time — payload shapes
# are static, so tracers log exactly what a real wire counter would.  The
# byte total is a plain running counter; per-entry records are kept in a
# bounded deque so a long-running loop that never resets cannot leak.

_WIRE_LOG = collections.deque(maxlen=1024)
_WIRE_TOTAL = 0


def reset_wire_log() -> None:
    global _WIRE_TOTAL
    _WIRE_TOTAL = 0
    _WIRE_LOG.clear()


def wire_log() -> list:
    """Recent entries ``{"tag", "method", "bytes"}``, one per all_to_all
    traced (most recent 1024)."""
    return list(_WIRE_LOG)


def logged_exchange_bytes() -> int:
    """Total per-device payload bytes shipped since the last reset."""
    return _WIRE_TOTAL


def _log_wire(tag: str, method: str, nbytes: int) -> None:
    global _WIRE_TOTAL
    _WIRE_TOTAL += nbytes
    _WIRE_LOG.append({"tag": tag, "method": method, "bytes": nbytes})


# ---------------------------------------------------------------------------
# Exchange integrity: energy checksum, verified post-exchange
# ---------------------------------------------------------------------------
# An all_to_all is a permutation of the payload, so the *global* payload
# energy (sum of squares, psum'd over the mesh axis) is conserved exactly —
# a lightweight in-graph checksum with no extra wire beyond two scalar
# psums.  A dropped shard removes ~1/p of the energy, a scaled/garbled
# payload shifts it, and a NaN/Inf poisons the comparison (NaN <= tol is
# False) — all detected by one relative-delta test.  ``verify=True`` on
# :func:`pfft2` / :func:`prfft2` / :func:`pirfft2` threads every exchange's
# delta out of the shard_map as a replicated scalar, checks it eagerly, and
# retries the whole transform **once** on mismatch (a transient wire fault
# does not recur; the injected ``dist.exchange`` faults are consumed on the
# first attempt, which is exactly the transient model).  A second mismatch
# raises :class:`ExchangeIntegrityError` — never a silent wrong answer.
# Lossy wire formats legitimately perturb energy, hence per-method
# tolerances.

_VERIFY_TOL = {"none": 1e-3, "bf16": 2e-2, "int8": 2e-2}

_EXCHANGE_LOG = collections.deque(maxlen=256)


class ExchangeIntegrityError(RuntimeError):
    """A pencil exchange failed its energy checksum even after retry."""

    def __init__(self, tag: str, delta: float, tol: float):
        self.tag, self.delta, self.tol = tag, delta, tol
        super().__init__(
            f"exchange checksum mismatch in {tag!r}: relative energy "
            f"delta {delta:.3g} > {tol:g} after retry")


def reset_exchange_log() -> None:
    _EXCHANGE_LOG.clear()


def exchange_log() -> list:
    """Recent verification events ``{"tag", "method", "delta", "ok",
    "attempt"}`` — one per verified transform attempt (most recent 256)."""
    return list(_EXCHANGE_LOG)


def _payload_energy(x: SplitComplex):
    return (jnp.sum(jnp.square(x.re.astype(jnp.float32)))
            + jnp.sum(jnp.square(x.im.astype(jnp.float32))))


def _wire_fault(y: SplitComplex, axis_name: str, tag: str) -> SplitComplex:
    """The ``dist.exchange`` fault site: corrupt the payload *received on
    device 0* (``lax.axis_index`` mask) when an armed spec fires.  Consulted
    at trace time — the pencil bodies are re-traced per transform call, so
    visit counting works, and a one-shot spec is consumed by the first
    attempt, leaving the retry clean."""
    spec = _faults.fire("dist.exchange", tag)
    if spec is None:
        return y
    bad = _faults.apply_corruption(y, spec)
    on0 = jax.lax.axis_index(axis_name) == 0
    return SplitComplex(jnp.where(on0, bad.re, y.re),
                        jnp.where(on0, bad.im, y.im))


def _max_delta(collect):
    d = collect[0]
    for extra in collect[1:]:
        d = jnp.maximum(d, extra)
    return d


def _run_verified(run, *, tag: str, method: str, retries: int = 1):
    """Eager driver for ``verify=True`` transforms: run, check the
    replicated delta, retry once, raise on repeat mismatch."""
    tol = _VERIFY_TOL.get(method, _VERIFY_TOL["none"])
    delta = float("nan")
    for attempt in range(1 + retries):
        out, d = run()
        delta = float(jax.device_get(d))
        ok = delta <= tol                    # NaN compares False: poisoned
        _EXCHANGE_LOG.append({"tag": tag, "method": method, "delta": delta,
                              "ok": bool(ok), "attempt": attempt})
        if ok:
            return out
    raise ExchangeIntegrityError(tag, delta, tol)


# ---------------------------------------------------------------------------
# Local helpers (run inside shard_map on per-device blocks)
# ---------------------------------------------------------------------------

def _fft_last(x: SplitComplex, *, inverse: bool, backend: str) -> SplitComplex:
    """1-D FFT of the last axis through the plan registry (algo="auto")."""
    pl = plan_lib.get_plan((x.shape[-1],), dtype=x.dtype, inverse=inverse,
                           backend=backend)
    return pl(x)


def _fft_axis(x: SplitComplex, axis: int, *, inverse: bool,
              backend: str) -> SplitComplex:
    re = jnp.moveaxis(x.re, axis, -1)
    im = jnp.moveaxis(x.im, axis, -1)
    y = _fft_last(SplitComplex(re, im), inverse=inverse, backend=backend)
    return SplitComplex(jnp.moveaxis(y.re, -1, axis),
                        jnp.moveaxis(y.im, -1, axis))


def _a2a(x: SplitComplex, axis_name: str, split_axis: int, concat_axis: int,
         *, method: str = "none", tag: str = "a2a",
         collect=None) -> SplitComplex:
    """One pencil exchange.  ``collect`` (a list) arms the energy checksum:
    the exchange's relative global-energy delta is appended as a traced
    replicated scalar for the transform body to return."""
    _log_wire(tag, method, wire_bytes((x.re, x.im), method))
    with jax.named_scope("pencil.a2a"):
        if collect is not None:
            e0 = jax.lax.psum(_payload_energy(x), axis_name)
        if method == "none":
            y = SplitComplex(
                all_to_all(x.re, axis_name, split_axis, concat_axis),
                all_to_all(x.im, axis_name, split_axis, concat_axis))
        else:
            y = SplitComplex(
                all_to_all_compressed(x.re, axis_name, split_axis,
                                      concat_axis, method),
                all_to_all_compressed(x.im, axis_name, split_axis,
                                      concat_axis, method))
        y = _wire_fault(y, axis_name, tag)
        if collect is not None:
            e1 = jax.lax.psum(_payload_energy(y), axis_name)
            collect.append(jnp.abs(e1 - e0) / (e0 + 1e-30))
    return y


def _swap_last2(x: SplitComplex) -> SplitComplex:
    return SplitComplex(jnp.swapaxes(x.re, -1, -2),
                        jnp.swapaxes(x.im, -1, -2))


# ---------------------------------------------------------------------------
# 2-D pencil FFT over one mesh axis
# ---------------------------------------------------------------------------

def pfft2(x: SplitComplex, mesh, axis: str = "data", *, chunks: int = 1,
          transposed_output: bool = True, inverse: bool = False,
          compress: str = "none", backend: str = "jnp",
          verify: bool = False) -> SplitComplex:
    """2-D FFT of a (H, W) array whose rows are sharded over ``axis``.

    Schedule per device (p = mesh size along ``axis``):

    1. local row FFTs on the (H/p, W) slab — in ``chunks`` slices, each
       immediately followed by its all_to_all so communication of chunk c
       can overlap compute of chunk c+1;
    2. all_to_all pencil transpose (H/p, W) -> (H, W/p);
    3. local column FFTs on the now-resident columns.

    With ``transposed_output=True`` (default) the result is returned as the
    (W, H) transpose — column-major frequencies — sharded over ``axis``;
    this needs *no second all_to_all* (only a local transpose), exactly like
    the paper's fused kernel leaves the transpose implicit.  With
    ``transposed_output=False`` a second all_to_all restores natural (H, W)
    row-sharded order, so ``pfft2(pfft2(x), inverse=True)`` roundtrips.
    ``compress`` routes the exchanges through the
    :mod:`repro.dist.compression` wire formats.  ``verify=True`` checksums
    every exchange (global payload energy, conserved by any permutation),
    retries the transform once on mismatch and raises
    :class:`ExchangeIntegrityError` if the retry fails too.
    """
    h, w = x.shape[-2], x.shape[-1]
    p = mesh.shape[axis]
    assert h % p == 0 and w % p == 0, (x.shape, p)
    assert (h // p) % chunks == 0, (h, p, chunks)

    def run(collect=None):
        def body(re, im):
            rows = re.shape[0]                   # H/p local rows
            rc = rows // chunks
            pieces = []
            for c in range(chunks):
                sl = slice(c * rc, (c + 1) * rc)
                y = _fft_last(SplitComplex(re[sl], im[sl]),
                              inverse=inverse, backend=backend)
                pieces.append(_a2a(y, axis, 1, 0, method=compress,
                                   tag="pfft2/a2a",
                                   collect=collect))  # (p*rc, W/p)
            if chunks == 1:
                z = pieces[0]
            else:
                # chunk-major (chunks, p, rc, W/p) -> natural (p, chunks, ..)
                sr = jnp.stack([q.re for q in pieces]) \
                        .reshape(chunks, p, rc, -1)
                si = jnp.stack([q.im for q in pieces]) \
                        .reshape(chunks, p, rc, -1)
                z = SplitComplex(sr.transpose(1, 0, 2, 3).reshape(h, -1),
                                 si.transpose(1, 0, 2, 3).reshape(h, -1))
            z = _fft_axis(z, 0, inverse=inverse, backend=backend)  # (H, W/p)
            if transposed_output:
                out = _swap_last2(z)             # (W/p, H): local only
            else:
                out = _a2a(z, axis, 0, 1, method=compress,
                           tag="pfft2/a2a_out",
                           collect=collect)      # (H/p, W): natural order
            if collect is None:
                return out
            return out, _max_delta(collect)

        out_spec = P(axis, None)
        outs = SplitComplex(out_spec, out_spec)
        fn = shard_map_unchecked(body, mesh=mesh,
                       in_specs=(P(axis, None), P(axis, None)),
                       out_specs=outs if collect is None else (outs, P()))
        return fn(x.re, x.im)

    if not verify:
        return run()
    return _run_verified(lambda: run(collect=[]), tag="pfft2",
                         method=compress)


# ---------------------------------------------------------------------------
# Real-input 2-D pencil FFT (the halved-exchange schedule)
# ---------------------------------------------------------------------------
# Layout of the exchanged/returned half spectrum ("packed"): an rfft row has
# W/2+1 bins, but bins 0 (DC) and W/2 (Nyquist) are exactly real, so the
# Nyquist bin is carried in the DC bin's imaginary slot.  W real samples
# become exactly W/2 complex values per row — information-tight — and the
# pencil exchange ships W/2 columns instead of pfft2's W.  After the column
# FFTs the packed column 0 holds FFT(dc_col) + i*FFT(nyq_col); because
# dc_col/nyq_col are real, :func:`unpack_half_spectrum` recovers both with
# the standard Hermitian untangle (a local O(H) post-pass, no extra wire).


def _pack_rows(y: SplitComplex) -> SplitComplex:
    """(..., W/2+1) row half-spectra -> (..., W/2) packed (Nyquist into the
    DC imaginary plane; both bins are exactly real for real input)."""
    hw = y.shape[-1] - 1
    return SplitComplex(
        y.re[..., :hw],
        jnp.concatenate([y.re[..., hw:], y.im[..., 1:hw]], axis=-1))


def _unpack_rows(z: SplitComplex) -> SplitComplex:
    """Inverse of :func:`_pack_rows`: (..., W/2) packed -> (..., W/2+1)."""
    zero = jnp.zeros_like(z.re[..., :1])
    return SplitComplex(
        jnp.concatenate([z.re[..., :1], z.re[..., 1:], z.im[..., :1]], -1),
        jnp.concatenate([zero, z.im[..., 1:], zero], -1))


def _split_packed_col(z: SplitComplex):
    """Hermitian-untangle one packed column Z = A + i*B (A, B the FFTs of
    two real length-H sequences) into (A, B).  Acts on the last axis."""
    h = z.shape[-1]
    idx = (-jnp.arange(h)) % h
    cr = jnp.take(z.re, idx, axis=-1)          # conj(Z[-k]): re
    ci = -jnp.take(z.im, idx, axis=-1)         # conj(Z[-k]): im
    a = SplitComplex((z.re + cr) * 0.5, (z.im + ci) * 0.5)
    b = SplitComplex((z.im - ci) * 0.5, (cr - z.re) * 0.5)
    return a, b


def unpack_half_spectrum(spec_t: SplitComplex) -> SplitComplex:
    """Expand :func:`prfft2`'s packed transposed output (..., W/2, H) into
    the standard transposed half spectrum (..., W/2+1, H) —
    ``numpy.fft.rfft2(x).T`` — by untangling the packed column 0 into the
    DC and Nyquist columns.  Pure jnp; run it on the gathered result (or
    any full-H shard)."""
    dc, nyq = _split_packed_col(
        SplitComplex(spec_t.re[..., 0, :], spec_t.im[..., 0, :]))
    cat = lambda r0, body, rn: jnp.concatenate(
        [r0[..., None, :], body, rn[..., None, :]], axis=-2)
    return SplitComplex(cat(dc.re, spec_t.re[..., 1:, :], nyq.re),
                        cat(dc.im, spec_t.im[..., 1:, :], nyq.im))


def pack_half_spectrum(spec_t: SplitComplex) -> SplitComplex:
    """Inverse of :func:`unpack_half_spectrum`: fold a standard transposed
    half spectrum (..., W/2+1, H) into the packed (..., W/2, H) layout
    :func:`pirfft2` consumes (row 0 := DC + i*Nyquist)."""
    dc = SplitComplex(spec_t.re[..., 0, :], spec_t.im[..., 0, :])
    ny = SplitComplex(spec_t.re[..., -1, :], spec_t.im[..., -1, :])
    row0_re = dc.re - ny.im
    row0_im = dc.im + ny.re
    return SplitComplex(
        jnp.concatenate([row0_re[..., None, :], spec_t.re[..., 1:-1, :]], -2),
        jnp.concatenate([row0_im[..., None, :], spec_t.im[..., 1:-1, :]], -2))


def _fit_last(x: SplitComplex, n: int) -> SplitComplex:
    """Truncate / zero-pad the last axis to ``n`` (numpy ``fft(a, n=...)``
    semantics: crop or append trailing zeros)."""
    cur = x.shape[-1]
    if cur == n:
        return x
    if cur > n:
        return SplitComplex(x.re[..., :n], x.im[..., :n])
    pad = [(0, 0)] * (x.re.ndim - 1) + [(0, n - cur)]
    return SplitComplex(jnp.pad(x.re, pad), jnp.pad(x.im, pad))


def _set_row0_on_owner(plane, row, axis: str):
    """``plane`` (..., rows, cols) with its local row 0 replaced by ``row``
    on the device that owns global row 0 (``axis_index == 0``)."""
    own0 = jax.lax.axis_index(axis) == 0
    return plane.at[..., 0, :].set(jnp.where(own0, row, plane[..., 0, :]))


def _rows_spec(ndim: int, axis: str):
    """Spec of a (..., rows, cols) array whose rows (axis -2) are sharded
    over ``axis``; leading batch axes stay whole on every device."""
    return P(*([None] * (ndim - 2)), axis, None)


def _prfft2_local(xr, axis: str, *, compress: str, backend: str,
                  collect=None) -> SplitComplex:
    """:func:`prfft2`'s per-device body up to its column FFTs: real
    (..., H/p, W) -> packed (..., H, W/(2p)), columns transformed."""
    w = xr.shape[-1]
    with jax.named_scope("pencil.row_rfft"):
        pl = plan_lib.get_plan((w,), dtype=xr.dtype, kind="rfft",
                               backend=backend)
        y = _pack_rows(pl(xr))                   # (..., H/p, W/2) packed
    z = _a2a(y, axis, y.re.ndim - 1, y.re.ndim - 2, method=compress,
             tag="prfft2/a2a", collect=collect)  # (..., H, W/(2p))
    with jax.named_scope("pencil.col_fft"):
        return _fft_axis(z, -2, inverse=False, backend=backend)


def _pirfft2_local(zin: SplitComplex, axis: str, h_out: int, w_out: int, *,
                   compress: str, backend: str, collect=None):
    """:func:`pirfft2`'s per-device body: packed transposed
    (..., W/(2p), h_in) -> real (..., h_out/p, w_out)."""
    h_in = zin.shape[-1]
    with jax.named_scope("pencil.col_ifft"):
        z = _fit_last(zin, h_out)                # numpy ifft n= fit
        z = _fft_last(z, inverse=True, backend=backend)
        if h_out != h_in:
            # the H fit breaks the packed column's Hermitian symmetry (a
            # cropped/padded DC column no longer inverse-transforms to a
            # real signal), so the packed column is untangled at full
            # height, fitted and transformed as two real columns, and
            # spliced back on the device that owns global column 0
            dc, ny = _split_packed_col(
                SplitComplex(zin.re[..., 0, :], zin.im[..., 0, :]))
            a = _fft_last(_fit_last(dc, h_out), inverse=True,
                          backend=backend)
            b = _fft_last(_fit_last(ny, h_out), inverse=True,
                          backend=backend)
            z = SplitComplex(_set_row0_on_owner(z.re, a.re, axis),
                             _set_row0_on_owner(z.im, b.re, axis))
    z = _a2a(z, axis, z.re.ndim - 1, z.re.ndim - 2, method=compress,
             tag="pirfft2/a2a", collect=collect)  # (..., W/2, h_out/p)
    with jax.named_scope("pencil.row_irfft"):
        z = _swap_last2(z)                       # (..., h_out/p, W/2) packed
        half = fft1d._fit_half_spectrum(_unpack_rows(z), w_out)
        pl = plan_lib.get_plan((w_out,), dtype=z.dtype, kind="rfft",
                               inverse=True, backend=backend)
        return pl(half)                          # real (..., h_out/p, w_out)


def prfft2(x: jnp.ndarray, mesh, axis: str = "data", *,
           transposed_output: bool = True, compress: str = "none",
           backend: str = "jnp", verify: bool = False) -> SplitComplex:
    """Real-input 2-D pencil FFT of real (..., H, W) fields whose rows
    (axis -2) are sharded over ``axis``: the distributed
    :func:`repro.core.fft2d.rfft2`.  Leading axes are a batch: one
    all_to_all per plane carries all of it.

    Schedule per device (p = mesh size along ``axis``):

    1. local row rfft via the plan registry's ``kind="rfft"`` entries
       ((H/p, W) real -> (H/p, W/2+1) half spectra, half the row FLOPs;
       ``backend="pallas"`` runs the inner transform on the 1-D kernels);
    2. pack: Nyquist bin into the DC bin's imaginary plane -> (H/p, W/2);
    3. all_to_all of the W/2 packed pencils — **half** of :func:`pfft2`'s
       exchange bytes — to (H, W/(2p));
    4. local column FFTs on the full-height packed pencils.

    Output (default) is the packed transposed half spectrum (..., W/2, H)
    sharded over its rows; :func:`unpack_half_spectrum` expands it to the
    standard (W/2+1, H) = ``rfft2(x).T``.  ``transposed_output=False``
    spends a second (still packed, still halved) all_to_all to return the
    natural row-sharded (..., H/p, W/2) layout instead.  ``verify=True``
    checksums the exchanges as in :func:`pfft2`.
    """
    h, w = x.shape[-2], x.shape[-1]
    p = mesh.shape[axis]
    assert w % 2 == 0, f"prfft2 needs an even width, got {x.shape}"
    assert h % p == 0 and (w // 2) % p == 0, (x.shape, p)

    def run(collect=None):
        def body(xr):
            z = _prfft2_local(xr, axis, compress=compress, backend=backend,
                              collect=collect)
            if transposed_output:
                out = _swap_last2(z)             # (..., W/(2p), H)
            else:
                out = _a2a(z, axis, z.re.ndim - 2, z.re.ndim - 1,
                           method=compress, tag="prfft2/a2a_out",
                           collect=collect)      # (..., H/p, W/2) natural
            if collect is None:
                return out
            return out, _max_delta(collect)

        spec = _rows_spec(x.ndim, axis)
        outs = SplitComplex(spec, spec)
        fn = shard_map_unchecked(body, mesh=mesh, in_specs=(spec,),
                                 out_specs=outs if collect is None
                                 else (outs, P()))
        return fn(x)

    if not verify:
        return run()
    return _run_verified(lambda: run(collect=[]), tag="prfft2",
                         method=compress)


def pirfft2(xf: SplitComplex, mesh, axis: str = "data", *, s=None,
            compress: str = "none", backend: str = "jnp",
            verify: bool = False) -> jnp.ndarray:
    """Inverse of :func:`prfft2`: packed transposed half spectra
    (..., W/2, H) sharded over their rows (axis -2) -> real (..., H, W)
    row-sharded.

    ``s=(h, w)`` follows ``numpy.fft.irfft2`` truncate/pad semantics.  Both
    fits are *local*: the H fit happens on the full-height pencils before
    the inverse column FFTs, and the W fit on the complete row half-spectra
    after the exchange — so explicit shapes never cost extra wire.
    """
    hw, h_in = xf.shape[-2], xf.shape[-1]
    p = mesh.shape[axis]
    h_out, w_out = (int(s[0]), int(s[1])) if s is not None else (h_in, 2 * hw)
    assert w_out % 2 == 0 and w_out >= 2, \
        f"pirfft2 needs an even output width, got s={s}"
    assert hw % p == 0 and h_out % p == 0, (xf.shape, s, p)

    def run(collect=None):
        def body(re, im):
            out = _pirfft2_local(SplitComplex(re, im), axis, h_out, w_out,
                                 compress=compress, backend=backend,
                                 collect=collect)
            if collect is None:
                return out
            return out, _max_delta(collect)

        spec = _rows_spec(xf.re.ndim, axis)
        fn = shard_map_unchecked(body, mesh=mesh, in_specs=(spec, spec),
                                 out_specs=spec if collect is None
                                 else (spec, P()))
        return fn(xf.re, xf.im)

    if not verify:
        return run()
    return _run_verified(lambda: run(collect=[]), tag="pirfft2",
                         method=compress)


# ---------------------------------------------------------------------------
# Spectral operators in the packed layout
# ---------------------------------------------------------------------------
# A real filter's operator g lives on the natural half spectrum (H, W/2+1).
# Rows 1..W/2-1 of prfft2's packed transposed output are plain columns kx of
# the spectrum and take g[:, kx] elementwise.  Row 0 is DC + i*Nyquist, and
# an operator whose DC and Nyquist columns differ (a Helmholtz solve, a
# derivative) cannot multiply it as it stands: the device that owns global
# row 0 untangles it, applies each column its own operator and repacks.  That
# needs the DC and Nyquist columns of g to be Hermitian in ky, as a real
# filter's are (an ``i*ky`` derivative with its ky = -H/2 row zeroed).


class HalfOperator(NamedTuple):
    """A spectral operator in :func:`prfft2`'s packed transposed layout, as
    :func:`shard_half_operator` places it.  ``im`` planes are None for a
    real operator."""

    rows: SplitComplex   # (W/2, H) row-sharded: row kx holds g[:, kx]
                         # (row 0 the DC column)
    nyq: SplitComplex    # (H,) on every device: the Nyquist column


def shard_half_operator(g, mesh, axis: str = "data") -> HalfOperator:
    """Place an operator ``g`` given on the natural half spectrum
    (H, W/2+1) -- real, or complex with a real filter's symmetry -- in
    float32 on ``mesh`` for :func:`pfilter2`.  Runs on the host, once."""
    g = np.asarray(g)
    if g.ndim != 2 or g.shape[1] < 2:
        raise ValueError(f"g must be an (H, W/2+1) half spectrum, got "
                         f"{g.shape}")
    hw = g.shape[1] - 1
    if hw % mesh.shape[axis]:
        raise ValueError(f"W/2 = {hw} rows do not divide over "
                         f"{mesh.shape[axis]} devices")

    def place(a, spec):
        put = lambda b: jax.device_put(np.ascontiguousarray(b, np.float32),
                                       NamedSharding(mesh, spec))
        if np.iscomplexobj(a):
            return SplitComplex(put(a.real), put(a.imag))
        return SplitComplex(put(a), None)

    return HalfOperator(place(g[:, :hw].T, P(axis, None)),
                        place(g[:, hw], P()))


def _times(z: SplitComplex, g: SplitComplex) -> SplitComplex:
    """z * g, broadcast over z's leading axes; ``g.im`` None is real."""
    if g.im is None:
        return SplitComplex(z.re * g.re, z.im * g.re)
    return mul(z, g)


def _apply_half_operator(z: SplitComplex, op: HalfOperator,
                         axis: str) -> SplitComplex:
    """Multiply the local block (..., W/(2p), H) of a packed transposed
    half spectrum by ``op``'s local rows; on the owner of global row 0,
    that row is untangled into A (DC) and B (Nyquist), A takes the DC
    column, B the Nyquist column, and A' + i*B' is packed back."""
    out = _times(z, op.rows)
    a, b = _split_packed_col(SplitComplex(z.re[..., 0, :], z.im[..., 0, :]))
    dc = SplitComplex(op.rows.re[0],
                      None if op.rows.im is None else op.rows.im[0])
    a, b = _times(a, dc), _times(b, op.nyq)
    return SplitComplex(_set_row0_on_owner(out.re, a.re - b.im, axis),
                        _set_row0_on_owner(out.im, a.im + b.re, axis))


def pfilter2(x: jnp.ndarray, op: HalfOperator, mesh,
             axis: str = "data") -> jnp.ndarray:
    """``irfft2(rfft2(x) * g)`` of real (..., H, W) fields whose rows are
    sharded over ``axis``, as one shard_map program: the body of
    :func:`prfft2`, the operator ``op`` (from :func:`shard_half_operator`)
    on the packed transposed half spectrum, then the body of
    :func:`pirfft2`.  Two uncompressed all_to_alls, no gather; local passes
    on the plan registry's jnp plans; the output is row-sharded like
    ``x``."""
    h, w = x.shape[-2], x.shape[-1]
    p = mesh.shape[axis]
    assert w % 2 == 0 and h % p == 0 and (w // 2) % p == 0, (x.shape, p)
    assert op.rows.shape == (w // 2, h), (op.rows.shape, x.shape)

    def body(xr, g):
        z = _swap_last2(_prfft2_local(xr, axis, compress="none",
                                      backend="jnp"))  # (..., W/(2p), H)
        with jax.named_scope("pencil.operator"):
            z = _apply_half_operator(z, g, axis)
        return _pirfft2_local(z, axis, h, w, compress="none", backend="jnp")

    spec = _rows_spec(x.ndim, axis)
    fn = shard_map_unchecked(
        body, mesh=mesh, in_specs=(spec, HalfOperator(P(axis, None), P())),
        out_specs=spec)
    return fn(x, op)


def exchange_bytes(h: int, w: int, devices: int, *, real: bool = False,
                   method: str = "none", dtype=jnp.float32,
                   transposed_output: bool = True, batch: int = 1) -> int:
    """Per-device all_to_all *payload* bytes of one :func:`pfft2` /
    :func:`prfft2` call on ``batch`` fields — exactly what the wire log
    records.
    :func:`repro.tt.trace.trace_dist` prices the (devices-1)/devices
    fraction of this that actually leaves the chip.  ``real=True`` halves
    the column count (the packed half spectrum); the per-element wire
    width derives from :func:`repro.dist.compression.wire_bytes` on a
    probe leaf so the two pricings can never drift."""
    cols = w // 2 if real else w
    legs = 1 if transposed_output else 2
    per_elem = wire_bytes(np.zeros((1,), jnp.dtype(dtype)), method)
    return legs * 2 * batch * (h // devices) * cols * per_elem


# ---------------------------------------------------------------------------
# Hierarchical two-hop transpose (multi-pod)
# ---------------------------------------------------------------------------

def pfft2_hierarchical(x: SplitComplex, mesh, pod_axis: str = "pod",
                       data_axis: str = "data", *, inverse: bool = False,
                       backend: str = "jnp") -> SplitComplex:
    """2-D pencil FFT on a (pod, data) mesh with a two-hop transpose.

    Rows are sharded over *both* axes (``P((pod, data), None)``).  Instead of
    one flat all_to_all over all pod*data devices, the pencil exchange runs
    as (1) an intra-pod all_to_all over ``data_axis`` — the cheap hop, full
    row blocks — then (2) an inter-pod all_to_all over ``pod_axis`` that
    only moves already-narrowed (W/data) pencils.  Output is the (W, H)
    transpose sharded ``P((data, pod), None)`` — the data-major tiling is
    what makes the two-hop chunk order line up with the natural column
    order, so no cross-device reshuffle is ever needed.
    """
    h, w = x.shape[-2], x.shape[-1]
    np_, nd = mesh.shape[pod_axis], mesh.shape[data_axis]
    ndev = np_ * nd
    assert h % ndev == 0 and w % ndev == 0, (x.shape, np_, nd)

    def body(re, im):
        y = _fft_last(SplitComplex(re, im), inverse=inverse, backend=backend)
        # hop 1 (intra-pod): (H/(np*nd), W) -> (H/np, W/nd); rows stay
        # natural because each pod's devices hold contiguous row blocks
        y = _a2a(y, data_axis, 1, 0)
        # hop 2 (inter-pod): (H/np, W/nd) -> (H, W/(nd*np)); peer-major
        # concat over pods is again the natural row order
        y = _a2a(y, pod_axis, 1, 0)
        y = _fft_axis(y, 0, inverse=inverse, backend=backend)
        return _swap_last2(y)                    # (W/(nd*np), H)

    out_spec = P((data_axis, pod_axis), None)
    fn = shard_map_unchecked(body, mesh=mesh,
                   in_specs=(P((pod_axis, data_axis), None),) * 2,
                   out_specs=SplitComplex(out_spec, out_spec))
    return fn(x.re, x.im)


# ---------------------------------------------------------------------------
# 3-D pencil FFT over a 2-D process grid
# ---------------------------------------------------------------------------

def pfft3(x: SplitComplex, mesh, axes=("data", "model"), *,
          inverse: bool = False, backend: str = "jnp") -> SplitComplex:
    """3-D FFT of an (X, Y, Z) array on a 2-D process grid — the pencil
    decomposition proper (the paper's future-work case).

    Input is sharded ``P(axes[0], axes[1], None)``: every device owns a
    Z-pencil.  Three local FFT passes separated by two single-axis
    all_to_alls (never a global one):

    1. FFT along Z (local);
    2. all_to_all over ``axes[1]``: trade Z for Y -> Y-pencils; FFT along Y;
    3. all_to_all over ``axes[0]``: trade Y for X -> X-pencils; FFT along X.

    Output is returned transposed to (Z, Y, X) — a local transpose of the
    final X-pencils — sharded ``P(axes[1], axes[0], None)``.
    """
    a, b = axes
    na, nb = mesh.shape[a], mesh.shape[b]
    gx, gy, gz = x.shape[-3], x.shape[-2], x.shape[-1]
    assert gx % na == 0 and gy % (na * nb) == 0 and gz % nb == 0, \
        (x.shape, na, nb)

    def body(re, im):
        z = _fft_last(SplitComplex(re, im), inverse=inverse, backend=backend)
        z = _a2a(z, b, 2, 1)                     # (X/na, Y, Z/nb)
        z = _fft_axis(z, 1, inverse=inverse, backend=backend)
        z = _a2a(z, a, 1, 0)                     # (X, Y/na, Z/nb)
        z = _fft_axis(z, 0, inverse=inverse, backend=backend)
        t = lambda q: jnp.transpose(q, (2, 1, 0))
        return SplitComplex(t(z.re), t(z.im))    # (Z/nb, Y/na, X)

    out_spec = P(b, a, None)
    fn = shard_map_unchecked(body, mesh=mesh, in_specs=(P(a, b, None),) * 2,
                   out_specs=SplitComplex(out_spec, out_spec))
    return fn(x.re, x.im)


# ---------------------------------------------------------------------------
# Distributed 1-D four-step FFT
# ---------------------------------------------------------------------------

def fourstep_split(n: int, p: int) -> tuple:
    """Pick the (h, w) four-step factorisation of ``n`` on ``p`` devices:
    start at the flattest shard-compatible shape (p, n/p) and square it up
    while the column count stays even and shardable.  Deterministic, and
    mirrored by the tests so layouts agree."""
    h, w = p, n // p
    while (w > 2 * h) and (w % 2 == 0) and ((w // 2) % p == 0):
        h, w = h * 2, w // 2
    return h, w


def _fourstep_twiddle(h: int, w: int, j2, *, inverse: bool, dtype):
    """T[k1, j2] = exp(-+ 2*pi*i * k1*j2 / n) for the local column block.

    k1*j2 < h*w = n, so the integer product is exact and the angle argument
    never loses precision to a large-phase reduction.
    """
    n = h * w
    k1 = jnp.arange(h, dtype=jnp.int32)[:, None]
    prod = (k1 * j2[None, :]).astype(jnp.float32)
    ang = (2.0 * jnp.pi / n) * prod
    sign = 1.0 if inverse else -1.0
    return SplitComplex(jnp.cos(ang).astype(dtype),
                        (sign * jnp.sin(ang)).astype(dtype))


def pfft1d(x: SplitComplex, mesh, axis: str = "data", *,
           inverse: bool = False, backend: str = "jnp") -> SplitComplex:
    """One giant 1-D FFT sharded over ``axis``: distributed Bailey four-step.

    The length-n sequence is viewed as an (h, w) matrix (row-major,
    ``fourstep_split``): column FFTs of length h, the W_n^{k1*j2} twiddle
    correction, then row FFTs of length w.  The two inter-step transposes
    are the all_to_alls.  The final four-step output transpose is *not*
    performed: the result is the (h, w) frequency matrix flattened row-major
    and row-sharded, i.e. ``out.reshape(h, w).T.ravel()`` is ``fft(x)``.
    ``inverse=True`` consumes exactly this layout and returns natural-order
    samples, so forward->inverse roundtrips bit-exactly in layout.
    """
    (n,) = x.shape
    p = mesh.shape[axis]
    assert n % p == 0, (n, p)
    h, w = fourstep_split(n, p)
    assert h % p == 0 and w % p == 0, (h, w, p)

    def fwd(re, im):
        loc = SplitComplex(re.reshape(h // p, w), im.reshape(h // p, w))
        zz = _a2a(loc, axis, 1, 0)               # (h, w/p): full columns
        zz = _fft_axis(zz, 0, inverse=False, backend=backend)
        d = jax.lax.axis_index(axis)
        j2 = d * (w // p) + jnp.arange(w // p, dtype=jnp.int32)
        t = _fourstep_twiddle(h, w, j2, inverse=False, dtype=zz.dtype)
        zz = SplitComplex(zz.re * t.re - zz.im * t.im,
                          zz.re * t.im + zz.im * t.re)
        zz = _a2a(zz, axis, 0, 1)                # (h/p, w): full rows
        zz = _fft_last(zz, inverse=False, backend=backend)
        return SplitComplex(zz.re.reshape(-1), zz.im.reshape(-1))

    def inv(re, im):
        loc = SplitComplex(re.reshape(h // p, w), im.reshape(h // p, w))
        zz = _fft_last(loc, inverse=True, backend=backend)      # 1/w scale
        zz = _a2a(zz, axis, 1, 0)                # (h, w/p)
        d = jax.lax.axis_index(axis)
        j2 = d * (w // p) + jnp.arange(w // p, dtype=jnp.int32)
        t = _fourstep_twiddle(h, w, j2, inverse=True, dtype=zz.dtype)
        zz = SplitComplex(zz.re * t.re - zz.im * t.im,
                          zz.re * t.im + zz.im * t.re)
        zz = _fft_axis(zz, 0, inverse=True, backend=backend)    # 1/h scale
        zz = _a2a(zz, axis, 0, 1)                # (h/p, w)
        return SplitComplex(zz.re.reshape(-1), zz.im.reshape(-1))

    fn = shard_map_unchecked(inv if inverse else fwd, mesh=mesh,
                   in_specs=(P(axis), P(axis)),
                   out_specs=SplitComplex(P(axis), P(axis)))
    return fn(x.re, x.im)
