"""FFTW-style plan registry: resolve/tune once, apply many times.

A :class:`FFTPlan` captures a transform (shape, dtype, direction, backend)
plus the resolved execution config (algo, radix, block_batch) and exposes a
jit-friendly ``__call__``.  Plans are interned in a process-wide registry —
two requests with the same (shape, dtype, direction, backend) return the
*same object* — so auto-dispatch decisions (and autotune measurements) are
paid once per key, mirroring how the paper bakes per-size decisions
(chunking, reorder plan, twiddles) at initialisation and how FFTW separates
``plan`` from ``execute``.

``backend="jnp"`` uses the pure-JAX algorithms in :mod:`repro.core.fft1d`;
``backend="pallas"`` dispatches to the TPU kernels in
:mod:`repro.kernels.ops` (interpret-mode on CPU).  1-D shapes are ``(n,)``;
2-D shapes ``(h, w)`` cover :func:`repro.core.fft2d.fft2`, where the pallas
backend runs the GEMM-formulated fused kernel
(:mod:`repro.kernels.fft2d_gemm`, ``algo="fused"``; the previous
Stockham-stage kernel stays reachable as the explicit-algo oracle
``algo="fused_stockham"``); 3-D shapes ``(d, h, w)`` cover
:func:`repro.core.fft2d.fft3`, where the pallas backend runs the fused
pencil-in-VMEM kernel (:mod:`repro.kernels.fft3d_fused`).

GEMM-fused plans additionally carry a ``variant``: ``"plain"`` casts the
DFT operand tables straight to the working dtype, while
``"compensated"`` (the auto default for sub-fp32 dtypes) stores them as
split hi/lo pairs and accumulates in fp32 — the precision-compensated
bf16 path that halves the VMEM working set (the 1024x1024 capacity
question) without paying the full bf16 arithmetic error.

``tune=True`` runs an opt-in FFTW-style measuring autotuner: every candidate
(algo, radix, block_batch) config is timed on synthetic data and the winner
is recorded in the registry, so the measurement also happens at most once
per key.  ``prune="model"`` first ranks the candidates with the analytical
Wormhole/Tensix cost model (:func:`repro.tt.trace.predict_cost`) and only
measures the top-k — cheaper tuning, same measured winner when the model
ranks sanely (the heuristic default is always kept in the measured set).

Plans with ``kind="rfft"`` cover the real-input transforms: the key
includes the kind, so ``rfft``/``irfft``/``rfft2``/``irfft2`` resolve their
inner complex algo once per shape instead of re-deriving it per call.
Real-input plans have a kernel path too: 2-D rfft keys on
``backend="pallas"`` resolve to the fused real-input kernel
(:mod:`repro.kernels.rfft2d_fused`, ``algo="fused"``), 1-D rfft keys run
their inner complex transform on the 1-D kernels, and shapes with no
kernel path demote to jnp with the reason recorded on
``FFTPlan.demote_reason``.  rfft-kind autotuning measures the
(algo, backend, block_batch) grid — the jnp schedule is always a
candidate, so tuning can cross backends.

Tuned winners persist across processes FFTW-"wisdom" style:
:func:`save_wisdom` / :func:`load_wisdom` round-trip the registry's tuned
(algo, radix, block_batch, backend, variant) entries as versioned,
key-hashed JSON.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import time
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .complexmath import SplitComplex
from . import fft1d
from .fft1d import KERNEL_INNER_ALGOS, resolve_algo


def _is_pow2(n: int) -> bool:
    return n > 0 and (n & (n - 1)) == 0


PlanKey = Tuple[Tuple[int, ...], str, bool, str, str]

_PLAN_CACHE: Dict[PlanKey, "FFTPlan"] = {}      # algo="auto" plans
_OVERRIDE_CACHE: Dict[tuple, "FFTPlan"] = {}    # (key, algo, radix) overrides
_AUTOTUNE_RUNS: Dict[tuple, int] = {}

# conv-kind plans fuse rfft -> pointwise multiply -> irfft over one padded
# FFT length; the causal/circular mode is part of the kind (and therefore
# the key), because the two modes pad — and therefore cache — different
# filter spectra at the same length
CONV_KINDS = ("conv_causal", "conv_circular")
PLAN_KINDS = ("c2c", "rfft") + CONV_KINDS


def _plan_key(shape, dtype, inverse, backend, kind="c2c") -> PlanKey:
    return (tuple(int(d) for d in shape), str(jnp.dtype(dtype)),
            bool(inverse), backend, kind)


@dataclasses.dataclass(frozen=True)
class FFTPlan:
    shape: Tuple[int, ...]            # transform shape: (n,) or (h, w)
    dtype: str = "float32"
    inverse: bool = False
    algo: str = "auto"                # resolved at construction, never "auto"
    backend: str = "jnp"              # "jnp" | "pallas"
    radix: int = 4                    # Stockham radix (4 = mixed 4/2, 2 = oracle)
    block_batch: int = 8              # pallas batch tile
    kind: str = "c2c"                 # "c2c" | "rfft" (real input/output)
    variant: str = "plain"            # GEMM kernels: "plain" | "compensated"
    tuned: bool = False
    tune_report: Optional[dict] = None   # {candidate label: us} when tuned
    demote_reason: Optional[str] = None  # why a pallas request fell to jnp

    # -- introspection -------------------------------------------------------

    @property
    def n(self) -> int:
        return self.shape[-1]

    @property
    def ndim(self) -> int:
        return len(self.shape)

    # -- construction --------------------------------------------------------

    @staticmethod
    def create(n: int, *, inverse: bool = False, algo: str = "auto",
               backend: str = "jnp", dtype=jnp.float32,
               tune: bool = False) -> "FFTPlan":
        """1-D plan through the registry (kept as the historical entry point)."""
        return get_plan((n,), dtype=dtype, inverse=inverse, algo=algo,
                        backend=backend, tune=tune)

    # -- execution -----------------------------------------------------------

    def __call__(self, x, *args) -> SplitComplex:
        """Execute through the guarded executor
        (:mod:`repro.resilience.executor`): eager kernel executions are
        integrity-checked and fall back to the jnp schedule on failure
        (repeated failures open the key's circuit breaker and demote the
        registry entry with ``demote_reason="runtime_circuit_open"``);
        traced calls — and disabled resilience — take the raw path
        unchanged.  conv-kind plans take the filter half spectrum as a
        second operand: ``plan(x, kf)``."""
        from repro.resilience import executor as _rexec
        return _rexec.execute(self, x, *args)

    def _execute(self, x, *args) -> SplitComplex:
        """The raw execution path (no guards, no fallback)."""
        if self.kind in CONV_KINDS:
            return self._call_conv(x, *args)
        assert not args, "only conv-kind plans take extra operands"
        if self.kind == "rfft":
            return self._call_rfft(x)
        assert x.shape[-self.ndim:] == self.shape, (x.shape, self.shape)
        if self.ndim == 2:
            from . import fft2d
            return fft2d._fft2_direct(x, inverse=self.inverse, algo=self.algo,
                                      backend=self.backend,
                                      block_batch=self.block_batch,
                                      variant=self.variant)
        if self.ndim == 3:
            from . import fft2d
            return fft2d._fft3_direct(x, inverse=self.inverse, algo=self.algo,
                                      backend=self.backend,
                                      block_batch=self.block_batch,
                                      variant=self.variant)
        if self.backend == "pallas":
            from repro.kernels import ops as kops
            if self.algo == "four_step":
                return kops.fft_fourstep(x, inverse=self.inverse,
                                         block_batch=self.block_batch)
            return kops.fft_stockham(x, inverse=self.inverse,
                                     radix=self.radix,
                                     block_batch=self.block_batch)
        algo = "stockham2" if (self.algo == "stockham" and self.radix == 2) \
            else self.algo
        return fft1d.fft(x, inverse=self.inverse, algo=algo)

    def _call_rfft(self, x):
        """Execute a real-input plan.  On ``backend="jnp"`` the resolved
        ``algo`` is the *inner* complex transform of the rfft/irfft axis,
        passed explicitly so the dispatch decision baked into this plan is
        never re-derived, and the 2-D column pass is a c2c transform routed
        through its own registry key (``algo="auto"``), FFTW-style plan
        composition.  On ``backend="pallas"`` 2-D plans run the fused
        real-input kernel (``algo="fused"``) and 1-D plans run their inner
        complex transform on the 1-D kernels.
        """
        if self.ndim == 1:
            kw = dict(algo=self.algo, backend=self.backend,
                      radix=self.radix, block_batch=self.block_batch)
            if self.inverse:            # input: (..., n/2+1) half spectrum
                assert x.shape[-1] == self.n // 2 + 1, (x.shape, self.shape)
                return fft1d._irfft_direct(x, self.n, **kw)
            assert x.shape[-1] == self.n, (x.shape, self.shape)
            return fft1d._rfft_direct(x, **kw)
        h, w = self.shape
        from . import fft2d
        if self.backend == "pallas" and self.algo == "fused":
            from repro.kernels import ops as kops
            if self.inverse:
                assert x.shape[-2:] == (h, w // 2 + 1), (x.shape, self.shape)
                return kops.irfft2d_fused(x, block_batch=self.block_batch)
            assert x.shape[-2:] == (h, w), (x.shape, self.shape)
            return kops.rfft2d_fused(x, block_batch=self.block_batch)
        # jnp plans run the row-column schedule with jnp passes; a pallas
        # plan with an explicit non-fused algo runs the SAME schedule with
        # kernel 1-D passes — identical to the direct rfft2()/irfft2()
        # path for the same (algo, backend) request
        col = self.algo if self.backend == "pallas" else "auto"
        if self.inverse:
            assert x.shape[-2:] == (h, w // 2 + 1), (x.shape, self.shape)
            return fft2d._irfft2_direct(x, row_algo=self.algo, col_algo=col,
                                        backend=self.backend)
        assert x.shape[-2:] == (h, w), (x.shape, self.shape)
        return fft2d._rfft2_direct(x, row_algo=self.algo, col_algo=col,
                                   backend=self.backend)

    def _call_conv(self, x, kf):
        """Execute a conv plan: circularly convolve real signals x (..., m)
        with the filter half spectra kf (..., m//2+1) over the plan's
        padded FFT length m.  ``algo="fused"`` runs the VMEM-resident
        pallas kernel (:mod:`repro.kernels.fftconv_fused`) — spectrum
        never touches HBM; ``algo="unfused"`` is the registry-composed
        rfft -> mul -> irfft baseline (the demotion and runtime-fallback
        target).  Causal padding/truncation happens upstream in
        :func:`repro.core.fftconv.fft_conv`."""
        m = self.n
        assert x.shape[-1] == m, (x.shape, self.shape)
        if self.algo == "fused":
            from repro.kernels import ops as kops
            return kops.fftconv_fused(x, kf, block_batch=self.block_batch)
        from . import complexmath as cm
        xf = fft1d.rfft(x, backend=self.backend)
        return fft1d.irfft(cm.mul(xf, kf), m, backend=self.backend)


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

def get_plan(shape, *, dtype=jnp.float32, inverse: bool = False,
             algo: str = "auto", backend: str = "jnp", kind: str = "c2c",
             variant: str = "auto",
             tune: bool = False, tune_batch: int = 8,
             prune: str = "none", prune_k: Optional[int] = None,
             model_arch: str = "tpu_v5e",
             measure_timeout_s: Optional[float] = "config") -> FFTPlan:
    """The registry entry point: return the interned plan for this key,
    resolving (or autotuning) it on first request.

    Keys are (shape, dtype, direction, backend-after-demotion, kind);
    requests with an explicit ``algo`` are interned separately under
    (key, algo) and never replace — or inherit — the auto-resolved plan.
    The autotuner runs at most once per cache entry; explicit-algo tuning
    measures only that algo's radix/block_batch variants.  ``tune_batch``
    sets the synthetic batch the tuner measures on — pass your workload's
    batch, since the best (algo, radix, block_batch) config is
    batch-dependent.

    ``kind="rfft"`` interns a real-input plan: ``shape`` is the *real*
    shape.  On ``backend="jnp"`` the resolved algo is the inner complex
    transform of the rfft/irfft axis (length n/2 forward, n inverse); on
    ``backend="pallas"`` 2-D shapes resolve to the fused real-input kernel
    (``algo="fused"``) and 1-D shapes run the inner transform on the 1-D
    kernels.  Shapes with no kernel path demote to jnp and record why in
    ``FFTPlan.demote_reason``.

    ``prune="model"`` makes the autotuner rank candidates with the
    :mod:`repro.tt.trace` cost model on ``model_arch`` and measure only the
    ``prune_k`` most promising (default: half, min 2 — the heuristic
    default config is always measured).

    ``measure_timeout_s`` is the per-candidate measurement watchdog (one
    retry, then the candidate is excluded — a hung config cannot hang
    tuning); the default defers to the resilience config
    (``resilience.config.get("measure_timeout_s")``), ``None`` disables it.

    ``variant`` selects the GEMM kernels' precision path: ``"auto"``
    resolves to ``"compensated"`` for sub-fp32 GEMM-fused plans (split
    hi/lo operand tables + fp32 accumulation) and ``"plain"`` otherwise;
    an explicit variant is interned separately like an explicit algo.
    """
    shape = tuple(int(d) for d in shape)
    assert len(shape) in (1, 2, 3), f"1-D/2-D/3-D plans only, got {shape}"
    assert kind in PLAN_KINDS, f"kind must be one of {PLAN_KINDS}, got {kind}"
    assert prune in ("none", "model"), prune
    assert variant in ("auto", "plain", "compensated"), variant
    if kind == "rfft" and len(shape) == 3:
        raise ValueError("rfft plans are 1-D or 2-D; 3-D real transforms "
                         "compose rfft2 with a c2c depth pass")
    if kind in CONV_KINDS:
        if len(shape) != 1:
            raise ValueError("conv plans are 1-D (keyed on the padded FFT "
                             f"length), got {shape}")
        if inverse:
            raise ValueError("conv plans have no inverse direction (the "
                             "irfft is fused inside the plan)")
    # the kernels need power-of-two tile dims of at least 2 (a unit dim
    # would underflow the tile asserts) — anything else demotes to jnp
    kernel_ok = all(_is_pow2(d) and d >= 2 for d in shape)
    radix = 4
    fixed_radix = False
    demote = None

    if kind in CONV_KINDS:
        m = shape[0]
        if backend == "pallas" and not (_is_pow2(m) and m >= 4):
            demote = ("fused conv kernel needs a power-of-two FFT length "
                      f">= 4, got {m}")
            if algo == "fused":
                algo = "auto"         # fused demotes with its backend
            backend = "jnp"
        if algo == "auto":
            resolved = "fused" if backend == "pallas" else "unfused"
        else:
            resolved = algo
        if backend == "jnp" and resolved == "fused":
            raise ValueError('algo="fused" requires backend="pallas" (the '
                             'fused conv kernel has no jnp equivalent)')
        if resolved not in ("fused", "unfused"):
            raise ValueError(f'algo={resolved!r} is not a conv plan algo; '
                             'use "fused", "unfused" or "auto"')
        block_batch = 1 if resolved == "fused" else 8
    elif kind == "rfft":
        n = shape[-1]
        if n % 2:
            raise ValueError(f"rfft plans need an even last dim, "
                             f"got {shape}")
        inner = n if inverse else n // 2
        if len(shape) == 1:
            # 1-D: the pack/untangle stays jnp; the inner complex
            # transform runs on the 1-D kernels when one exists
            resolved = resolve_algo(inner) if algo == "auto" else algo
            if backend == "pallas" and (
                    resolved not in KERNEL_INNER_ALGOS
                    or not (_is_pow2(inner) and inner >= 2)):
                demote = (f"inner algo {resolved!r} at inner length "
                          f"{inner} has no kernel path")
                backend = "jnp"
            block_batch = 8
        else:
            # 2-D: the fused real-input kernel (rfft2d_fused)
            if backend == "pallas" and not kernel_ok:
                demote = ("fused rfft kernel needs power-of-two dims "
                          f">= 2, got {shape}")
                if algo == "fused":
                    algo = "auto"
                backend = "jnp"
            if algo == "auto":
                resolved = "fused" if backend == "pallas" \
                    else resolve_algo(inner)
            else:
                resolved = algo
            if backend == "pallas" and resolved != "fused" and (
                    resolved not in KERNEL_INNER_ALGOS
                    or not (_is_pow2(inner) and inner >= 2)):
                # an explicit non-fused algo runs the row-column schedule
                # with kernel 1-D passes (same as the direct rfft2 path);
                # algos outside _fft_inner's kernel set demote visibly
                demote = (f"explicit inner algo {resolved!r} at inner "
                          f"length {inner} has no kernel path")
                backend = "jnp"
            if backend == "jnp" and resolved == "fused":
                raise ValueError('algo="fused" requires backend="pallas" '
                                 '(the fused rfft kernel has no jnp '
                                 'equivalent)')
            block_batch = 1 if resolved == "fused" else 8
    elif len(shape) == 1:
        resolved = resolve_algo(shape[0]) if algo == "auto" else algo
        if resolved == "stockham2":   # radix-2 oracle: a stockham radix config
            resolved, radix, fixed_radix = "stockham", 2, True
        if backend == "pallas" and (resolved in ("naive", "bluestein")
                                    or not kernel_ok):
            demote = f"algo {resolved!r} at {shape} has no kernel path"
            backend = "jnp"
        block_batch = 8
    else:
        fused_algos = ("fused", "fused_stockham") if len(shape) == 2 \
            else ("fused",)           # no 3-D Stockham oracle
        if backend == "pallas" and not kernel_ok:
            demote = ("kernels need power-of-two tile dims >= 2, "
                      f"got {shape}")
            if algo in fused_algos:
                algo = "auto"         # fused demotes with its backend
            backend = "jnp"
        if algo == "auto":
            resolved = "fused" if backend == "pallas" else "row_col"
        else:
            resolved = algo
        if backend == "jnp" and resolved in fused_algos:
            raise ValueError(f'algo={resolved!r} requires backend="pallas" '
                             '(the fused kernels have no jnp equivalent)')
        if resolved not in fused_algos + ("row_col",):
            raise ValueError(
                f'algo={resolved!r} is not a {len(shape)}-D plan algo; '
                f'use one of {fused_algos + ("row_col",)} or "auto"')
        # fused: one (h, w) image / (d, h, w) brick per VMEM tile; row_col:
        # the 1-D kernel's row-tile default (what the direct path executes)
        block_batch = 1 if resolved in fused_algos else 8

    # the GEMM kernels (complex fused 2-D/3-D) are the only variant-aware
    # paths; "auto" picks the compensated tables for sub-fp32 dtypes so a
    # bf16 plan gets the split-twiddle precision fix by default
    gemm_path = (kind == "c2c" and len(shape) >= 2 and backend == "pallas"
                 and resolved == "fused")
    if variant == "auto":
        res_variant = "compensated" if gemm_path and \
            jnp.dtype(dtype).itemsize < 4 else "plain"
    elif variant == "compensated" and not gemm_path:
        if demote is None:
            raise ValueError('variant="compensated" requires a GEMM fused '
                             'plan (2-D/3-D c2c, backend="pallas", '
                             'algo="fused")')
        res_variant = "plain"         # the kernel path demoted away
    else:
        res_variant = variant

    key = _plan_key(shape, dtype, inverse, backend, kind)
    explicit = algo != "auto" or variant != "auto"
    cache_key = key if not explicit else key + (resolved, radix, res_variant)
    cache = _PLAN_CACHE if not explicit else _OVERRIDE_CACHE
    plan = cache.get(cache_key)
    if plan is None:
        plan = FFTPlan(shape=shape, dtype=key[1], inverse=inverse,
                       algo=resolved, radix=radix, backend=backend,
                       block_batch=block_batch, kind=kind,
                       variant=res_variant, demote_reason=demote)
        cache[cache_key] = plan
    if tune and not plan.tuned:
        plan = _autotune(cache_key, plan, batch=tune_batch,
                         fixed_algo=algo != "auto", fixed_radix=fixed_radix,
                         fixed_variant=variant != "auto",
                         prune=prune, prune_k=prune_k, model_arch=model_arch,
                         measure_timeout_s=measure_timeout_s)
        cache[cache_key] = plan
    return plan


def clear_plan_cache() -> None:
    _PLAN_CACHE.clear()
    _OVERRIDE_CACHE.clear()
    _AUTOTUNE_RUNS.clear()
    from . import fftconv as _fftconv   # deferred: fftconv imports plan
    _fftconv.clear_spectrum_cache()     # per-plan filter spectra key on plans


# -- runtime demotion (driven by the resilience circuit breaker) ------------

def _runtime_demote(key: PlanKey, reason: str = "runtime_circuit_open"):
    """Swap the auto registry entry for ``key`` (a pallas key) with its jnp
    schedule, carrying a registry-visible ``demote_reason``.  Anyone calling
    :func:`get_plan` for this key now receives the demoted plan; holders of
    the old object still route through the same circuit breaker.  Returns
    the entry that was displaced (None if the key was never interned)."""
    shape, dtype, inverse, _backend, kind = key
    orig = _PLAN_CACHE.get(key)
    twin = get_plan(shape, dtype=dtype, inverse=inverse, kind=kind,
                    backend="jnp")
    _PLAN_CACHE[key] = dataclasses.replace(twin, demote_reason=reason)
    return orig


def _runtime_restore(key: PlanKey, plan: "FFTPlan") -> None:
    """Undo :func:`_runtime_demote`: re-promote the healthy plan."""
    if plan is None:
        _PLAN_CACHE.pop(key, None)
    else:
        _PLAN_CACHE[key] = plan


def plan_cache_size() -> int:
    return len(_PLAN_CACHE)


# -- bulk pre-warm -----------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class WarmResult:
    """One key's outcome from :func:`warm`: the plan that will serve it
    (possibly the jnp twin), whether resolution degraded, and why."""
    plan: "FFTPlan"
    requested_backend: str
    degraded: bool = False
    reason: Optional[str] = None


def warm(keys, *, backend: str = "pallas", tune: bool = False,
         tune_batch: int = 8, fault_site: Optional[str] = "serve.prewarm",
         on_error: str = "degrade"):
    """Bulk-resolve (and optionally tune) N plan keys in one call — the
    single "compile these plans now or degrade" path shared by the serving
    pre-warm (:mod:`repro.serve.spectral.prewarm`) and
    :class:`repro.serve.engine.Engine`.

    ``keys`` is an iterable of shape tuples or dicts
    ``{"shape": (h, w), "dtype": ..., "kind": "c2c"|"rfft",
    "inverse": bool, "backend": ...}`` (dict fields beyond ``shape`` are
    optional; a per-key ``backend`` overrides the call-wide one).  Each key
    is consulted at ``fault_site`` (:func:`repro.resilience.faults.check`,
    tagged ``kind/shape``) before resolution, so injected pre-warm faults
    exercise the degrade path deterministically.

    A key whose resolution raises — kernel compile failure, injected
    fault — never takes the others down: with ``on_error="degrade"``
    (default) it falls back to the always-available jnp schedule and the
    :class:`WarmResult` records ``degraded=True`` plus the reason;
    ``on_error="raise"`` propagates instead.  Results come back in input
    order.
    """
    assert on_error in ("degrade", "raise"), on_error
    from repro.resilience import faults as _faults
    out = []
    for spec in keys:
        if not isinstance(spec, dict):
            spec = {"shape": spec}
        shape = tuple(int(d) for d in spec["shape"])
        kw = dict(dtype=spec.get("dtype", jnp.float32),
                  inverse=bool(spec.get("inverse", False)),
                  kind=spec.get("kind", "c2c"))
        bk = spec.get("backend", backend)
        tag = f"{kw['kind']}/{'x'.join(map(str, shape))}"
        try:
            if fault_site:
                _faults.check(fault_site, tag=tag)
            plan = get_plan(shape, backend=bk, tune=tune,
                            tune_batch=spec.get("tune_batch", tune_batch),
                            **kw)
            out.append(WarmResult(plan=plan, requested_backend=bk))
        except Exception as e:      # noqa: BLE001 — degrade, never crash
            if on_error == "raise":
                raise
            plan = get_plan(shape, backend="jnp", **kw)
            out.append(WarmResult(plan=plan, requested_backend=bk,
                                  degraded=True,
                                  reason=f"{type(e).__name__}: {e}"))
    return out


def autotune_count(shape, *, dtype=jnp.float32, inverse: bool = False,
                   backend: str = "jnp", kind: str = "c2c") -> int:
    """How many times the measuring autotuner ran for this key, counting
    both the auto plan and any explicit-algo override tunes under it.
    ``backend`` is the post-demotion backend (a pallas request that fell
    back to jnp is counted under "jnp")."""
    base = _plan_key(shape, dtype, inverse, backend, kind)
    return sum(v for k, v in _AUTOTUNE_RUNS.items() if k[:5] == base)


# ---------------------------------------------------------------------------
# Wisdom (FFTW-style persisted plans)
# ---------------------------------------------------------------------------

# v3: entries carry the tuned *variant* (GEMM-fused keys autotune over
# the plain/compensated precision variants since the GEMM core landed);
# a v2 file has no variant field, so loading one would silently install
# bf16 GEMM winners with the wrong (plain) tables — the version guard
# rejects v2 outright, like v2 rejected the backend-less v1 files (which
# were written when rfft keys were hard-pinned to backend="jnp").
WISDOM_VERSION = 3


def _wisdom_key_str(key: PlanKey) -> str:
    shape, dtype, inverse, backend, kind = key
    return (f"shape={'x'.join(map(str, shape))};dtype={dtype};"
            f"inverse={int(inverse)};backend={backend};kind={kind}")


def _wisdom_key_parse(s: str) -> PlanKey:
    parts = dict(p.split("=", 1) for p in s.split(";"))
    return (tuple(int(d) for d in parts["shape"].split("x")), parts["dtype"],
            bool(int(parts["inverse"])), parts["backend"], parts["kind"])


def _wisdom_hash(key_str: str, algo, radix, block_batch, backend,
                 variant) -> str:
    """Guard hash over the version, the key AND the tuned values, so a
    stale or hand-edited entry (wrong algo for the shape, typo'd radix,
    swapped backend or precision variant) cannot install a bogus tuned
    plan."""
    payload = (f"v{WISDOM_VERSION}:{key_str}:{algo}:{radix}:{block_batch}"
               f":{backend}:{variant}")
    return hashlib.sha256(payload.encode()).hexdigest()[:16]


def save_wisdom(path: str) -> int:
    """Persist every *tuned* auto-keyed plan to ``path`` as JSON, FFTW
    "wisdom" style.  Each entry carries a hash of its (version, key) so a
    stale or hand-edited file cannot silently poison the registry.
    Returns the number of entries written.

    The write is **atomic**: the payload lands in a same-directory temp
    file that is ``os.replace``-d over ``path``, so a crash mid-write (or
    a concurrent writer losing the race) can never leave a torn wisdom
    file — readers see either the old complete file or the new one.  A
    crash leaves only a stale ``.tmp.<pid>`` sibling behind.
    """
    entries = []
    for key, plan in sorted(_PLAN_CACHE.items(), key=lambda kv: repr(kv[0])):
        if not plan.tuned:
            continue
        ks = _wisdom_key_str(key)
        entries.append({
            "key": ks,
            "key_hash": _wisdom_hash(ks, plan.algo, plan.radix,
                                     plan.block_batch, plan.backend,
                                     plan.variant),
            "algo": plan.algo, "radix": plan.radix,
            "block_batch": plan.block_batch,
            # the *tuned* backend: a pallas key's winner may be the jnp
            # schedule (and the key records the requested backend)
            "backend": plan.backend,
            # the tuned precision variant (GEMM-fused keys; "plain"
            # everywhere else)
            "variant": plan.variant,
            "tune_report": plan.tune_report,
        })
    payload = json.dumps({"version": WISDOM_VERSION, "entries": entries},
                         indent=2) + "\n"
    tmp = f"{path}.tmp.{os.getpid()}"
    from repro.resilience import faults as _faults
    with open(tmp, "w") as fh:
        fh.write(payload[:len(payload) // 2])
        # crash-simulation point: a "wisdom.save" error fault aborts here,
        # after a partial write but before the atomic rename — exactly the
        # torn state the temp-file protocol exists to keep out of ``path``
        _faults.check("wisdom.save", tag=path)
        fh.write(payload[len(payload) // 2:])
        fh.flush()
        os.fsync(fh.fileno())
    os.replace(tmp, path)
    return len(entries)


def load_wisdom(path: str, *, strict: bool = False) -> int:
    """Load wisdom saved by :func:`save_wisdom` into the registry.

    Version-mismatched files and hash-mismatched entries are skipped
    (raised with ``strict=True``); an in-process plan that is *already
    tuned* is never overwritten — live measurements outrank stored ones.
    Loaded plans arrive ``tuned=True``, so a later ``tune=True`` request
    for the same key skips the measuring autotuner entirely.  Returns the
    number of entries installed.
    """
    with open(path) as fh:
        data = json.load(fh)
    if data.get("version") != WISDOM_VERSION:
        if strict:
            raise ValueError(f"wisdom version {data.get('version')!r} != "
                             f"{WISDOM_VERSION} in {path}")
        return 0
    loaded = 0
    for e in data.get("entries", ()):
        try:
            ks = e["key"]
            algo = e["algo"]
            radix = int(e["radix"])
            block_batch = int(e["block_batch"])
            backend = e["backend"]
            variant = e["variant"]
            if _wisdom_hash(ks, algo, radix, block_batch, backend,
                            variant) != e["key_hash"]:
                raise ValueError(f"wisdom key-hash mismatch for {ks!r}")
            key = _wisdom_key_parse(ks)
        except (KeyError, ValueError, TypeError) as ex:
            if strict:
                raise ValueError(f"malformed wisdom entry {e!r}: hash or "
                                 f"field error ({ex})") from ex
            continue
        live = _PLAN_CACHE.get(key)
        if live is not None and live.tuned:
            continue
        report = dict(e.get("tune_report") or {})
        report.setdefault("winner", "wisdom")
        report["source"] = "wisdom"
        _PLAN_CACHE[key] = FFTPlan(
            shape=key[0], dtype=key[1], inverse=key[2], backend=backend,
            kind=key[4], algo=algo, radix=radix,
            block_batch=block_batch, variant=variant,
            tuned=True, tune_report=report)
        loaded += 1
    return loaded


WISDOM_ENV = "REPRO_FFT_WISDOM"


_WISDOM_WARNED = False


def _warn_wisdom_once(msg: str) -> None:
    """One-shot observability for a bad ``$REPRO_FFT_WISDOM`` file: warn
    exactly once per process (imports can re-enter) and never raise."""
    global _WISDOM_WARNED
    if _WISDOM_WARNED:
        return
    _WISDOM_WARNED = True
    import warnings
    warnings.warn(f"{WISDOM_ENV}: {msg}; starting with a cold plan registry",
                  RuntimeWarning, stacklevel=3)


def _autoload_wisdom() -> int:
    """Load wisdom from ``$REPRO_FFT_WISDOM`` at import, FFTW style.

    Best-effort by design: an unset/empty variable is a no-op and a
    missing or corrupt file must never break ``import repro`` — bad
    entries are already skipped non-strictly by :func:`load_wisdom`.  But
    best-effort is not *silent*: an unreadable, non-JSON, wrong-shape or
    version-mismatched file emits a one-shot :class:`RuntimeWarning`
    naming the path and the error class, so a corrupted wisdom deployment
    is observable instead of just mysteriously slow.  Returns the number
    of entries installed (kept in ``WISDOM_AUTOLOADED`` for
    introspection).
    """
    path = os.environ.get(WISDOM_ENV, "").strip()
    if not path:
        return 0
    try:
        loaded = load_wisdom(path)
    except (OSError, ValueError, TypeError, AttributeError, KeyError,
            json.JSONDecodeError) as e:
        # unreadable, not JSON, or JSON of the wrong shape entirely
        _warn_wisdom_once(f"failed to load wisdom from {path!r}: "
                          f"{type(e).__name__}: {e}")
        return 0
    if loaded == 0:
        # loaded-but-empty is legitimate (a fresh save with no tuned
        # plans); a version mismatch is not — name it
        try:
            with open(path) as fh:
                version = json.load(fh).get("version")
        except Exception:  # noqa: BLE001 — diagnosis only, already loaded=0
            version = WISDOM_VERSION
        if version != WISDOM_VERSION:
            _warn_wisdom_once(f"wisdom file {path!r} has version "
                              f"{version!r}, expected {WISDOM_VERSION} "
                              "(all entries skipped)")
    return loaded


WISDOM_AUTOLOADED = _autoload_wisdom()


# ---------------------------------------------------------------------------
# Autotuner
# ---------------------------------------------------------------------------

class CandidateTimeout(RuntimeError):
    """An autotune candidate measurement exceeded the watchdog timeout."""


def _watchdog_call(work, timeout_s: Optional[float]):
    """Run ``work()`` with a timeout: the call executes on a daemon thread
    and :class:`CandidateTimeout` is raised if it does not return in time
    (the stuck thread is abandoned — a daemon can never block exit)."""
    if timeout_s is None:
        return work()
    import threading
    out, err = [], []

    def runner():
        try:
            out.append(work())
        except BaseException as e:  # noqa: BLE001 — reraised on the caller
            err.append(e)

    th = threading.Thread(target=runner, daemon=True,
                          name="repro-autotune-measure")
    th.start()
    th.join(timeout_s)
    if th.is_alive():
        raise CandidateTimeout(f"measurement exceeded {timeout_s:g}s")
    if err:
        raise err[0]
    return out[0]


def _time_candidates(plans, x: SplitComplex, *, warmup: int = 1,
                     iters: int = 5, labels=None,
                     timeout_s: Optional[float] = None, extra=()):
    """Best-of-iters wall time (us) per candidate, measured round-robin so
    machine-load drift hits every candidate equally instead of whichever
    happened to run during a busy stretch.

    Every measurement runs under a per-candidate watchdog (``timeout_s``,
    None = off): a candidate that hangs gets ONE retry during warmup and is
    then excluded (time = +inf) instead of hanging the whole tuning run —
    one bad config must never cost the registry its autotuner.  Returns
    ``(times_us, timed_out_labels)``.
    """
    from repro.resilience import faults as _faults
    labels = labels if labels is not None else [str(i) for i in
                                                range(len(plans))]
    fns = [jax.jit(lambda q, p=p: p(q, *extra)) for p in plans]
    best = [float("inf")] * len(fns)
    dead = [False] * len(fns)
    timed_out = []

    def measure(i):
        def work():
            _faults.check("autotune.measure", tag=labels[i])
            t0 = time.perf_counter()
            jax.block_until_ready(fns[i](x))
            return time.perf_counter() - t0
        return _watchdog_call(work, timeout_s)

    for i in range(len(fns)):
        for attempt in range(1 + warmup):        # warmup + one retry
            try:
                measure(i)
                break
            except CandidateTimeout:
                if attempt == warmup:            # retries exhausted
                    dead[i] = True
                    timed_out.append(labels[i])
    for _ in range(iters):
        for i in range(len(fns)):
            if dead[i]:
                continue
            try:
                best[i] = min(best[i], measure(i))
            except CandidateTimeout:
                dead[i] = True
                best[i] = float("inf")           # a hanger can never win
                if labels[i] not in timed_out:
                    timed_out.append(labels[i])
    return [b * 1e6 for b in best], timed_out


def _candidates(plan: FFTPlan, *, fixed_algo: bool = False,
                fixed_radix: bool = False, fixed_variant: bool = False,
                batch: int = 8):
    """(label, plan) candidate configs for this key — the (algo, radix,
    block_batch) grid, kept small so measuring stays cheap.  The heuristic
    default is always candidate 0, so tuning can never pick a config that
    measured worse than what the registry would have used anyway.  With
    ``fixed_algo`` (caller requested a specific algo) only that algo's
    radix/block_batch variants are measured.  block_batch candidates are
    clamped to ``batch`` — padding the measured batch up to a larger tile
    would time a strictly larger workload."""
    base = dataclasses.replace
    out = [("default", plan)]
    if plan.kind in CONV_KINDS:
        if plan.backend != "pallas":
            # unfused jnp conv composes rfft/irfft keys that tune
            # independently; nothing plan-level to vary here
            return out
        for bb in sorted({min(b, batch) for b in (1, 2)}):
            out.append((f"fused/bb{bb}",
                        base(plan, algo="fused", block_batch=bb)))
        # the registry-composed unfused path as the cross-backend baseline
        out.append(("unfused/jnp", base(plan, backend="jnp", algo="unfused",
                                        block_batch=8)))
        if fixed_algo:
            out = [(lbl, c) for lbl, c in out if c.algo == plan.algo]
        seen, uniq = set(), []
        for lbl, c in out:
            cfg = (c.algo, c.radix, c.block_batch, c.backend)
            if cfg not in seen:
                seen.add(cfg)
                uniq.append((lbl, c))
        return uniq
    if plan.kind == "rfft":
        if plan.backend != "pallas":
            # jnp rfft wraps an inner c2c transform whose own key is tuned
            # independently; nothing plan-level to vary here
            return out
        # pallas rfft keys tune over (algo, backend, block_batch): the
        # kernel variants plus the jnp schedule as the cross-backend
        # baseline — tuning may conclude the kernel does not pay here
        inner = plan.n if plan.inverse else plan.n // 2
        if plan.ndim == 2:
            for bb in sorted({min(b, batch) for b in (1, 2)}):
                out.append((f"fused/bb{bb}",
                            base(plan, algo="fused", block_batch=bb)))
        else:
            for bb in sorted({min(b, batch) for b in (4, 8, 16)}):
                out.append((f"stockham/r4/bb{bb}",
                            base(plan, algo="stockham", radix=4,
                                 block_batch=bb)))
            bb4s = min(4, batch)
            out.append((f"four_step/bb{bb4s}",
                        base(plan, algo="four_step", block_batch=bb4s)))
        out.append(("jnp", base(plan, backend="jnp",
                                algo=resolve_algo(inner), block_batch=8)))
        if fixed_algo:
            out = [(lbl, c) for lbl, c in out if c.algo == plan.algo]
        seen, uniq = set(), []
        for lbl, c in out:
            cfg = (c.algo, c.radix, c.block_batch, c.backend)
            if cfg not in seen:
                seen.add(cfg)
                uniq.append((lbl, c))
        return uniq
    if plan.ndim == 1:
        n = plan.n
        if not _is_pow2(n):
            return out                       # naive/bluestein: nothing to tune
        if plan.backend == "pallas":
            for bb in sorted({min(b, batch) for b in (4, 8, 16)}):
                out.append((f"stockham/r4/bb{bb}",
                            base(plan, algo="stockham", radix=4,
                                 block_batch=bb)))
            bb2 = min(8, batch)
            out.append((f"stockham/r2/bb{bb2}",
                        base(plan, algo="stockham", radix=2,
                             block_batch=bb2)))
            bb4s = min(4, batch)
            out.append((f"four_step/bb{bb4s}",
                        base(plan, algo="four_step", block_batch=bb4s)))
        else:
            out.append(("stockham/r4", base(plan, algo="stockham", radix=4)))
            out.append(("stockham/r2", base(plan, algo="stockham", radix=2)))
            out.append(("four_step", base(plan, algo="four_step")))
            if n <= 2048:
                out.append(("naive", base(plan, algo="naive")))
    else:
        if plan.backend == "pallas":
            for bb in sorted({min(b, batch) for b in (1, 2)}):
                out.append((f"fused/bb{bb}",
                            base(plan, algo="fused", block_batch=bb)))
            if jnp.dtype(plan.dtype).itemsize < 4 and not fixed_variant:
                # sub-fp32 GEMM keys also measure the *other* precision
                # variant: compensated pays 2x table flops for ~2x less
                # error, and which side wins is a measurement question
                other = "plain" if plan.variant == "compensated" \
                    else "compensated"
                out.append((f"fused/{other}/bb1",
                            base(plan, algo="fused", block_batch=1,
                                 variant=other)))
            if plan.ndim == 2:
                out.append(("fused_stockham/bb1",
                            base(plan, algo="fused_stockham", block_batch=1,
                                 variant="plain")))
            out.append(("row_col", base(plan, algo="row_col",
                                        variant="plain")))
        else:
            out.append(("row_col", base(plan, algo="row_col")))
    if fixed_algo:
        out = [(lbl, c) for lbl, c in out if c.algo == plan.algo]
    if fixed_radix:                   # e.g. the "stockham2" radix-2 oracle
        out = [(lbl, c) for lbl, c in out if c.radix == plan.radix]
    seen, uniq = set(), []
    for lbl, c in out:                # drop configs identical to the default
        cfg = (c.algo, c.radix, c.block_batch, c.variant)
        if cfg not in seen:
            seen.add(cfg)
            uniq.append((lbl, c))
    return uniq


def _model_prune(cands, *, batch: int, prune_k: Optional[int],
                 model_arch: str):
    """Rank candidates with the analytic cost model and keep the top-k.

    The heuristic default (candidate 0) is always kept, so pruning can
    only *add* model-favoured configs to the measured set, never remove
    the config the registry would have used untuned.  Candidates on a
    *different backend* than the default are also always kept: the model
    is an intra-backend ranker, and the cross-backend wall-clock question
    (interpret-mode overhead vs XLA batch amortisation) is exactly what
    it cannot see — pruning the jnp schedule from an rfft pallas key
    would install a measurably slower winner at small sizes.  Candidates
    whose working set busts the arch's SRAM budget rank last
    (predict_cost is +inf for them).  Returns (kept, pruned_labels).
    """
    if len(cands) <= 2:
        return cands, []
    from repro.tt.trace import predict_cost
    k = prune_k if prune_k is not None else max(2, (len(cands) + 1) // 2)
    k = max(2, min(k, len(cands)))
    if k >= len(cands):
        return cands, []
    base_backend = cands[0][1].backend
    forced = [i for i in range(1, len(cands))
              if cands[i][1].backend != base_backend]
    costs = [predict_cost(c, arch=model_arch, batch=batch)
             for _, c in cands]
    rest = sorted((i for i in range(1, len(cands)) if i not in forced),
                  key=costs.__getitem__)
    keep_idx = sorted(set([0] + forced + rest[:max(0, k - 1 - len(forced))]))
    kept = [cands[i] for i in keep_idx]
    pruned = [cands[i][0] for i in range(len(cands)) if i not in keep_idx]
    return kept, pruned


def _autotune(key, plan: FFTPlan, *, batch: int = 8,
              fixed_algo: bool = False, fixed_radix: bool = False,
              fixed_variant: bool = False,
              prune: str = "none", prune_k: Optional[int] = None,
              model_arch: str = "tpu_v5e",
              measure_timeout_s: Optional[float] = "config") -> FFTPlan:
    """Measure every candidate config (or, with ``prune="model"``, the
    model-ranked top-k) and return the winner (tuned=True).  Candidates
    that exceed the per-measurement watchdog are excluded (one retry
    first) and named in ``tune_report["timeouts"]``; if *every* candidate
    times out the heuristic default is kept untouched (winner
    ``"default/untimed"``)."""
    _AUTOTUNE_RUNS[key] = _AUTOTUNE_RUNS.get(key, 0) + 1
    if measure_timeout_s == "config":
        from repro.resilience import config as _rcfg
        measure_timeout_s = _rcfg.get("measure_timeout_s")
    rng = np.random.default_rng(0)
    shp = (batch,) + plan.shape
    dt = jnp.dtype(plan.dtype)
    extra = ()
    if plan.kind in CONV_KINDS:
        # real signals (batch rows of the padded length) convolved against
        # one shared synthetic filter half spectrum — the second operand
        x = jnp.asarray(rng.standard_normal(shp), dt)
        hshp = (plan.n // 2 + 1,)
        extra = (SplitComplex(jnp.asarray(rng.standard_normal(hshp), dt),
                              jnp.asarray(rng.standard_normal(hshp), dt)),)
    elif plan.kind == "rfft":
        x = jnp.asarray(rng.standard_normal(shp), dt)
        if plan.inverse:                       # half-spectrum input
            hshp = shp[:-1] + (plan.shape[-1] // 2 + 1,)
            x = SplitComplex(jnp.asarray(rng.standard_normal(hshp), dt),
                             jnp.asarray(rng.standard_normal(hshp), dt))
    else:
        x = SplitComplex(jnp.asarray(rng.standard_normal(shp), dt),
                         jnp.asarray(rng.standard_normal(shp), dt))
    cands = _candidates(plan, fixed_algo=fixed_algo, fixed_radix=fixed_radix,
                        fixed_variant=fixed_variant, batch=batch)
    n_all = len(cands)
    pruned_labels = []
    if prune == "model":
        cands, pruned_labels = _model_prune(cands, batch=batch,
                                            prune_k=prune_k,
                                            model_arch=model_arch)
    times, timed_out = _time_candidates(
        [c for _, c in cands], x, labels=[lbl for lbl, _ in cands],
        timeout_s=measure_timeout_s, extra=extra)
    report = {label: (round(us, 1) if us != float("inf") else "timeout")
              for (label, _), us in zip(cands, times)}
    report["n_candidates"] = n_all
    report["n_measured"] = len(cands)
    if pruned_labels:
        report["model_pruned"] = "|".join(pruned_labels)
    if timed_out:
        report["timeouts"] = "|".join(timed_out)
    if all(t == float("inf") for t in times):
        # every candidate hung: keep the heuristic default, but mark the
        # key tuned so the pathological measurement is not re-run per call
        report["winner"] = "default/untimed"
        return dataclasses.replace(plan, tuned=True, tune_report=report)
    best = min(range(len(cands)), key=times.__getitem__)
    report["winner"] = cands[best][0]
    return dataclasses.replace(cands[best][1], tuned=True, tune_report=report)


# ---------------------------------------------------------------------------
# Convenience constructors
# ---------------------------------------------------------------------------

def plan_fft(n: int, **kw) -> FFTPlan:
    return FFTPlan.create(n, **kw)


def plan_ifft(n: int, **kw) -> FFTPlan:
    return FFTPlan.create(n, inverse=True, **kw)


def plan_fft2(h: int, w: int, **kw) -> FFTPlan:
    return get_plan((h, w), **kw)


def plan_ifft2(h: int, w: int, **kw) -> FFTPlan:
    return get_plan((h, w), inverse=True, **kw)
