"""Startup pre-warm: every configured bucket compiles before "ready".

FFTW's lesson — amortised planning only pays when a long-lived server
reuses plans across requests — applied at two levels:

1. **Plan resolution** through :func:`repro.core.plan.warm` (the shared
   "compile these plans now or degrade" path): wisdom from
   ``$REPRO_FFT_WISDOM`` has already auto-loaded tuned winners at import,
   so every bucket's (algo, backend, block_batch) is decided before the
   first request.  The ``serve.prewarm`` fault site fires per bucket
   inside ``warm`` — an injected failure degrades that bucket to its jnp
   twin instead of killing startup, integrating with the same resilience
   policy the guarded executor uses.
2. **XLA compilation**: each bucket's jitted dispatch function executes
   once on ``max_batch`` zero slots — the bucket's zero plane, made on
   the device here and kept on its state for the empty slots of every
   later batch — so no client request ever pays the compile.  A
   compile/execute failure degrades the bucket (jnp twin, recompile)
   rather than raising; if even the twin fails, the bucket is recorded as
   failed in the report and the runtime degrade path retries at first
   dispatch — startup never crashes.

:func:`compile_states` returns a :class:`PrewarmReport` with per-bucket
compile seconds and degrade reasons — the benchmark's cold-p99 comparison
reads straight off it.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import jax

from repro.core import plan as plan_lib

from .executor import BucketState, make_fn, zeros_input


@dataclasses.dataclass(frozen=True)
class PrewarmEntry:
    label: str
    backend: str                  # the backend that will actually serve
    algo: str
    block_batch: int
    max_batch: int
    tuned: bool
    degraded: bool
    reason: Optional[str]
    compile_s: float


@dataclasses.dataclass(frozen=True)
class PrewarmReport:
    entries: List[PrewarmEntry]
    wisdom_entries: int           # plans installed from $REPRO_FFT_WISDOM
    total_s: float

    @property
    def degraded(self) -> List[str]:
        return [e.label for e in self.entries if e.degraded]


def compile_states(states: Dict[str, BucketState],
                   metrics=None) -> PrewarmReport:
    """Compile every bucket's dispatch function (execute-once-on-zeros).

    Buckets whose plan resolution already degraded compile their jnp twin;
    a *compile* failure on a healthy pallas plan degrades it here, the
    same never-crash contract as :func:`repro.core.plan.warm`."""
    t_start = time.perf_counter()
    entries = []
    for label, state in states.items():
        t0 = time.perf_counter()
        x = zeros_input(state)
        try:
            state.fn = make_fn(state)
            jax.block_until_ready(state.fn(x))
        except Exception as e:      # noqa: BLE001 — degrade, never crash
            reason = f"{type(e).__name__}: {e}"
            try:
                cfg = state.cfg
                state.plan = plan_lib.get_plan(
                    cfg.shape, dtype=cfg.dtype, inverse=cfg.inverse,
                    kind=cfg.kind, backend="jnp")
                state.degraded = True
                state.reason = reason
                state.fn = make_fn(state)
                jax.block_until_ready(state.fn(x))
            except Exception as e2:  # noqa: BLE001 — still never crash
                # even the jnp twin failed to compile/execute: record the
                # bucket as failed and keep starting up — the runtime
                # degrade path retries at first dispatch
                state.degraded = True
                state.reason = (f"{reason}; jnp twin failed: "
                                f"{type(e2).__name__}: {e2}")
                state.fn = None
        compile_s = time.perf_counter() - t0
        entry = PrewarmEntry(
            label=label, backend=state.plan.backend, algo=state.plan.algo,
            block_batch=state.plan.block_batch,
            max_batch=state.cfg.max_batch, tuned=state.plan.tuned,
            degraded=state.degraded, reason=state.reason,
            compile_s=compile_s)
        entries.append(entry)
        if metrics is not None:
            metrics.annotate(label, plan_backend=state.plan.backend,
                             plan_algo=state.plan.algo,
                             block_batch=state.plan.block_batch,
                             max_batch=state.cfg.max_batch,
                             degraded=state.degraded,
                             degrade_reason=state.reason,
                             prewarm_compile_s=compile_s)
    return PrewarmReport(entries=entries,
                         wisdom_entries=plan_lib.WISDOM_AUTOLOADED,
                         total_s=time.perf_counter() - t_start)
