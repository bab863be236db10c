"""Async host↔device pipelining: staging → dispatch → drain.

Three stages, double-buffered through bounded queues, mirroring the
paper's decoupling of data movement from compute on the Tensix — device
dispatch never waits on host-side batch assembly:

1. **Staging** (host): pull a batch from the scheduler and ``device_put``
   each live request's own payload planes, one transfer per plane per
   request.  No host array of the batch geometry is built: empty slots
   (and the imaginary plane of a real payload in a c2c bucket) take the
   bucket's zero slot, which lives on the device.  Runs on the
   :class:`repro.data.Prefetcher` thread — the same bounded prefetch
   primitive the training data pipeline uses — with ``depth`` in-flight
   batches (2 = double buffering), so backpressure propagates from the
   device up to admission.
2. **Dispatch**: consult the ``serve.step`` fault site, then call the
   bucket's jitted program.  JAX dispatch is async, so this thread hands
   the in-flight computation straight to the drain queue.
3. **Drain** (host): ``block_until_ready``, copy the live slots' results
   back as numpy (the empty slots' outputs never leave the device), check
   in-flight deadlines, and complete each request.

Each bucket compiles exactly one XLA program, for ``max_batch`` slots: it
takes a tuple of per-image inputs, stacks them into the fixed
``(max_batch, *shape)`` batch on the device, runs the plan and returns a
tuple of per-image results.  Batch-size churn can never trigger
recompiles on the hot path, while the host link carries only the live
slots; occupancy is visible in the ``batch_occupancy`` gauge, and the
bytes moved each way in the ``h2d_bytes`` / ``d2h_bytes`` counters.  A
dispatch failure degrades the bucket to its jnp twin plan (once) and
retries, mirroring the pre-warm degrade semantics; the requests still
complete.

Each stage's work runs inside a :meth:`~.metrics.Metrics.span`: a
histogram per stage, and a host span in a profiler trace.
``serve.assemble`` gathers each request's host planes (cast to the
bucket's dtype; a padded-up request is zero-padded for its own slot
alone), ``serve.h2d`` is the ``jax.device_put`` of those planes,
``serve.dispatch`` the jitted call, ``serve.device_wait`` the
``block_until_ready`` and ``serve.copy_back`` the ``jax.device_get`` of
the live slots.  A request's ``wait`` runs from its admission to the
start of its batch's ``assemble``: its time in the scheduler.

``threaded=False`` runs the identical stage functions inline through
:meth:`PipelinedExecutor.step` — fully deterministic for the scheduler
edge-case tests (injectable clocks, fault sites) with zero thread
scheduling in the loop.
"""
from __future__ import annotations

import dataclasses
import queue
import threading
import time
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import plan as plan_lib
from repro.core.complexmath import SplitComplex
from repro.data.pipeline import Prefetcher
from repro.resilience import faults as _faults

from .scheduler import BucketConfig, Request, ShapeBucketScheduler


@dataclasses.dataclass
class BucketState:
    """A bucket plus its resolved plan and compiled dispatch function."""
    cfg: BucketConfig                  # max_batch resolved (never None)
    plan: plan_lib.FFTPlan
    requested_backend: str
    fn: Optional[Callable] = None      # jitted; built at pre-warm/first use
    zero: Optional[jax.Array] = None   # one zero input plane on the device
    degraded: bool = False
    reason: Optional[str] = None

    @property
    def label(self) -> str:
        return self.cfg.label


def derive_max_batch(cfg: BucketConfig, plan: plan_lib.FFTPlan) -> int:
    """The compiled batch size: the configured ``max_batch``, or at least
    8 rounded up to a multiple of the tuned plan's ``block_batch`` so the
    kernel's own batch tiling never pads internally."""
    if cfg.max_batch is not None:
        return cfg.max_batch
    bb = max(1, plan.block_batch)
    return ((max(8, bb) + bb - 1) // bb) * bb


def make_fn(state: BucketState) -> Callable:
    """The bucket's dispatch function: one jit per bucket, compiled for a
    tuple of ``max_batch`` per-image inputs.  It stacks them into the
    ``(max_batch, *shape)`` batch on the device, runs the plan, and
    returns a tuple of ``max_batch`` per-image results."""
    plan = state.plan

    def run(slots):
        y = plan(jax.tree.map(lambda *xs: jnp.stack(xs), *slots))
        return tuple(jax.tree.map(lambda a: a[i], y)
                     for i in range(len(slots)))

    return jax.jit(run)


def _input_is_complex(cfg: BucketConfig) -> bool:
    return cfg.kind == "c2c" or (cfg.kind == "rfft" and cfg.inverse)


def _input_shape(cfg: BucketConfig) -> tuple:
    """One request's input plane: the bucket's shape, or the
    ``(..., w/2+1)`` half spectrum of an inverse rfft bucket."""
    if cfg.kind == "rfft" and cfg.inverse:
        return cfg.shape[:-1] + (cfg.shape[-1] // 2 + 1,)
    return cfg.shape


def _slot(cfg: BucketConfig, planes):
    return SplitComplex(*planes) if _input_is_complex(cfg) else planes[0]


def _zero(state: BucketState) -> jax.Array:
    """The bucket's zero input plane, made on the device at first use and
    kept on its state: it fills every empty slot and is never sent from
    the host."""
    if state.zero is None:
        state.zero = jnp.zeros(_input_shape(state.cfg),
                               jnp.dtype(state.cfg.dtype))
    return state.zero


def zeros_input(state: BucketState) -> tuple:
    """``max_batch`` zero slots, the bucket's compiled signature (pre-warm
    and compile-cache warm-up)."""
    return (_slot(state.cfg, [_zero(state)] * 2),) * state.cfg.max_batch


def _payload_planes(req: Request) -> List[np.ndarray]:
    """The host-side planes of a request payload: [re, im] for complex
    inputs, [x] for real ones."""
    p = req.payload
    if isinstance(p, SplitComplex):
        return [np.asarray(p.re), np.asarray(p.im)]
    arr = np.asarray(p)
    if np.iscomplexobj(arr):
        return [np.ascontiguousarray(arr.real),
                np.ascontiguousarray(arr.imag)]
    return [arr]


def _slot_planes(req: Request,
                 cfg: BucketConfig) -> List[Optional[np.ndarray]]:
    """A request's host planes in the bucket's dtype; None where the slot
    takes the zero plane (the imaginary part of a real payload in a c2c
    bucket).  A padded-up request is zero-padded here, for its own slot
    alone: it lands in the leading corner (spectral interpolation)."""
    shape, dt = _input_shape(cfg), np.dtype(cfg.dtype)
    nplanes = 2 if _input_is_complex(cfg) else 1
    out: List[Optional[np.ndarray]] = []
    for s in _payload_planes(req)[:nplanes]:
        s = s.astype(dt, copy=False)
        if s.shape != shape:
            padded = np.zeros(shape, dt)
            padded[tuple(slice(0, d) for d in s.shape)] = s
            s = padded
        out.append(s)
    return out + [None] * (nplanes - len(out))


@dataclasses.dataclass
class Assembled:
    """One staged batch: a tuple of ``max_batch`` device-resident slots
    (live requests first, then zero slots) + its requests."""
    state: BucketState
    requests: List[Request]
    x: tuple
    t_staged: float = 0.0


class PipelinedExecutor:
    """Drive scheduler batches through staging/dispatch/drain.

    ``complete(req, status, value, t_done)`` is the server's completion
    callback (status: "completed" | "timed_out_inflight"); the executor
    never touches result bookkeeping itself.
    """

    def __init__(self, states: Dict[str, BucketState],
                 scheduler: ShapeBucketScheduler, metrics, complete,
                 *, depth: int = 2, threaded: bool = True,
                 clock: Callable[[], float] = time.monotonic):
        if depth < 1:
            raise ValueError(f"pipeline depth must be >= 1, got {depth}")
        self.states = states
        self.scheduler = scheduler
        self.metrics = metrics
        self._complete = complete
        self._depth = depth
        self._threaded = threaded
        self._clock = clock
        self._stop = False
        self._work = threading.Event()    # pokes the staging loop
        self._threads: List[threading.Thread] = []
        self._prefetch: Optional[Prefetcher] = None
        self._drainq: Optional[queue.Queue] = None

    # -- stage functions (shared by threaded and inline modes) ---------------

    def _assemble(self, bucket: BucketConfig,
                  reqs: List[Request]) -> Assembled:
        lbl, n = bucket.label, len(reqs)
        state = self.states[lbl]
        B = state.cfg.max_batch
        with self.metrics.span(lbl, "assemble", n) as t_picked:
            zero = _zero(state)
            host = [_slot_planes(req, bucket) for req in reqs]
            sent = [p for planes in host for p in planes if p is not None]
        with self.metrics.span(lbl, "h2d", n):
            dev = iter(jax.device_put(sent))
            live = tuple(_slot(bucket, [zero if p is None else next(dev)
                                        for p in planes]) for planes in host)
        now = self._clock()
        for req in reqs:
            self.metrics.observe(lbl, "wait", t_picked - req.t_submit)
            self.metrics.observe(lbl, "queue", now - req.t_submit)
        self.metrics.inc(lbl, "batches")
        self.metrics.inc(lbl, "batch_items", n)
        self.metrics.inc(lbl, "batch_pad_slots", B - n)
        self.metrics.inc(lbl, "h2d_bytes", sum(p.nbytes for p in sent))
        self.metrics.sample(lbl, "batch_occupancy", n / B)
        empty = (_slot(bucket, [zero] * 2),) * (B - n)
        return Assembled(state=state, requests=reqs, x=live + empty,
                         t_staged=now)

    def _call_with_degrade(self, state: BucketState, x):
        """Dispatch on the bucket's plan; one failure degrades the bucket
        to its jnp twin (registry lookup) and retries — the runtime mirror
        of the pre-warm degrade path."""
        if state.fn is None:
            state.fn = make_fn(state)
        try:
            return state.fn(x)
        except Exception as e:      # noqa: BLE001 — resilience boundary
            if state.plan.backend == "jnp":
                raise               # nothing further to degrade to
            cfg = state.cfg
            state.plan = plan_lib.get_plan(
                cfg.shape, dtype=cfg.dtype, inverse=cfg.inverse,
                kind=cfg.kind, backend="jnp")
            state.degraded = True
            state.reason = f"{type(e).__name__}: {e}"
            state.fn = make_fn(state)
            self.metrics.annotate(state.label, degraded=True,
                                  degrade_reason=state.reason)
            return state.fn(x)

    def _dispatch(self, asm: Assembled):
        with self.metrics.span(asm.state.label, "dispatch",
                               len(asm.requests)):
            _faults.check("serve.step", tag=asm.state.label)
            return self._call_with_degrade(asm.state, asm.x)

    def _drain(self, asm: Assembled, y) -> None:
        lbl, n = asm.state.label, len(asm.requests)
        with self.metrics.span(lbl, "device_wait", n):
            jax.block_until_ready(y)
        with self.metrics.span(lbl, "copy_back", n):
            results = jax.device_get(list(y[:n]))
        self.metrics.inc(lbl, "d2h_bytes", sum(
            a.nbytes for a in jax.tree.leaves(results)))
        now = self._clock()
        fallback = asm.state.plan.backend != asm.state.requested_backend
        for req, val in zip(asm.requests, results):
            self.metrics.observe(lbl, "service", now - asm.t_staged)
            self.metrics.observe(lbl, "e2e", now - req.t_submit)
            if req.deadline is not None and now >= req.deadline:
                self.metrics.inc(lbl, "timed_out_inflight")
                self._complete(req, "timed_out_inflight", None, now)
                continue
            self.metrics.inc(lbl, "completed")
            if fallback:
                self.metrics.inc(lbl, "fallback_served")
            self._complete(req, "completed", val, now)

    # -- inline mode ---------------------------------------------------------

    def step(self) -> bool:
        """Run one batch through all three stages inline; False when the
        scheduler had nothing to hand out."""
        sel = self.scheduler.next_batch()
        if sel is None:
            return False
        asm = self._assemble(*sel)
        y = self._dispatch(asm)
        self._drain(asm, y)
        return True

    # -- threaded mode -------------------------------------------------------

    def _staged_batches(self):
        """Generator the staging Prefetcher thread consumes: blocks until
        the scheduler has work, yields assembled (device-resident)
        batches."""
        while not self._stop:
            sel = self.scheduler.next_batch()
            if sel is None:
                self._work.wait(timeout=0.005)
                self._work.clear()
                continue
            bucket, reqs = sel
            try:
                asm = self._assemble(bucket, reqs)
            except Exception as e:  # noqa: BLE001 — resilience boundary
                # assembly failed after the batch left the scheduler: the
                # requests must still terminate (exactly-one-terminal
                # guarantee), and staging must survive to serve the rest
                now = self._clock()
                for req in reqs:
                    self._complete(req, "error", e, now)
                continue
            yield asm

    def _dispatch_loop(self) -> None:
        try:
            for asm in self._prefetch:
                try:
                    y = self._dispatch(asm)
                except BaseException as e:  # noqa: BLE001 — to drain
                    y = e
                self._drainq.put((asm, y))
        except BaseException as e:  # noqa: BLE001 — staging died
            self.metrics.annotate(
                "_pipeline", staging_error=f"{type(e).__name__}: {e}")
        finally:
            # unconditional: a staging error the Prefetcher re-raises at
            # next() must still release the drain loop, or every queued
            # request orphans and shutdown() hangs on the joins
            self._drainq.put(None)

    def _drain_loop(self) -> None:
        while True:
            item = self._drainq.get()
            if item is None:
                return
            asm, y = item
            if isinstance(y, BaseException):
                # dispatch raised even after degrade: requests must still
                # terminate — nobody may wait forever on a crashed batch
                now = self._clock()
                for req in asm.requests:
                    self._complete(req, "error", y, now)
                continue
            self._drain(asm, y)

    def start(self) -> None:
        if not self._threaded or self._threads:
            return
        self._drainq = queue.Queue(maxsize=self._depth)
        self._prefetch = Prefetcher(self._staged_batches(),
                                    depth=self._depth)
        t_disp = threading.Thread(target=self._dispatch_loop, daemon=True,
                                  name="repro-serve-dispatch")
        t_drain = threading.Thread(target=self._drain_loop, daemon=True,
                                   name="repro-serve-drain")
        self._threads = [t_disp, t_drain]
        for t in self._threads:
            t.start()

    def poke(self) -> None:
        """Wake the staging loop (the server calls this on admission)."""
        self._work.set()

    def run_pending(self, outstanding: Callable[[], int],
                    timeout_s: Optional[float] = None) -> bool:
        """Drive until ``outstanding()`` hits zero.  Inline mode pumps
        :meth:`step`; threaded mode waits on the pipeline.  Returns False
        on timeout."""
        deadline = None if timeout_s is None \
            else time.monotonic() + timeout_s
        while outstanding() > 0:
            if deadline is not None and time.monotonic() > deadline:
                return False
            if self._threaded:
                self.poke()
                time.sleep(0.002)
            else:
                if not self.step() and outstanding() > 0:
                    # nothing schedulable but work still outstanding can
                    # only mean a sweep retired it concurrently — re-check
                    if self.scheduler.pending() == 0 and outstanding() > 0:
                        return False
        return True

    def shutdown(self) -> None:
        """Stop the stage threads.  The stop flag ends the staging
        generator, which ends the Prefetcher (DONE), which ends the
        dispatch loop (drain sentinel), which ends the drain loop —
        already-staged batches still flow through and complete, so a
        shutdown can never orphan admitted work."""
        self._stop = True
        self._work.set()
        for t in self._threads:
            t.join(timeout=5.0)
        self._threads = []
