"""Spectral serving: shape-bucket scheduling, pipelined execution,
pre-warm/degrade, deadlines, drain-on-shutdown, and the load generator."""
import threading
import time

import jax
import numpy as np
import pytest

from repro.core import plan as plan_lib
from repro.core.complexmath import SplitComplex
from repro.data.pipeline import Prefetcher
from repro.resilience import faults
from repro.serve.spectral import (BucketConfig, MixItem, NoBucketError,
                                  Request, ShapeBucketScheduler,
                                  SpectralServer, closed_loop, open_loop)
from repro.serve.spectral.metrics import LatencyHistogram, Metrics


def _c2c_payload(rng, shape):
    return SplitComplex(rng.standard_normal(shape).astype(np.float32),
                        rng.standard_normal(shape).astype(np.float32))


def _to_complex(sc):
    return np.asarray(sc.re) + 1j * np.asarray(sc.im)


class FakeClock:
    """Settable clock for deterministic deadline/aging tests."""

    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t


# -- plan.warm (bulk pre-warm API) -------------------------------------------


def test_warm_bulk_resolves_in_order():
    res = plan_lib.warm([(64, 64), {"shape": (64, 64), "kind": "rfft"},
                         {"shape": (64, 64), "inverse": True}])
    assert [r.plan.shape for r in res] == [(64, 64)] * 3
    assert [r.plan.kind for r in res] == ["c2c", "rfft", "c2c"]
    assert res[2].plan.inverse
    assert not any(r.degraded for r in res)


def test_warm_degrades_on_injected_fault():
    with faults.inject("serve.prewarm", "error", tag="c2c/64x64"):
        res = plan_lib.warm([(64, 64), {"shape": (64, 64), "kind": "rfft"}])
    assert res[0].degraded and "FaultInjected" in res[0].reason
    assert res[0].plan.backend == "jnp"
    assert res[0].requested_backend == "pallas"
    assert not res[1].degraded        # the fault never takes others down


def test_warm_on_error_raise_propagates():
    with faults.inject("serve.prewarm", "error"):
        with pytest.raises(faults.FaultInjected):
            plan_lib.warm([(64, 64)], on_error="raise")


# -- scheduler ---------------------------------------------------------------


def _sched(clock=None, **kw):
    buckets = [BucketConfig((64, 64), max_batch=4),
               BucketConfig((128, 128), max_batch=4)]
    return ShapeBucketScheduler(buckets, clock=clock or time.monotonic,
                                **kw)


def test_scheduler_reject_unmatched():
    s = _sched()
    with pytest.raises(NoBucketError):
        s.admit(Request(rid=0, payload=None, shape=(48, 48)))
    assert s.pending() == 0


def test_scheduler_pad_up_picks_smallest_fitting():
    s = _sched(unmatched="pad_up")
    b, padded = s.match("c2c", (48, 48))
    assert padded and b.shape == (64, 64)
    b, padded = s.match("c2c", (100, 20))
    assert padded and b.shape == (128, 128)
    # inverse transforms never pad up (no spectral-interpolation reading)
    b, padded = s.match("c2c", (48, 48), inverse=True)
    assert b is None
    # too big for every bucket
    b, padded = s.match("c2c", (256, 256))
    assert b is None


def test_scheduler_backpressure_bounded_queue():
    s = _sched(max_queue=2)
    assert s.admit(Request(rid=0, payload=None, shape=(64, 64)))
    assert s.admit(Request(rid=1, payload=None, shape=(64, 64)))
    r = Request(rid=2, payload=None, shape=(64, 64))
    assert not s.admit(r)
    assert r.bucket_label == "c2c/f/64x64"   # label known even on rejection
    assert s.pending() == 2


def test_scheduler_priority_aging_no_starvation():
    clk = FakeClock()
    s = _sched(clock=clk, aging_rate=1.0)
    s.admit(Request(rid="old-low", payload=None, shape=(64, 64),
                    priority=0.0))
    clk.t = 5.0
    s.admit(Request(rid="new-high", payload=None, shape=(128, 128),
                    priority=2.0))
    # old-low has aged 5s * 1.0 = 5.0 effective > 2.0: it dispatches first
    bucket, reqs = s.next_batch()
    assert [r.rid for r in reqs] == ["old-low"]
    bucket, reqs = s.next_batch()
    assert [r.rid for r in reqs] == ["new-high"]


def test_scheduler_deadline_sweep_retires_queued(recwarn):
    clk = FakeClock()
    retired = []
    s = _sched(clock=clk, on_timeout=retired.append)
    r = Request(rid="dies", payload=None, shape=(64, 64), deadline=1.0)
    live = Request(rid="lives", payload=None, shape=(64, 64))
    s.admit(r)
    s.admit(live)
    clk.t = 2.0
    bucket, reqs = s.next_batch()
    assert [x.rid for x in reqs] == ["lives"]
    assert [x.rid for x in retired] == ["dies"]
    assert s.pending() == 0


def test_scheduler_on_timeout_fires_outside_lock():
    """The timeout callback may re-enter the scheduler (the server's
    completion path reads queue depths): it must run with the internal
    lock released, or a non-reentrant lock deadlocks here."""
    clk = FakeClock()
    seen = []
    s = ShapeBucketScheduler(
        [BucketConfig((64, 64), max_batch=4)], clock=clk,
        on_timeout=lambda r: seen.append((r.rid, s.pending(),
                                          s.queue_depths())))
    s.admit(Request(rid="t", payload=None, shape=(64, 64), deadline=1.0))
    clk.t = 2.0
    assert s.next_batch() is None
    assert seen == [("t", 0, {"c2c/f/64x64": 0})]


def test_scheduler_threaded_admit_vs_sweep_loses_nothing():
    """Client threads hammer admit() while a consumer thread sweeps and
    dequeues: every admitted request comes out exactly once (dispatched
    or timed out) — the expiry sweep's queue rebuild must not discard a
    concurrently pushed request, and _pending must not drift."""
    timed_out = []
    s = ShapeBucketScheduler([BucketConfig((64, 64), max_batch=4)],
                             max_queue=100_000,
                             on_timeout=timed_out.append)
    n_threads, n_req = 4, 250
    admitted = [0] * n_threads

    def producer(t):
        for i in range(n_req):
            # half pre-expired: every sweep rebuilds the heap, so the
            # push-vs-rebuild window is exercised constantly
            dl = time.monotonic() if i % 2 else None
            if s.admit(Request(rid=(t, i), payload=None, shape=(64, 64),
                               deadline=dl)):
                admitted[t] += 1

    dispatched = []
    stop = threading.Event()

    def consumer():
        while not stop.is_set() or s.pending():
            sel = s.next_batch()
            if sel is not None:
                dispatched.extend(sel[1])

    threads = [threading.Thread(target=producer, args=(t,))
               for t in range(n_threads)]
    c = threading.Thread(target=consumer)
    c.start()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    stop.set()
    c.join(timeout=30)
    assert not c.is_alive()
    total = sum(admitted)
    assert len(dispatched) + len(timed_out) == total
    rids = [r.rid for r in dispatched] + [r.rid for r in timed_out]
    assert len(set(rids)) == total        # exactly once, no duplicates
    assert s.pending() == 0


# -- metrics -----------------------------------------------------------------


def test_latency_histogram_percentiles_bracket_samples():
    h = LatencyHistogram()
    for ms in [1, 1, 1, 1, 1, 1, 1, 1, 1, 100]:
        h.record(ms / 1e3)
    snap = h.snapshot()
    assert snap["count"] == 10
    # p50 lands in the 1ms bucket (log-spaced edge <= ~1.26ms)
    assert 0.9 <= snap["p50_ms"] <= 1.3
    # p99 is the tail sample's bucket, capped at the true max
    assert 90 <= snap["p99_ms"] <= 100.0
    assert snap["max_ms"] == pytest.approx(100.0)


def test_metrics_snapshot_totals_roll_up():
    m = Metrics()
    m.inc("a", "admitted", 3)
    m.inc("b", "admitted", 2)
    m.observe("a", "e2e", 0.01)
    m.annotate("a", plan_backend="pallas")
    snap = m.snapshot()
    assert snap["totals"]["admitted"] == 5
    assert snap["buckets"]["a"]["counters"]["admitted"] == 3
    assert snap["buckets"]["a"]["plan_backend"] == "pallas"
    assert snap["buckets"]["a"]["latency"]["e2e"]["count"] == 1


# -- data.pipeline.Prefetcher ------------------------------------------------


def test_prefetcher_preserves_order_and_exhausts():
    with Prefetcher(iter(range(100)), depth=4) as p:
        assert list(p) == list(range(100))


def test_prefetcher_propagates_producer_error():
    def gen():
        yield 1
        raise RuntimeError("boom")

    p = Prefetcher(gen(), depth=2)
    it = iter(p)
    assert next(it) == 1
    with pytest.raises(RuntimeError, match="boom"):
        next(it)


def test_prefetcher_inline_mode_is_passthrough():
    p = Prefetcher(iter([1, 2, 3]), depth=2, threaded=False)
    assert list(p) == [1, 2, 3]


def test_prefetcher_bounded_depth_backpressures_producer():
    produced = []

    def gen():
        for i in range(50):
            produced.append(i)
            yield i

    p = Prefetcher(gen(), depth=2)
    it = iter(p)
    assert next(it) == 0
    time.sleep(0.05)                      # let the producer run ahead
    assert len(produced) <= 2 + 2 + 1     # queue + sentinel slack, not 50
    p.close()


# -- server: correctness through the full pipeline ---------------------------


def test_server_inline_serves_correct_spectra():
    rng = np.random.default_rng(0)
    buckets = [BucketConfig((64, 64)), BucketConfig((64, 64), kind="rfft")]
    with SpectralServer(buckets, threaded=False) as srv:
        x = _c2c_payload(rng, (64, 64))
        r = rng.standard_normal((64, 64)).astype(np.float32)
        srv.submit("a", x)
        srv.submit("b", r, kind="rfft")
        assert srv.drain()
        got = _to_complex(srv.result("a").value)
        ref = np.fft.fft2(_to_complex(x))
        assert np.max(np.abs(got - ref)) / np.max(np.abs(ref)) < 1e-4
        gotb = _to_complex(srv.result("b").value)
        refb = np.fft.rfft2(r)
        assert gotb.shape == (64, 33)
        assert np.max(np.abs(gotb - refb)) / np.max(np.abs(refb)) < 1e-4


def test_server_pad_up_matches_zero_padded_fft():
    rng = np.random.default_rng(1)
    with SpectralServer([BucketConfig((64, 64))], threaded=False,
                        unmatched="pad_up") as srv:
        x = _c2c_payload(rng, (48, 40))
        srv.submit("p", x)
        assert srv.drain()
        rec = srv.result("p")
        assert rec.status == "completed" and rec.padded
        padded = np.zeros((64, 64), np.complex128)
        padded[:48, :40] = _to_complex(x)
        ref = np.fft.fft2(padded)
        got = _to_complex(rec.value)
        assert got.shape == (64, 64)
        assert np.max(np.abs(got - ref)) / np.max(np.abs(ref)) < 1e-4
        assert srv.metrics.counter("c2c/f/64x64", "padded_up") == 1


# Each slot-path case: (bucket, submit kwargs, payload of one request).
SLOT_MAX_BATCH = 4
SLOT_CASES = {
    "c2c": (BucketConfig((64, 64), max_batch=SLOT_MAX_BATCH), {},
            lambda rng: _c2c_payload(rng, (64, 64))),
    "rfft": (BucketConfig((64, 64), kind="rfft", max_batch=SLOT_MAX_BATCH),
             {"kind": "rfft"},
             lambda rng: rng.standard_normal((64, 64)).astype(np.float32)),
    "irfft": (BucketConfig((64, 64), kind="rfft", inverse=True,
                           max_batch=SLOT_MAX_BATCH),
              {"kind": "rfft", "inverse": True},
              lambda rng: _c2c_payload(rng, (64, 33))),
    "pad_up": (BucketConfig((64, 64), max_batch=SLOT_MAX_BATCH), {},
               lambda rng: _c2c_payload(rng, (48, 40))),
    "real_into_c2c": (BucketConfig((64, 64), max_batch=SLOT_MAX_BATCH), {},
                      lambda rng: rng.standard_normal((64, 64))
                      .astype(np.float32)),
}


@pytest.fixture(scope="module")
def slot_servers():
    """One pre-warmed server per (case, mode), shared by the live counts;
    each comes with the bucket's plan jitted on a padded host batch, the
    path the slot program replaced."""
    made = {}

    def get(case, threaded):
        if (case, threaded) not in made:
            bucket = SLOT_CASES[case][0]
            srv = SpectralServer([bucket], threaded=threaded,
                                 unmatched="pad_up")
            plan = srv.states[bucket.label].plan
            made[case, threaded] = (srv, jax.jit(lambda x, p=plan: p(x)))
        return made[case, threaded]

    yield get
    for srv, _ in made.values():
        srv.close()


def _padded_batch(payloads, bucket):
    """The bucket's ``(max_batch, *shape)`` input planes with each
    payload zero-padded into its slot, as a host batch."""
    shape = bucket.shape[:-1] + (bucket.shape[-1] // 2 + 1,) \
        if bucket.inverse else bucket.shape
    complex_in = bucket.kind == "c2c" or bucket.inverse
    planes = [np.zeros((bucket.max_batch,) + shape, np.float32)
              for _ in range(2 if complex_in else 1)]
    for i, x in enumerate(payloads):
        src = [x.re, x.im] if isinstance(x, SplitComplex) else [x]
        for plane, s in zip(planes, src):
            plane[(i,) + tuple(slice(0, d) for d in s.shape)] = s
    return SplitComplex(*planes) if complex_in else planes[0]


@pytest.mark.parametrize("threaded", [False, True],
                         ids=["inline", "threaded"])
@pytest.mark.parametrize("live", [1, 3, SLOT_MAX_BATCH])
@pytest.mark.parametrize("case", list(SLOT_CASES))
def test_slot_path_matches_plan_on_padded_batch(slot_servers, case, live,
                                                threaded):
    """Each request served through the per-slot program gets, bit for
    bit, what the bucket's plan returns for its image inside a padded
    full batch, with 1, some or all of the slots live."""
    bucket, kw, make = SLOT_CASES[case]
    srv, padded_plan = slot_servers(case, threaded)
    rng = np.random.default_rng(100 + live)
    payloads = [make(rng) for _ in range(live)]
    before = srv.metrics.counter(bucket.label, "batches")
    release = threading.Event()
    if threaded:
        # hold the staging thread until every request is queued, so the
        # batch carries exactly ``live`` slots
        pick = srv.scheduler.next_batch
        srv.scheduler.next_batch = lambda: (release.wait(30), pick())[1]
        time.sleep(0.05)        # any call already under way finds no work
    for i, x in enumerate(payloads):
        assert srv.submit((case, live, i), x, **kw)
    release.set()
    assert srv.drain(timeout_s=120)
    if threaded:
        srv.scheduler.next_batch = pick
    assert srv.metrics.counter(bucket.label, "batches") == before + 1
    want = padded_plan(_padded_batch(payloads, bucket))
    for i in range(live):
        rec = srv.result((case, live, i))
        assert rec.status == "completed"
        got = [np.asarray(a) for a in jax.tree.leaves(rec.value)]
        ref = [np.asarray(a)[i] for a in jax.tree.leaves(want)]
        assert [g.shape for g in got] == [r.shape for r in ref]
        for g, r in zip(got, ref):
            np.testing.assert_array_equal(g, r)


def test_server_rejects_unmatched_and_counts_it():
    rng = np.random.default_rng(2)
    with SpectralServer([BucketConfig((64, 64))], threaded=False) as srv:
        with pytest.raises(NoBucketError):
            srv.submit("nope", _c2c_payload(rng, (48, 48)))
        assert srv.metrics.counter("_unmatched", "rejected_nobucket") == 1
        with pytest.raises(KeyError):
            srv.result("nope")            # nothing was recorded


def test_server_prime_size_rides_demoted_jnp_plan():
    """A bucket whose shape the pallas kernels can't take (prime dims)
    resolves to a demoted jnp plan; requests are served correctly and the
    demotion is visible in fallback metrics + the bucket annotation."""
    rng = np.random.default_rng(3)
    with SpectralServer([BucketConfig((61, 61))], threaded=False) as srv:
        st = srv.states["c2c/f/61x61"]
        assert st.requested_backend == "pallas"
        assert st.plan.backend == "jnp" and st.plan.demote_reason
        x = _c2c_payload(rng, (61, 61))
        srv.submit("prime", x)
        assert srv.drain()
        rec = srv.result("prime")
        assert rec.status == "completed"
        ref = np.fft.fft2(_to_complex(x))
        got = _to_complex(rec.value)
        assert np.max(np.abs(got - ref)) / np.max(np.abs(ref)) < 1e-4
        assert srv.metrics.counter("c2c/f/61x61", "fallback_served") == 1
        snap = srv.snapshot()
        assert snap["buckets"]["c2c/f/61x61"]["demote_reason"]


def test_server_backpressure_and_duplicate_rid():
    rng = np.random.default_rng(4)
    with SpectralServer([BucketConfig((64, 64))], threaded=False,
                        max_queue=1) as srv:
        assert srv.submit("a", _c2c_payload(rng, (64, 64)))
        assert not srv.submit("b", _c2c_payload(rng, (64, 64)))
        assert srv.metrics.counter("c2c/f/64x64",
                                   "rejected_backpressure") == 1
        with pytest.raises(ValueError, match="duplicate"):
            srv.submit("a", _c2c_payload(rng, (64, 64)))
        assert srv.drain()
        assert srv.result("a").status == "completed"


def test_server_rejects_batched_payloads():
    rng = np.random.default_rng(5)
    with SpectralServer([BucketConfig((64, 64))], threaded=False) as srv:
        with pytest.raises(ValueError, match="batch"):
            srv.submit("x", rng.standard_normal((3, 64, 64)), kind="rfft")


# -- deadlines: queued vs in-flight ------------------------------------------


def test_deadline_expires_queued_deterministic_clock():
    clk = FakeClock()
    rng = np.random.default_rng(6)
    with SpectralServer([BucketConfig((64, 64))], threaded=False,
                        clock=clk) as srv:
        srv.submit("dies", _c2c_payload(rng, (64, 64)), deadline_s=1.0)
        srv.submit("lives", _c2c_payload(rng, (64, 64)))
        clk.t = 2.0                       # past the queued deadline
        assert srv.drain()
        assert srv.result("dies").status == "timed_out_queued"
        assert srv.result("lives").status == "completed"
        assert srv.metrics.counter("c2c/f/64x64", "timed_out_queued") == 1
        assert srv.metrics.counter("c2c/f/64x64", "completed") == 1


def test_deadline_expires_inflight_under_step_hang():
    """The deadline passes while the batch is already dispatched (a
    ``serve.step`` hang): the request terminates ``timed_out_inflight``,
    never ``timed_out_queued``, and never blocks forever."""
    rng = np.random.default_rng(7)
    with SpectralServer([BucketConfig((64, 64))], threaded=False) as srv:
        with faults.inject("serve.step", "hang", duration=0.25):
            srv.submit("late", _c2c_payload(rng, (64, 64)), deadline_s=0.05)
            assert srv.drain()
        rec = srv.result("late")
        assert rec.status == "timed_out_inflight"
        assert rec.value is None
        assert srv.metrics.counter("c2c/f/64x64", "timed_out_inflight") == 1
        assert srv.metrics.counter("c2c/f/64x64", "timed_out_queued") == 0


# -- prewarm + resilience ----------------------------------------------------


def test_prewarm_fault_degrades_with_identical_outputs():
    """An injected pre-warm fault demotes the bucket to jnp with no crash;
    the degraded server's spectra match a healthy server's bit-for-bit at
    fp32 tolerance (acceptance: degrade changes the path, not the math)."""
    rng = np.random.default_rng(8)
    x = _c2c_payload(rng, (64, 64))
    with SpectralServer([BucketConfig((64, 64))], threaded=False) as ok:
        ok.submit("r", x)
        ok.drain()
        want = _to_complex(ok.result("r").value)
    with faults.inject("serve.prewarm", "error"):
        srv = SpectralServer([BucketConfig((64, 64))], threaded=False)
    with srv:
        assert srv.degraded_buckets == ["c2c/f/64x64"]
        st = srv.states["c2c/f/64x64"]
        assert st.plan.backend == "jnp" and "FaultInjected" in st.reason
        assert srv.prewarm_report.degraded == ["c2c/f/64x64"]
        srv.submit("r", x)
        srv.drain()
        got = _to_complex(srv.result("r").value)
    assert np.max(np.abs(got - want)) <= 1e-6 * max(1.0, np.abs(want).max())


def test_prewarm_report_entries():
    with SpectralServer([BucketConfig((64, 64)),
                         BucketConfig((64, 64), kind="rfft")],
                        threaded=False) as srv:
        rep = srv.prewarm_report
        assert sorted(e.label for e in rep.entries) == \
            ["c2c/f/64x64", "rfft/f/64x64"]
        assert all(e.compile_s > 0 for e in rep.entries)
        assert rep.total_s >= max(e.compile_s for e in rep.entries)
        assert not rep.degraded


# -- threaded pipeline: drain-on-shutdown, zero orphans ----------------------


def test_threaded_drain_on_shutdown_zero_orphans():
    rng = np.random.default_rng(9)
    buckets = [BucketConfig((64, 64)), BucketConfig((64, 64), kind="rfft")]
    srv = SpectralServer(buckets, threaded=True)
    rids = []
    for i in range(30):
        rid = f"r{i}"
        if i % 2:
            ok = srv.submit(rid, rng.standard_normal((64, 64))
                            .astype(np.float32), kind="rfft")
        else:
            ok = srv.submit(rid, _c2c_payload(rng, (64, 64)))
        if ok:
            rids.append(rid)
    assert srv.close(timeout_s=60)        # stop admission + drain + join
    for rid in rids:                      # every admitted rid terminated
        rec = srv.result(rid, timeout=0)
        assert rec is not None and rec.status == "completed"
    assert not srv.submit("late", _c2c_payload(rng, (64, 64)))
    snap = srv.snapshot()
    assert snap["pending"] == 0
    assert snap["totals"]["completed"] == len(rids)
    assert not any(t.is_alive() for t in srv.executor._threads)


def test_result_consumes_record_and_frees_rid():
    """result() evicts the terminal record + event (no per-request leak
    in a long-lived server) and the rid becomes reusable."""
    rng = np.random.default_rng(14)
    with SpectralServer([BucketConfig((64, 64))], threaded=False) as srv:
        x = _c2c_payload(rng, (64, 64))
        srv.submit("r", x)
        assert srv.drain()
        assert srv.result("r").status == "completed"
        assert srv._records == {} and srv._done == {}
        with pytest.raises(KeyError):
            srv.result("r")                   # consumed
        srv.submit("r", x)                    # reuse: no duplicate error
        assert srv.drain()
        assert srv.result("r").status == "completed"


def test_prewarm_jnp_twin_failure_never_crashes(monkeypatch):
    """Both the bucket's plan AND its jnp twin fail to compile at
    pre-warm: startup still succeeds (degrade, never crash), the report
    records the double failure, and the runtime degrade path serves the
    first request anyway."""
    from repro.serve.spectral import prewarm as prewarm_mod

    def broken(state):
        raise RuntimeError("no compile for you")

    # only pre-warm sees the broken compiler; the executor's runtime
    # make_fn is untouched, so first dispatch recovers
    monkeypatch.setattr(prewarm_mod, "make_fn", broken)
    rng = np.random.default_rng(15)
    with SpectralServer([BucketConfig((64, 64))], threaded=False) as srv:
        (entry,) = srv.prewarm_report.entries
        assert entry.degraded
        assert "jnp twin failed" in entry.reason
        st = srv.states["c2c/f/64x64"]
        assert st.fn is None and st.plan.backend == "jnp"
        srv.submit("r", _c2c_payload(rng, (64, 64)))
        assert srv.drain()
        assert srv.result("r").status == "completed"


def test_threaded_step_error_terminates_requests():
    """A dispatch error that survives the degrade path still terminates
    every request in the batch (status "error"), never orphans them."""
    rng = np.random.default_rng(10)
    srv = SpectralServer([BucketConfig((64, 64))], threaded=True)
    try:
        # error fires on the jnp twin too: degrade re-raise path
        with faults.inject("serve.step", "error", times=None):
            srv.submit("e", _c2c_payload(rng, (64, 64)))
            rec = srv.result("e", timeout=30)
        assert rec is not None and rec.status == "error"
        assert isinstance(rec.error, faults.FaultInjected)
    finally:
        srv.close()


def test_threaded_staging_crash_still_releases_pipeline():
    """A staging-side crash (next_batch itself raising) kills the staging
    generator; the Prefetcher re-raises at the dispatch loop's next().
    The drain sentinel must still flow — shutdown() joins promptly and no
    pipeline thread is left alive."""
    srv = SpectralServer([BucketConfig((64, 64))], threaded=True)
    threads = list(srv.executor._threads)

    def boom():
        raise RuntimeError("staging boom")

    srv.scheduler.next_batch = boom
    srv.executor.poke()
    time.sleep(0.2)                   # let staging hit the crash
    t0 = time.monotonic()
    srv.executor.shutdown()
    assert time.monotonic() - t0 < 5.0
    assert not any(t.is_alive() for t in threads)
    snap = srv.metrics.snapshot()
    assert "staging boom" in snap["buckets"]["_pipeline"]["staging_error"]


def test_threaded_assembly_error_terminates_requests_not_pipeline():
    """Batch assembly failing after the requests left the scheduler still
    gives each an "error" terminal record, and staging survives to serve
    later requests."""
    rng = np.random.default_rng(16)
    srv = SpectralServer([BucketConfig((64, 64))], threaded=True)
    try:
        orig = srv.executor._assemble
        calls = {"n": 0}

        def flaky(bucket, reqs):
            calls["n"] += 1
            if calls["n"] == 1:
                raise RuntimeError("assembly boom")
            return orig(bucket, reqs)

        srv.executor._assemble = flaky
        srv.submit("a", _c2c_payload(rng, (64, 64)))
        rec = srv.result("a", timeout=30)
        assert rec is not None and rec.status == "error"
        assert "assembly boom" in str(rec.error)
        srv.submit("b", _c2c_payload(rng, (64, 64)))
        rec = srv.result("b", timeout=30)
        assert rec is not None and rec.status == "completed"
    finally:
        srv.close()


# -- loadgen + metrics endpoint ----------------------------------------------


def test_closed_loop_completes_all():
    buckets = [BucketConfig((64, 64)), BucketConfig((128,))]
    mix = [MixItem((64, 64)), MixItem((128,), weight=0.5)]
    with SpectralServer(buckets, threaded=True) as srv:
        res = closed_loop(srv, mix, requests=24, concurrency=6, seed=0)
        assert res["completed"] == 24
        assert res["timed_out"] == 0
        assert res["achieved_qps"] > 0
        assert res["p99_ms"] >= res["p50_ms"] > 0


def test_open_loop_reports_offered_vs_achieved():
    with SpectralServer([BucketConfig((64, 64))], threaded=True) as srv:
        res = open_loop(srv, [MixItem((64, 64))], qps=100.0,
                        duration_s=0.3, seed=1)
        assert res["offered_qps"] == 100.0
        assert res["completed"] + res["timed_out"] + res["rejected"] > 0
        assert res["completed"] > 0


def test_metrics_http_endpoint_serves_snapshot():
    import json
    import urllib.request
    rng = np.random.default_rng(11)
    with SpectralServer([BucketConfig((64, 64))], threaded=False) as srv:
        port = srv.serve_metrics_http()
        srv.submit("m", _c2c_payload(rng, (64, 64)))
        srv.drain()
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=5).read()
        snap = json.loads(body)
        assert snap["buckets"]["c2c/f/64x64"]["counters"]["admitted"] == 1
