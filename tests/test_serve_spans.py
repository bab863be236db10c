"""Stage spans of the spectral server: each pipeline stage is timed into a
histogram of its own, one observation per request, and shows as a
``serve.<stage>`` host span in a profiler trace."""
import glob
import os

import numpy as np
import pytest

from repro.core.complexmath import SplitComplex
from repro.serve.spectral import BucketConfig, SpectralServer

SHAPE = (64, 64)
MAX_BATCH = 4
STAGING = ("wait", "assemble", "h2d")
SPANS = ("assemble", "h2d", "dispatch", "device_wait", "copy_back")


HALF = SHAPE[:-1] + (SHAPE[-1] // 2 + 1,)
PLANE = 4 * SHAPE[0] * SHAPE[1]          # bytes of one fp32 plane
HALF_PLANE = 4 * HALF[0] * HALF[1]
# case: (bucket kind, inverse, payload kind, slot bytes in, slot bytes out)
CASES = {"c2c": ("c2c", False, "c2c", 2 * PLANE, 2 * PLANE),
         "rfft": ("rfft", False, "real", PLANE, 2 * HALF_PLANE),
         "irfft": ("rfft", True, "half", 2 * HALF_PLANE, PLANE),
         "real_into_c2c": ("c2c", False, "real", PLANE, 2 * PLANE)}


def _payload(rng, kind):
    if kind in ("rfft", "real"):
        return rng.standard_normal(SHAPE).astype(np.float32)
    shape = HALF if kind == "half" else SHAPE
    return SplitComplex(rng.standard_normal(shape).astype(np.float32),
                        rng.standard_normal(shape).astype(np.float32))


def _serve(case, n):
    """Serve ``n`` requests inline through one jnp bucket; the bucket's
    section of the metrics snapshot."""
    kind, inverse, payload = CASES.get(case, (case, False, case))[:3]
    rng = np.random.default_rng(0)
    bucket = BucketConfig(SHAPE, kind=kind, inverse=inverse, backend="jnp",
                          max_batch=MAX_BATCH)
    with SpectralServer([bucket], threaded=False) as srv:
        for i in range(n):
            assert srv.submit(i, _payload(rng, payload), kind=kind,
                              inverse=inverse)
        assert srv.drain()
        assert all(srv.result(i).status == "completed" for i in range(n))
        return srv.metrics.snapshot()["buckets"][bucket.label]


def _sum_s(section, name):
    h = section["latency"][name]
    return h["mean_ms"] * h["count"] * 1e-3


@pytest.mark.parametrize("kind", ["c2c", "rfft"])
def test_each_stage_histogram_counts_one_per_completed_request(kind):
    sec = _serve(kind, 10)                 # batches of 4, 4 and 2
    assert sec["counters"]["completed"] == 10
    assert sec["counters"]["batches"] == 3
    for name in STAGING + SPANS[2:]:
        assert sec["latency"][name]["count"] == 10, name


@pytest.mark.parametrize("kind", ["c2c", "rfft"])
def test_queue_is_wait_plus_assemble_plus_h2d(kind):
    sec = _serve(kind, 10)
    parts = sum(_sum_s(sec, name) for name in STAGING)
    assert parts <= _sum_s(sec, "queue")
    assert _sum_s(sec, "queue") == pytest.approx(parts, abs=1e-3)


@pytest.mark.parametrize("case", list(CASES))
def test_link_bytes_count_live_slots_only(case):
    sec = _serve(case, 10)                 # batches of 4, 4 and 2
    c = sec["counters"]
    assert (c["batch_items"], c["batch_pad_slots"]) == (10, 2)
    slot_in, slot_out = CASES[case][3:]
    assert c["h2d_bytes"] == 10 * slot_in
    assert c["d2h_bytes"] == 10 * slot_out


@pytest.mark.parametrize("case", list(CASES))
def test_assemble_allocates_no_batch_geometry(case, monkeypatch):
    """Staging builds no host array of the ``(max_batch, *shape)`` batch:
    the padding stays on the device."""
    made = []
    for name in ("zeros", "empty"):
        orig = getattr(np, name)

        def record(shape, *a, _orig=orig, **kw):
            made.append(tuple(np.atleast_1d(shape)))
            return _orig(shape, *a, **kw)

        monkeypatch.setattr(np, name, record)
    _serve(case, 6)                        # a full and a partial batch
    batch = [(MAX_BATCH,) + s for s in (SHAPE, HALF)]
    assert not [m for m in made if m in batch]


def test_trace_holds_one_span_per_batch_matching_the_histograms(tmp_path):
    import jax
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        sec = _serve("c2c", 3 * MAX_BATCH)         # three full batches
    (path,) = glob.glob(os.path.join(tmp_path, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    spans = {name: [] for name in SPANS}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serve."):
                    spans[e.name[len("serve."):]].append(e.duration_ns)
    for name in SPANS:
        assert len(spans[name]) == 3, name
        # every request of a full batch counts the batch's span once
        traced_s = MAX_BATCH * sum(spans[name]) * 1e-9
        assert traced_s == pytest.approx(_sum_s(sec, name), rel=0.1), name
