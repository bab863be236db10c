"""The open-loop generator gives every seed the same work: the same
number of requests of each mix item and the same gaps between them,
inside the window, in another order."""
import numpy as np
import pytest

import run

drv = run.load_module(run.os.path.join(run.HERE, "drivers",
                                       "serve_open_loop.py"),
                      "bench_driver_serve_open_loop")

MIX = [{"kind": "c2c", "shape": [8, 8], "weight": 1},
       {"kind": "rfft", "shape": [8, 8], "weight": 1},
       {"kind": "c2c", "shape": [16, 16], "weight": 2}]


def test_same_work_for_every_seed():
    traffic = {"mix": MIX, "arrivals": {"process": "poisson", "gap_seed": 0},
               "rate_per_s": 40,
               "pool_per_item": 3}
    seen = []
    for seed in (1, 2**31 + 5):
        s = drv.schedule(traffic, 10.0, np.random.default_rng(seed))
        assert len(s["due"]) == 400
        assert np.all(np.diff(s["due"]) >= 0)
        assert 0 <= s["due"][0] and s["due"][-1] < 10.0
        assert np.bincount(s["item"]).tolist() == [100, 100, 200]
        assert s["slot"].max() < 3
        seen.append(s["due"])
    assert not np.array_equal(seen[0], seen[1])
    gaps = [np.sort(np.diff(d, prepend=0.0)) for d in seen]
    np.testing.assert_allclose(gaps[0], gaps[1], rtol=0, atol=1e-12)


def test_same_seed_same_schedule():
    traffic = {"mix": MIX, "arrivals": {"process": "poisson", "gap_seed": 0},
               "rate_per_s": 7, "pool_per_item": 2}
    a = drv.schedule(traffic, 3.0, np.random.default_rng(9))
    b = drv.schedule(traffic, 3.0, np.random.default_rng(9))
    for k in ("due", "item", "slot"):
        assert np.array_equal(a[k], b[k])


def test_refused_request_is_a_failure_not_a_missing_answer():
    base = {"mix": MIX, "sched": {"item": np.array([0, 0]),
                                  "slot": np.array([0, 0])},
            "pool": [[None]], "fallback": 0, "kept": {}}
    refused = drv.compare(dict(base, admitted_sample=frozenset()))
    lost = drv.compare(dict(base, admitted_sample=frozenset({1})))
    assert refused["missing_answers"] == 0
    assert lost["missing_answers"] == 1


class _Rec:
    status, latency_s, value = "completed", 0.001, None


class _HalfRefusingServer:
    """Admits even request ids, answers each at once; refuses the rest."""

    def submit(self, rid, payload, kind):
        return rid % 2 == 0

    def result(self, rid, timeout):
        return _Rec()


def test_unanswered_requests_count_in_the_tail():
    traffic = {"mix": MIX[:1], "arrivals": {"process": "poisson", "gap_seed": 0},
               "rate_per_s": 50, "pool_per_item": 1}
    sched = drv.schedule(traffic, 0.2, np.random.default_rng(4))
    out = drv.open_loop(_HalfRefusingServer(), MIX[:1], sched, [[None]])
    n = len(sched["due"])
    assert len(out.latency_s) == n and out.answered.sum() == (n + 1) // 2
    assert len(out.rejected) == n // 2
    assert np.all(out.latency_s[~out.answered] >= drv.RESULT_GRACE_S)
    assert np.percentile(out.latency_s, 95) >= drv.RESULT_GRACE_S
