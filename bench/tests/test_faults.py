"""Drive whole runs of each cell at a CPU's size, with the timed path
broken underneath, and see ``correct`` come out false.

Each fault is planted in the program the harness drives:

- ``unchanged``: every transform returns its input as it came;
- ``half_batch``: the leading half of every batch is left out (zeros);
- ``altered``: one value of every answer is changed where it is produced.
"""
import json
import os

import jax.numpy as jnp
import pytest

import run


def mix(n):
    return [{"kind": k, "shape": [n, n], "weight": 1}
            for k in ("c2c", "rfft")]


SMALL = {
    "serve2d.img1024.poisson": {"traffic": {"mix": mix(64),
                                            "rate_per_s": 20,
                                            "sample": 1000}},
    "lib2d.img1024.b32": {"traffic": {"shape": [64, 64], "batch": 4,
                                      "sample_images": 4}},
}
FAULTS = {
    "serve2d.img1024.poisson": ["unchanged", "half_batch", "altered"],
    "lib2d.img1024.b32": ["unchanged", "half_batch", "altered"],
}
SECONDS = 1.0


@pytest.fixture(scope="module")
def bench():
    return run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))


def _split_map(y, fn):
    from repro.core.complexmath import SplitComplex
    if isinstance(y, SplitComplex):
        return SplitComplex(fn(y.re), fn(y.im))
    return fn(y)


def _unchanged(plan, x):
    """The transform's input, cut or padded to its output's shape."""
    from repro.core.complexmath import SplitComplex
    if plan.kind == "rfft" and not plan.inverse:
        half = x[..., : x.shape[-1] // 2 + 1]
        return SplitComplex(half, jnp.zeros_like(half))
    if plan.kind == "rfft":
        w = plan.shape[-1]
        body = jnp.concatenate([x.re, x.re[..., 1:-1]], axis=-1)
        return body[..., :w]
    return x


def _half(a):
    keep = jnp.arange(a.shape[0]) >= a.shape[0] // 2
    return a * keep.reshape((-1,) + (1,) * (a.ndim - 1))


def _alter(a):
    return a.at[..., 0, 0].add(1.0) if a.ndim >= 2 else a.at[0].add(1.0)


def plant(monkeypatch, fault):
    from repro.core import plan as plan_lib
    original = plan_lib.FFTPlan.__call__

    def broken(self, x, *args):
        if fault == "unchanged":
            return _unchanged(self, x)
        y = original(self, x, *args)
        return _split_map(y, _half if fault == "half_batch" else _alter)

    monkeypatch.setattr(plan_lib.FFTPlan, "__call__", broken)


def _run(bench, cell, seed=2**31 + 11):
    res = run.run_cell(bench, cell, seed, SECONDS, False,
                       overrides=SMALL[cell])
    json.dumps(res)                       # the result line is plain JSON
    return res


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_sound_run_is_correct(bench, cell):
    res = _run(bench, cell)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"
    assert {"setup_s"} < set(res["metrics"])


@pytest.mark.parametrize("cell,fault",
                         [(c, f) for c in sorted(FAULTS) for f in FAULTS[c]])
def test_fault_makes_the_run_incorrect(bench, cell, fault, monkeypatch):
    plant(monkeypatch, fault)
    res = _run(bench, cell)
    assert not res["correct"], res["checks"]
