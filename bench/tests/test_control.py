"""The control -- the reference's operations at ``Precision.HIGH``, put in
the program's place on the same sampled inputs -- must fail each cell's
comparison, and the program must pass it, at a size a CPU holds.  The
control's bf16 passes are spelled out, so it reads on the CPU as on the
chip."""
import os

import pytest

import calibrate
import run


def mix(n):
    return [{"kind": k, "shape": [n, n], "weight": 1}
            for k in ("c2c", "rfft")]


SMALL = {
    "serve2d.img1024.poisson": {"traffic": {"mix": mix(128),
                                            "rate_per_s": 16,
                                            "sample": 6}},
    "lib2d.img1024.b32": {"traffic": {"shape": [128, 128], "batch": 4,
                                      "sample_images": 2}},
}


@pytest.fixture(scope="module")
def bench():
    return run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_control_fails_and_program_passes(bench, cell):
    limit = run.cell_spec(bench, cell)["limits"]["max_rel_l2"]["max"]
    prog = calibrate.readings(bench, cell, 3, 0.5, control=False,
                              overrides=SMALL[cell])
    ctrl = calibrate.readings(bench, cell, 3, 0.5, control=True,
                              overrides=SMALL[cell])
    assert prog["max_rel_l2"] < limit < ctrl["max_rel_l2"]
