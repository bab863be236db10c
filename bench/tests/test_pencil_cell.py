"""The pencil cell at a CPU's size, on 4 fake devices in one subprocess: a
sound run (untraced and traced) is correct, a planted fault in the
packed-row operator is not, and the control fails the limit the program
meets.  In-process: the four readers on a hand-made reduction, and the
window's bound on the steps in flight."""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import run
import work

CELL = "pencil2d.r4096.x4"

drv = run.load_module(os.path.join(run.HERE, "drivers", "pencil_step.py"),
                      "bench_driver_pencil_step")

CODE = r"""
import json, os, sys
import run, calibrate
from repro.dist import pencil

CELL = "pencil2d.r4096.x4"
bench = run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))


def small(n):
    # nu_dt scaled by (4096/n)^2: the operator at the highest |k| is 1/9 to
    # 1/85, as in the cell
    s = (4096 / n) ** 2
    return {"traffic": {"shape": [n, n], "batch": 2, "ahead_steps": 4,
                        "nu_dt": [1e-6 * s, 1e-5 * s]}}


def naive(z, op, axis):
    # the packed row 0 (DC + i*Nyquist) multiplied as it stands: the
    # Nyquist column's own operator left out
    return pencil._times(z, op.rows)


out = {"sound": run.run_cell(bench, CELL, 2**31 + 11, 1.0, False,
                             overrides=small(64)),
       "traced": run.run_cell(bench, CELL, 2**31 + 12, 0.5, True,
                              overrides=small(64))}
sound = pencil._apply_half_operator
pencil._apply_half_operator = naive
try:
    out["naive"] = run.run_cell(bench, CELL, 2**31 + 13, 0.5, False,
                                overrides=small(64))
finally:
    pencil._apply_half_operator = sound
drv = run.load_module(os.path.join(run.HERE, "drivers", "pencil_step.py"),
                      "bench_driver_pencil_step")
calibrate.CONTROLS["pencil_step"] = drv.control
for who in ("program", "control"):
    out[who] = calibrate.readings(bench, CELL, 3, 0.5,
                                  control=who == "control",
                                  overrides=small(128))
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([run.HERE,
                                           os.path.join(run.ROOT, "src")]))
    proc = subprocess.run([sys.executable, "-c", CODE], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


@pytest.fixture(scope="module")
def limit():
    bench = run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))
    return run.cell_spec(bench, CELL)["limits"]["max_rel_l2"]["max"]


@pytest.mark.parametrize("kind", ["sound", "traced"])
def test_sound_run_is_correct(runs, kind):
    res = runs[kind]
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["checks"]["kinds_unchecked"]["value"] == 0
    assert res["device"]["count"] == 4
    if kind == "sound":
        assert set(res["metrics"]) == {"step_ms", "setup_s"}
    else:
        # no device plane on a CPU: the readers find nothing and say so
        assert res["metrics"] == {}


def test_packed_row_fault_reads_far_above_the_limit(runs, limit):
    res = runs["naive"]
    assert not res["correct"]
    assert res["checks"]["max_rel_l2"]["value"] > 100 * limit


def test_control_fails_and_program_passes(runs, limit):
    assert runs["program"]["max_rel_l2"] < limit \
        < runs["control"]["max_rel_l2"]


# -- readers, on a hand-made reduction of 4 chips ---------------------------

N = 4096
STEPS, BATCH, CHIPS = 2000, 8, 4
WIRE = 2 * 2 * BATCH * (N // CHIPS) * (N // 2) * 4     # 256 MiB per step


def _ctx(**trace):
    red = {"devices": CHIPS, "busy_s": 50.0, "window_s": 51.0,
           "a2a_s": 10.0, "a2a_exposed_s": 8.0}
    red.update(trace)
    fields = BATCH * STEPS
    return {"trace": red, "cfg": {"chips": CHIPS},
            "device_kind": "TPU v5 lite",
            "window": {"diag": {"steps": STEPS},
                       "counters": {"exchange_offchip_bytes":
                                    0.75 * WIRE * STEPS},
                       "work": {k: work.transform_work(k, (N, N),
                                                       batch=fields)
                                for k in ("r2c", "c2r")}}}


def _read(name, ctx):
    mod = run.load_module(os.path.join(run.HERE, "metrics", f"{name}.py"),
                          f"bench_metric_{name.replace('.', '_')}")
    return mod.read(ctx)


def test_readers_by_hand():
    ctx = _ctx()
    assert _read("a2a_ms.step", ctx) == pytest.approx(5.0)       # 10 s/2000
    assert _read("a2a_exposed_share", ctx) == pytest.approx(800 / 51)
    assert _read("a2a_gbytes_per_s", ctx) == pytest.approx(
        0.75 * 268435456 * 2000 / 10.0 / 1e9)                     # 40.27
    got = _read("pencil_local_roofline", ctx)
    # one chip's share: 16000 fields x (r2c + c2r bytes) / 4 chips at
    # 819 GB/s, over 50 - 8 s of device time outside exposed all-to-all
    nbytes = 2 * (4 * N * N + 8 * N * (N // 2 + 1))
    assert got["bound"] == "memory"
    assert got["value"] == pytest.approx(
        100 * BATCH * STEPS * nbytes / CHIPS / 819e9 / 42.0)      # 3.12%
    assert got["value"] < 100


def test_roofline_counts_one_chips_share():
    whole = _ctx()
    one = _ctx()
    one["cfg"] = {"chips": 1}
    assert _read("pencil_local_roofline", one)["value"] == pytest.approx(
        CHIPS * _read("pencil_local_roofline", whole)["value"])


@pytest.mark.parametrize("name", ["a2a_ms.step", "a2a_exposed_share",
                                  "a2a_gbytes_per_s",
                                  "pencil_local_roofline"])
def test_readers_find_nothing_without_a_trace_or_exchange(name):
    assert _read(name, dict(_ctx(), trace=None)) is None
    assert _read(name, _ctx(devices=0)) is None
    if name != "pencil_local_roofline":
        assert _read(name, _ctx(a2a_s=0.0, a2a_exposed_s=0.0)) is None


# -- the window ---------------------------------------------------------------

def test_window_bounds_the_steps_in_flight(monkeypatch):
    sent, waited, depth = [], [], []

    def step(*_args):
        sent.append(object())
        depth.append(len(sent) - len(waited))
        return sent[-1]

    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: waited.append(x) or x)
    traffic = {"shape": [4, 4], "batch": 1, "pool": 2, "ahead_steps": 3,
               "sample_steps_per_input": 1}
    state = drv.State(cfg={"chips": 4}, traffic=traffic,
                      rng=np.random.default_rng(0), step=step, op=None,
                      g=None, inputs=[0, 1], wire_bytes=100)
    win = drv.window(state, 0.05)
    assert win["attempted"] == len(sent) > 3
    assert waited == sent                    # every step, in order
    assert max(depth) == 4                   # three ahead of the newest
    assert win["window_s"] >= 0.05
    assert win["counters"]["exchange_offchip_bytes"] == \
        pytest.approx(0.75 * 100 * len(sent))
    # one sampled step of each input stack
    assert [[p for _i, p, _out in res.items] for res in state.sampled] \
        == [[0], [1]]


# -- what the check compares --------------------------------------------------

def _filtered_run(bad=None):
    """A window over 2 input stacks of 3 fields whose step is the float64
    reference, except field ``bad[1]`` of input ``bad[0]``, which is left
    unfiltered; then the driver's finish and compare."""
    import reference
    rng = np.random.default_rng(3)
    g = drv.helmholtz((8, 8), 1e-2)
    inputs = [rng.standard_normal((3, 8, 8)).astype(np.float32)
              for _ in range(2)]

    def step(x, _op):
        out = np.stack([reference.filter_real(f, g) for f in x])
        if bad is not None and x is inputs[bad[0]]:
            out[bad[1]] = x[bad[1]]
        return out

    traffic = {"shape": [8, 8], "batch": 3, "pool": 2, "ahead_steps": 2,
               "sample_steps_per_input": 1}
    state = drv.State(cfg={"chips": 4}, traffic=traffic,
                      rng=np.random.default_rng(0), step=step, op=None,
                      g=g, inputs=inputs, wire_bytes=100)
    drv.window(state, 0.02)
    answers = drv.finish(state)
    return answers, drv.compare(answers)


def test_every_field_of_each_input_stack_is_checked():
    answers, sound = _filtered_run()
    assert sorted(len(x) for _i, x, _got in answers["cases"]) == [3, 3]
    assert sound == {"max_rel_l2": pytest.approx(0.0, abs=1e-6),
                     "kinds_unchecked": 0}


@pytest.mark.parametrize("bad", [(0, 0), (0, 2), (1, 1)])
def test_one_wrong_field_is_caught(bad, limit):
    _answers, got = _filtered_run(bad)
    assert got["max_rel_l2"] > 100 * limit


def test_an_input_stack_never_sampled_is_unchecked():
    answers, _ = _filtered_run()
    answers = dict(answers, cases=answers["cases"][:1], inputs_unchecked=1)
    assert drv.compare(answers)["kinds_unchecked"] == 1


@pytest.mark.parametrize("key,value", [("dtype", "bfloat16"),
                                       ("compress", "bf16"),
                                       ("backend", "pallas"),
                                       ("precision", "HIGH")])
def test_config_must_state_what_the_step_runs(key, value):
    cfg = run.load_json(os.path.join(run.HERE, "configs",
                                     "pencil2d_fp32_x4.json"))
    drv.check_config(cfg)
    with pytest.raises(ValueError, match=key):
        drv.check_config(dict(cfg, **{key: value}))
