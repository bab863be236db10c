"""Readings that the correctness limits are set from, in one process.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,... \\
        --control-seeds 7,8,9 --seconds 3

For each seed of ``--seeds`` the cell runs as ``run.py`` runs it (set-up,
a short window at the cell's own load and size, the sampled answers
compared with the float64 reference) and prints the readings of the
program.  For each seed of ``--control-seeds`` it does the same, then puts
the control in the program's place -- the reference's operations as
dense DFT products at ``Precision.HIGH`` -- on the same sampled inputs,
and prints the control's readings.  The lower reading of a number is the
largest the program gives; the upper one the smallest the control gives.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def _device_tables(shape, *, real: bool):
    import jax.numpy as jnp
    import reference
    return {k: tuple(jnp.asarray(a) for a in v)
            for k, v in reference.control_tables(shape, real=real).items()}


def control_serve(answers: dict) -> dict:
    """Serve answers with the control's forward transforms in place of
    the server's."""
    import jax
    import reference
    from repro.core.complexmath import SplitComplex
    sched, pool = answers["sched"], answers["pool"]
    fwd = jax.jit(reference.control_forward, static_argnames="real")
    tables = {}
    kept = {}
    for i in answers["kept"]:
        item, slot = int(sched["item"][i]), int(sched["slot"][i])
        m = answers["mix"][item]
        real, shape = m["kind"] == "rfft", tuple(m["shape"])
        if (real, shape) not in tables:
            tables[real, shape] = _device_tables(shape, real=real)
        p = pool[item][slot]
        z = p if real else np.stack([p.re, p.im])
        out = np.asarray(fwd(z, tables[real, shape], real=real))
        kept[i] = SplitComplex(out[0], out[1])
    return dict(answers, kept=kept)


def control_lib(answers: dict) -> dict:
    """Library answers with the control's round trips in place of the
    program's."""
    import jax
    import jax.numpy as jnp
    import reference
    h, g = answers["ops"]
    prop = jax.jit(reference.control_propagate)
    filt = jax.jit(reference.control_filter)
    cases = []
    for kind, i, x, _got in answers["cases"]:
        t = _device_tables(x.shape[-2:], real=bool(kind))
        if kind:
            got = np.asarray(filt(jnp.asarray(x, jnp.float32),
                                  jnp.asarray(g), t))
        else:
            out = np.asarray(prop(jnp.asarray(x.real, jnp.float32),
                                  jnp.asarray(x.imag, jnp.float32),
                                  jnp.asarray(h.real), jnp.asarray(h.imag),
                                  t))
            got = out[0] + 1j * out[1]
        cases.append((kind, i, x, got))
    return dict(answers, cases=cases)


CONTROLS = {"serve_open_loop": control_serve, "lib_step": control_lib}


def readings(bench: dict, name: str, seed: int, seconds: float, *,
             control: bool, overrides: dict = None,
             bench_dir: str = HERE) -> dict:
    """One short run of cell ``name``: the program's readings, or with
    ``control`` the control's on the same sampled inputs."""
    import run
    spec = run.cell_spec(bench, name, bench_dir=bench_dir)
    for key, upd in (overrides or {}).items():
        spec[key].update(upd)
    cfg, traffic = spec["cfg"], spec["traffic"]
    driver = run.load_module(os.path.join(bench_dir, "drivers",
                                          f"{cfg['entry']}.py"),
                             f"bench_driver_{cfg['entry']}")
    state = driver.setup(cfg, traffic, seed, seconds)
    driver.window(state, seconds)
    answers = driver.finish(state)
    if control:
        answers = CONTROLS[cfg["entry"]](answers)
    return driver.compare(answers)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    from common import start_on_tpu
    if start_on_tpu("calibrate") is None:
        return 1
    import run
    bench = run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))
    rows = {"program": [], "control": []}
    for who, seeds in (("program", args.seeds),
                       ("control", args.control_seeds)):
        for seed in (int(s) for s in seeds.split(",")):
            t0 = time.perf_counter()
            got = readings(bench, args.workload, seed, args.seconds,
                           control=who == "control")
            rows[who].append(got)
            print(json.dumps({"who": who, "seed": seed, "readings": got,
                              "s": time.perf_counter() - t0}), flush=True)
    summary = {}
    for k in rows["program"][0]:
        summary[k] = {"program_max": max(r[k] for r in rows["program"]),
                      "control_min": min(r[k] for r in rows["control"])}
    print(json.dumps({"workload": args.workload, "summary": summary}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
