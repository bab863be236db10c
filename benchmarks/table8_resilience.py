"""Table 8 — resilience: the fault-sweep matrix and the guard overhead pin.

Two sections land in BENCH_resilience.json:

- ``fault_matrix``: every instrumented fault site x fault kind, injected
  via :mod:`repro.resilience.faults` and driven through the production
  path it targets.  Each row records whether the fault was *detected*
  (guard / checksum / breaker / warning fired), *recovered* (the call
  still completed), and whether the recovered result matches the
  fault-free reference (``max_rel_err`` <= 1e-6, or exact).  The run
  **asserts zero silent corruptions**: every injected fault must be
  detected-and-recovered-correct or detected-and-reported — a wrong
  answer that looks healthy fails the benchmark, in CI too.
- ``guard_overhead``: interleaved A/B wall time of the guarded executor
  on the fused 2-D path with ``guard_level="off"`` vs ``"basic"`` (the
  production default, a NaN/Inf scan).  The full run measures 1024x1024
  and asserts overhead <= 5%; ``--smoke`` measures 256x256 and only
  records the number.

Usage: ``python -m benchmarks.table8_resilience [--smoke]``
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import plan as plan_lib
from repro.core.complexmath import SplitComplex

from .common import write_json

BENCH_JSON = "BENCH_resilience.json"
DEVICES = 8


def _row(site, kind, detected, recovered, max_rel_err, note=""):
    correct = max_rel_err is not None and max_rel_err <= 1e-6
    row = {"site": site, "kind": kind, "detected": bool(detected),
           "recovered": bool(recovered),
           "result_correct": bool(correct) if max_rel_err is not None
           else None,
           "max_rel_err": max_rel_err, "note": note}
    print(f"table8/{site}/{kind},detected={row['detected']},"
          f"recovered={row['recovered']},correct={row['result_correct']},"
          f"err={max_rel_err}")
    return row


def _rel_err(a: SplitComplex, b: SplitComplex) -> float:
    num = max(float(jnp.max(jnp.abs(a.re - b.re))),
              float(jnp.max(jnp.abs(a.im - b.im))))
    den = max(float(jnp.max(jnp.abs(b.re))), float(jnp.max(jnp.abs(b.im))))
    return num / den


def local_fault_rows(tmpdir: str) -> list:
    """plan/autotune/wisdom/serve sites, in-process."""
    from repro import resilience
    from repro.resilience import config as rcfg
    from repro.resilience import executor, faults, policy

    rows = []
    rng = np.random.default_rng(0)
    x = SplitComplex(jnp.asarray(rng.standard_normal((64, 64)), jnp.float32),
                     jnp.asarray(rng.standard_normal((64, 64)), jnp.float32))

    def fresh():
        resilience.reset()
        plan_lib.clear_plan_cache()
        rcfg.configure(guard_level="full")
        ref = plan_lib.get_plan((64, 64), backend="jnp")._execute(x)
        return plan_lib.get_plan((64, 64), backend="pallas"), ref

    # kernel-launch failure
    pl, ref = fresh()
    key = plan_lib._plan_key((64, 64), jnp.float32, False, "pallas", "c2c")
    with faults.inject("plan.execute", "error") as fp:
        y = pl(x)
    rows.append(_row("plan.execute", "error",
                     detected=executor.stats(key)["failures"] == 1
                     and fp.fired() == 1,
                     recovered=True, max_rel_err=_rel_err(y, ref),
                     note="breaker counted the failure; jnp fallback served"))

    # kernel-output corruption, every array kind
    for kind in ("nan", "inf", "corrupt", "drop"):
        pl, ref = fresh()
        with faults.inject("plan.output", kind):
            y = pl(x)
        st = executor.stats(key)
        rows.append(_row("plan.output", kind,
                         detected=st["failures"] == 1,
                         recovered=True, max_rel_err=_rel_err(y, ref),
                         note=str(st["last_reason"])[:80]))

    # slow candidate during autotune: watchdog excludes it
    resilience.reset()
    plan_lib.clear_plan_cache()
    rcfg.configure(measure_timeout_s=0.5)
    with faults.inject("autotune.measure", "hang", duration=2.0,
                       tag="four_step", times=None):
        tuned = plan_lib.get_plan((64,), backend="jnp", tune=True)
    rows.append(_row("autotune.measure", "hang",
                     detected="four_step" in tuned.tune_report.get(
                         "timeouts", ""),
                     recovered=tuned.tuned
                     and tuned.tune_report["winner"] != "four_step",
                     max_rel_err=0.0,
                     note=f"winner={tuned.tune_report['winner']}"))

    # torn wisdom write: crash mid-save must leave the target intact
    resilience.reset()
    plan_lib.clear_plan_cache()
    path = os.path.join(tmpdir, "wisdom.json")
    plan_lib.get_plan((256,), tune=True)
    plan_lib.save_wisdom(path)
    before = open(path).read()
    crashed = False
    with faults.inject("wisdom.save", "error"):
        try:
            plan_lib.save_wisdom(path)
        except faults.FaultInjected:
            crashed = True
    plan_lib.clear_plan_cache()
    rows.append(_row("wisdom.save", "error",
                     detected=crashed,
                     recovered=open(path).read() == before
                     and plan_lib.load_wisdom(path) == 1,
                     max_rel_err=0.0, note="os.replace atomicity"))

    # serve pre-warm failure: degrade, keep serving, same tokens
    import repro.configs as C
    from repro.models import model as M
    from repro.serve.engine import Engine, ServeConfig

    resilience.reset()
    plan_lib.clear_plan_cache()
    cfg = C.get_config("fnet_demo").reduced()
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    prompt = np.asarray([5, 6, 7], np.int32)
    want = Engine(cfg, ServeConfig(batch_size=2, max_len=64),
                  params).run([(0, prompt)], max_new=4)[0]
    with faults.inject("serve.prewarm", "error"):
        eng = Engine(cfg, ServeConfig(batch_size=2, max_len=64), params)
    got = eng.run([(0, prompt)], max_new=4)[0]
    rows.append(_row("serve.prewarm", "error",
                     detected=eng.degraded,
                     recovered=got == want, max_rel_err=0.0,
                     note=str(eng.degrade_reason)[:60]))

    resilience.reset()
    plan_lib.clear_plan_cache()
    return rows


_DIST_CODE = r"""
import json
import numpy as np
import jax.numpy as jnp
from repro.core.complexmath import SplitComplex
from repro.dist import pencil
from repro.dist._compat import make_mesh
from repro.resilience import faults

mesh = make_mesh((8,), ("data",))
rng = np.random.default_rng(0)
x = SplitComplex(jnp.asarray(rng.standard_normal((64, 64)), jnp.float32),
                 jnp.asarray(rng.standard_normal((64, 64)), jnp.float32))
ref = pencil.pfft2(x, mesh)
rows = []
for kind in ("drop", "corrupt", "nan"):
    pencil.reset_exchange_log()
    with faults.inject("dist.exchange", kind):
        out = pencil.pfft2(x, mesh, verify=True)
    log = pencil.exchange_log()
    err = max(float(np.abs(np.asarray(out.re) - np.asarray(ref.re)).max()),
              float(np.abs(np.asarray(out.im) - np.asarray(ref.im)).max()))
    scale = float(np.abs(np.asarray(ref.re)).max())
    rows.append({"site": "dist.exchange", "kind": kind,
                 "detected": [e["ok"] for e in log] == [False, True],
                 "recovered": True, "max_rel_err": err / scale,
                 "note": "checksum mismatch -> one retry, clean re-run"})
print("TABLE8_JSON " + json.dumps(rows))
"""


def dist_fault_rows(devices: int = DEVICES) -> list:
    env = dict(os.environ)
    # CPU emulation: the child must never reach for a chip this process
    # may hold
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.path.join(repo, "src")
    proc = subprocess.run([sys.executable, "-c", _DIST_CODE], env=env,
                          capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, \
        f"dist subprocess failed:\nSTDOUT:{proc.stdout}\n" \
        f"STDERR:{proc.stderr[-3000:]}"
    rows = []
    for line in proc.stdout.splitlines():
        if line.startswith("TABLE8_JSON "):
            for r in json.loads(line[len("TABLE8_JSON "):]):
                rows.append(_row(r["site"], r["kind"], r["detected"],
                                 r["recovered"], r["max_rel_err"],
                                 r["note"]))
            return rows
    raise AssertionError(f"no TABLE8_JSON line in:\n{proc.stdout}")


def guard_overhead(n: int, *, iters: int = 15) -> dict:
    """Interleaved eager A/B: guarded executor with guards off vs the
    basic NaN/Inf scan, on the fused (n, n) pallas path."""
    import time as _time

    from repro import resilience
    from repro.resilience import config as rcfg

    resilience.reset()
    plan_lib.clear_plan_cache()
    rng = np.random.default_rng(0)
    x = SplitComplex(jnp.asarray(rng.standard_normal((n, n)), jnp.float32),
                     jnp.asarray(rng.standard_normal((n, n)), jnp.float32))
    pl = plan_lib.get_plan((n, n), backend="pallas")

    def run_at(level):
        with rcfg.overrides(guard_level=level):
            t0 = _time.perf_counter()
            jax.block_until_ready(pl(x).re)
            return _time.perf_counter() - t0

    for level in ("off", "basic"):              # warm both traces
        run_at(level)
    t_off, t_basic = [], []
    for _ in range(iters):
        t_off.append(run_at("off"))
        t_basic.append(run_at("basic"))
    us_off = float(np.median(t_off) * 1e6)
    us_basic = float(np.median(t_basic) * 1e6)
    overhead = us_basic / us_off - 1.0
    print(f"table8/guard_overhead_{n},off_us={us_off:.1f},"
          f"basic_us={us_basic:.1f},overhead={overhead * 100:.2f}%")
    resilience.reset()
    plan_lib.clear_plan_cache()
    return {"size": f"{n}x{n}", "us_guard_off": us_off,
            "us_guard_basic": us_basic, "overhead_frac": overhead,
            "acceptance": "<= 0.05 at 1024x1024 (full run)"}


def run(smoke: bool = False) -> dict:
    import tempfile
    with tempfile.TemporaryDirectory() as td:
        rows = local_fault_rows(td)
    rows += dist_fault_rows()

    silent = [r for r in rows
              if not (r["detected"] and r["recovered"]
                      and r["result_correct"] in (True, None))]
    assert not silent, f"SILENT CORRUPTIONS: {silent}"
    matrix = {f"{r['site']}/{r['kind']}": r for r in rows}
    matrix["silent_corruptions"] = 0
    write_json(BENCH_JSON, "fault_matrix", matrix)

    ov = guard_overhead(256 if smoke else 1024)
    if not smoke:
        assert ov["overhead_frac"] <= 0.05, \
            f"guard overhead {ov['overhead_frac']:.3f} > 5%"
    write_json(BENCH_JSON, "guard_overhead", ov)
    return {"fault_matrix": matrix, "guard_overhead": ov}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="small sizes for CI smoke runs")
    args = ap.parse_args()
    run(smoke=args.smoke)


if __name__ == "__main__":
    main()
