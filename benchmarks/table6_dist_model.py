"""Table 6 — distributed pencil FFTs, model-verified wire traffic.

The ROADMAP's "halve the all_to_all bytes" follow-on as numbers: the
real-input :func:`repro.dist.pencil.prfft2` exchanges W/2 packed pencils
where :func:`~repro.dist.pencil.pfft2` exchanges W, so both the *measured*
per-device wire bytes (the pencil wire log, priced by
``compression.wire_bytes``) and the *predicted* exchange bytes
(:func:`repro.tt.trace.trace_dist` on the multi-chip hop table) must show
~(N/2+1)/N ~ 0.5 of the complex schedule's exchange traffic.

Three sections land in BENCH_dist_model.json:

- ``predicted``  trace_dist rows per (size, schedule, wire format, arch):
                 wall time, energy, per-device exchange wire bytes.
- ``measured``   an 8-emulated-device subprocess runs the real pfft2 /
                 prfft2, recording wall time and the wire log.
- ``ranking``    measured-vs-predicted agreement: wire-byte ratios match
                 exactly (same ``wire_bytes`` pricing on both sides) and
                 the wire ordering always ranks prfft2 cheaper.

``--smoke`` shrinks sizes for CI; the full run covers the 512/1024 rows
the regression tests pin.

Usage: ``python -m benchmarks.table6_dist_model [--smoke]``
"""
from __future__ import annotations

import argparse
import json
import math
import os
import subprocess
import sys

from repro.tt import report as ttreport
from repro.tt import trace as tttrace
from .common import write_json

BENCH_JSON = "BENCH_dist_model.json"

DEVICES = 8
MODEL_ARCHS = ("wormhole_n300", "tpu_v5e")

_MEASURE_CODE = r"""
import json, time
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core.complexmath import SplitComplex
from repro.dist import pencil
from repro.launch.mesh import make_mesh

sizes = %(sizes)r
methods = %(methods)r
mesh = make_mesh((%(devices)d,), ("data",))
rng = np.random.default_rng(0)
out = {}
for n in sizes:
    x = rng.standard_normal((n, n)).astype(np.float32)
    sh = NamedSharding(mesh, P("data", None))
    xr = jax.device_put(jnp.asarray(x), sh)
    xc = SplitComplex(xr, jnp.zeros_like(xr))
    row = {}
    for method in methods:
        for kind in ("pfft2", "prfft2"):
            fn = (lambda m=method: pencil.prfft2(xr, mesh, "data",
                                                 compress=m)) \
                if kind == "prfft2" else \
                (lambda m=method: pencil.pfft2(xc, mesh, "data", compress=m))
            pencil.reset_wire_log()
            y = fn()
            jax.block_until_ready((y.re, y.im))
            wire = pencil.logged_exchange_bytes()
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                y = fn()
                jax.block_until_ready((y.re, y.im))
                best = min(best, time.perf_counter() - t0)
            row[f"{kind}/{method}"] = {"us": best * 1e6, "wire_bytes": wire}
    out[f"{n}x{n}"] = row
print("TABLE6_JSON " + json.dumps(out))
"""


def predicted_rows(sizes, *, devices: int = DEVICES, archs=MODEL_ARCHS,
                   methods=("none",)) -> dict:
    """Pure-model section: trace_dist per (size, schedule, method, arch).
    No devices needed — this is what tests/test_tt_model.py pins."""
    out = {}
    for n in sizes:
        row = {}
        for arch in archs:
            for method in methods:
                for kind, real in (("pfft2", False), ("prfft2", True)):
                    t = tttrace.trace_dist((n, n), devices=devices,
                                           arch=arch, real=real,
                                           method=method)
                    row[f"{kind}/{method}/{arch}"] = {
                        "us": t.seconds * 1e6,
                        "exchange_wire_bytes": t.exchange_wire_bytes,
                        "energy_j": t.energy_j,
                        "stages": [s.name for s in t.stages],
                    }
                a = row[f"pfft2/{method}/{arch}"]
                b = row[f"prfft2/{method}/{arch}"]
                row[f"wire_ratio/{method}/{arch}"] = \
                    b["exchange_wire_bytes"] / a["exchange_wire_bytes"]
        out[f"{n}x{n}"] = row
    return out


def measured_rows(sizes, *, devices: int = DEVICES,
                  methods=("none",)) -> dict:
    """Run the actual pencil transforms on emulated devices (subprocess so
    this process's single-device jax stays untouched)."""
    env = dict(os.environ)
    # CPU emulation: the child must never reach for a chip this process
    # may hold
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.path.join(repo, "src")
    code = _MEASURE_CODE % {"sizes": tuple(sizes), "methods": tuple(methods),
                            "devices": devices}
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, \
        f"measure subprocess failed:\nSTDOUT:{proc.stdout}\n" \
        f"STDERR:{proc.stderr[-3000:]}"
    for line in proc.stdout.splitlines():
        if line.startswith("TABLE6_JSON "):
            return json.loads(line[len("TABLE6_JSON "):])
    raise AssertionError(f"no TABLE6_JSON line in:\n{proc.stdout}")


def ranking_rows(sizes, predicted: dict, measured: dict,
                 methods=("none",)) -> dict:
    """Measured-vs-predicted agreement per size: the wire-byte ratio and
    the "which schedule ships fewer bytes" ordering."""
    out = {}
    for n in sizes:
        key = f"{n}x{n}"
        m = measured[key]
        row = {}
        for method in methods:
            m_ratio = m[f"prfft2/{method}"]["wire_bytes"] \
                / m[f"pfft2/{method}"]["wire_bytes"]
            bound = math.ceil((n // 2 + 1) / n * m[f"pfft2/{method}"]
                              ["wire_bytes"])
            row[f"measured_wire_ratio/{method}"] = m_ratio
            row[f"halved_bound_holds/{method}"] = \
                m[f"prfft2/{method}"]["wire_bytes"] <= bound
            for arch in MODEL_ARCHS:
                p_ratio = predicted[key][f"wire_ratio/{method}/{arch}"]
                row[f"predicted_wire_ratio/{method}/{arch}"] = p_ratio
                row[f"wire_ratio_agrees/{method}/{arch}"] = \
                    abs(p_ratio - m_ratio) < 1e-9
                row[f"wire_order_agrees/{method}/{arch}"] = \
                    (p_ratio < 1.0) == (m_ratio < 1.0)
        out[key] = row
        print(f"table6/rank_{n}: measured_ratio="
              f"{row['measured_wire_ratio/none']:.3f} agree="
              f"{[row[f'wire_ratio_agrees/none/{a}'] for a in MODEL_ARCHS]}")
    return out


def run(smoke: bool = False) -> dict:
    sizes = (128, 256) if smoke else (512, 1024)
    methods = ("none", "bf16") if smoke else ("none", "bf16", "int8")
    predicted = predicted_rows(sizes, methods=methods)
    write_json(BENCH_JSON, "predicted", predicted)
    print(ttreport.dist_markdown_table(ttreport.dist_compare(sizes)))
    measured = measured_rows(sizes, methods=methods)
    write_json(BENCH_JSON, "measured", measured)
    ranking = ranking_rows(sizes, predicted, measured, methods=methods)
    write_json(BENCH_JSON, "ranking", ranking)
    return {"predicted": predicted, "measured": measured, "ranking": ranking}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="small sizes for CI smoke runs")
    args = ap.parse_args()
    run(smoke=args.smoke)


if __name__ == "__main__":
    main()
