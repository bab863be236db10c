"""Chip smoke test: 2-D FFT serving on a TPU through compiled Pallas kernels.

    python chip_smoke.py             # one chip: SpectralServer, 4 buckets
    python chip_smoke.py --chips 4   # pencil pfft2/prfft2/pfilter2, 4 chips

One chip: builds the ``SpectralServer`` that ``python -m repro.launch.serve
--workload spectral --buckets 256x256,1024x1024`` builds (c2c and rfft,
fp32, ``backend="pallas"``, no tuning, no wisdom), checks that every
bucket is served by the fused Pallas kernel compiled into its program,
sends seeded requests and compares each answer with float64 numpy.  It
then runs the bf16 compensated kernel and both inverse kernels through
the plan registry.  Any degraded bucket, any fallback to the jnp
schedule, any missed error bound or any exception fails the run.

Four chips: the pencil transforms ``repro.dist.pencil.pfft2`` and
``prfft2`` on a 4096x4096 fp32 input whose rows are sharded over a
4-device mesh, and ``pfilter2`` (prfft2 -> Helmholtz operator -> pirfft2)
on a stack of 8 such fields, against float64 numpy, and nothing else.

The last line of standard output is the JSON verdict
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``;
a failed run exits non-zero and never prints it.  Without a TPU the
script exits non-zero before running anything.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
SERVE_BUCKETS = "256x256,1024x1024"
FP32_BOUND = 1e-5            # relative L2 vs float64 numpy
BF16_BOUND = 5e-3            # compensated bf16, same metric


class SmokeFailure(Exception):
    pass


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def rel_l2(got, ref) -> float:
    ref = np.asarray(ref, np.complex128)
    return float(np.linalg.norm(np.asarray(got, np.complex128) - ref)
                 / np.linalg.norm(ref))


def as_complex(sc) -> np.ndarray:
    return np.asarray(sc.re, np.float64) + 1j * np.asarray(sc.im, np.float64)


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


# -- one chip: the serving path ----------------------------------------------

def serve_phase(buckets: str = SERVE_BUCKETS, *, requests: int = 3,
                seed: int = 0) -> None:
    """SpectralServer over ``buckets``: plan, compiled kernel, answers and
    fallback counters of every bucket."""
    import jax
    from repro.launch.serve import spectral_buckets
    from repro.resilience import executor as rexec
    from repro.serve.spectral import SpectralServer
    from repro.serve.spectral.executor import zeros_input

    on_tpu = jax.default_backend() == "tpu"
    rng = np.random.default_rng(seed)
    with SpectralServer(spectral_buckets(buckets), tune=False) as srv:
        rep = srv.prewarm_report
        check(not rep.degraded, f"degraded at pre-warm: {rep.degraded}")
        check(not srv.degraded_buckets,
              f"degraded buckets: {srv.degraded_buckets}")
        compile_s = {e.label: e.compile_s for e in rep.entries}
        for lbl, st in srv.states.items():
            plan = st.plan
            check(plan.backend == "pallas" and plan.algo == "fused",
                  f"{lbl}: plan is {plan.backend}/{plan.algo}, "
                  "not pallas/fused")
            hlo = st.fn.lower(zeros_input(st)).compile().as_text()
            kernel = "tpu_custom_call" in hlo
            check(kernel or not on_tpu,
                  f"{lbl}: no Pallas kernel in the compiled program")
            payloads, rids = [], []
            for i in range(requests):
                x = rng.standard_normal(st.cfg.shape)
                if st.cfg.kind == "c2c":
                    x = x + 1j * rng.standard_normal(st.cfg.shape)
                    x = x.astype(np.complex64)
                else:
                    x = x.astype(np.float32)
                rid = f"{lbl}#{i}"
                check(srv.submit(rid, x, kind=st.cfg.kind),
                      f"{rid}: rejected by backpressure")
                payloads.append(x)
                rids.append(rid)
            errs, lat = [], []
            for rid, x in zip(rids, payloads):
                rec = srv.result(rid, timeout=600)
                check(rec is not None and rec.status == "completed",
                      f"{rid}: {None if rec is None else rec.status} "
                      f"{'' if rec is None else rec.error}")
                x64 = x.astype(np.complex128 if st.cfg.kind == "c2c"
                               else np.float64)
                ref = np.fft.fft2(x64) if st.cfg.kind == "c2c" \
                    else np.fft.rfft2(x64)
                got = as_complex(rec.value)
                check(got.shape == ref.shape and np.isfinite(got).all(),
                      f"{rid}: shape {got.shape} vs {ref.shape} or "
                      "non-finite values")
                errs.append(rel_l2(got, ref))
                lat.append(rec.latency_s)
            err = max(errs)
            log(f"{lbl}: plan={plan.backend}/{plan.algo} "
                f"block_batch={plan.block_batch} "
                f"max_batch={st.cfg.max_batch} kernel_compiled={kernel} "
                f"compile_s={compile_s[lbl]:.2f} max_rel_l2={err:.3e} "
                f"p50_ms={1e3 * float(np.median(lat)):.2f} "
                f"(host clock, {requests} requests)")
            check(err <= FP32_BOUND,
                  f"{lbl}: relative L2 error {err:.3e} > {FP32_BOUND}")
        snap = srv.snapshot()
        for lbl, b in snap["buckets"].items():
            fb = b.get("counters", {}).get("fallback_served", 0)
            check(fb == 0, f"{lbl}: {fb} requests served by the fallback")
            res = b.get("resilience") or {}
            check(not res.get("fallbacks"),
                  f"{lbl}: resilience fallbacks {res}")
    for key, st in rexec.stats().items():
        check(st["fallbacks"] == 0 and st["failures"] == 0,
              f"guarded executor fell back on {key}: {st}")


def registry_phase(sizes=(256, 1024), *, seed: int = 1) -> None:
    """The bf16 compensated c2c kernel and both fp32 inverse kernels,
    eager through the plan registry and its guarded executor."""
    import jax.numpy as jnp
    from repro.core import plan as plan_lib
    from repro.core.complexmath import SplitComplex
    from repro.resilience import executor as rexec

    rng = np.random.default_rng(seed)
    for n in sizes:
        zr, zi = rng.standard_normal((2, n, n)), rng.standard_normal((2, n, n))
        z = zr + 1j * zi
        cases = (
            ("c2c/bf16/f", jnp.bfloat16, False, "c2c",
             SplitComplex(jnp.asarray(zr, jnp.bfloat16),
                          jnp.asarray(zi, jnp.bfloat16)),
             np.fft.fft2(z), BF16_BOUND),
            ("c2c/fp32/i", jnp.float32, True, "c2c",
             SplitComplex(jnp.asarray(zr, jnp.float32),
                          jnp.asarray(zi, jnp.float32)),
             np.fft.ifft2(z), FP32_BOUND),
        )
        spec = np.fft.rfft2(zr)
        cases += (("rfft/fp32/i", jnp.float32, True, "rfft",
                   SplitComplex(jnp.asarray(spec.real, jnp.float32),
                                jnp.asarray(spec.imag, jnp.float32)),
                   zr, FP32_BOUND),)
        for name, dt, inverse, kind, x, ref, bound in cases:
            plan = plan_lib.get_plan((n, n), dtype=dt, inverse=inverse,
                                     kind=kind, backend="pallas")
            check(plan.backend == "pallas" and plan.algo == "fused",
                  f"{name}/{n}: plan is {plan.backend}/{plan.algo}")
            y = plan(x)
            got = np.asarray(y, np.float64) if kind == "rfft" \
                else as_complex(y)
            err = rel_l2(got, ref)
            log(f"{name}/{n}x{n}: plan={plan.backend}/{plan.algo} "
                f"variant={plan.variant} rel_l2={err:.3e} (bound {bound})")
            check(err <= bound, f"{name}/{n}: relative L2 {err:.3e} > "
                  f"{bound}")
    for key, st in rexec.stats().items():
        check(st["fallbacks"] == 0 and st["failures"] == 0,
              f"guarded executor fell back on {key}: {st}")


# -- four chips: the pencil transforms ---------------------------------------

def pencil_phase(n: int = 4096, *, chips: int = 4, fields: int = 8,
                 seed: int = 2) -> None:
    """pfft2 / prfft2 (default ``backend="jnp"``) with rows sharded over a
    ``chips``-device mesh built from ``jax.devices()``, then ``pfilter2`` on
    a stack of ``fields``."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.complexmath import SplitComplex
    from repro.dist import pencil
    from repro.dist._compat import make_mesh

    check(len(jax.devices()) >= chips,
          f"{chips} devices needed, JAX sees {len(jax.devices())}")
    mesh = make_mesh((chips,), ("data",))
    rows = NamedSharding(mesh, P("data", None))
    rng = np.random.default_rng(seed)
    xr = rng.standard_normal((n, n)).astype(np.float32)
    xi = rng.standard_normal((n, n)).astype(np.float32)

    def devices_of(a, what):
        devs = {s.device for s in a.addressable_shards}
        shapes = {s.data.shape for s in a.addressable_shards}
        check(len(devs) == chips and len(shapes) == 1
              and next(iter(shapes))[-2] * chips == a.shape[-2],
              f"{what}: shards {sorted(map(str, devs))} of shapes {shapes} "
              f"are not {chips} row blocks on distinct devices")
        return len(devs)

    x = SplitComplex(jax.device_put(jnp.asarray(xr), rows),
                     jax.device_put(jnp.asarray(xi), rows))
    devices_of(x.re, "pfft2 input")
    t0 = time.perf_counter()
    y = pencil.pfft2(x, mesh)                    # (W, H) transposed output
    jax.block_until_ready(y)
    dt = time.perf_counter() - t0
    nd = devices_of(y.re, "pfft2 output")
    ref = np.fft.fft2(xr.astype(np.float64) + 1j * xi).T
    err = rel_l2(as_complex(y), ref)
    log(f"pfft2 {n}x{n} fp32 on {nd} devices: rel_l2={err:.3e} "
        f"first_call_s={dt:.2f} (includes compile)")
    check(err <= FP32_BOUND, f"pfft2: relative L2 {err:.3e} > {FP32_BOUND}")

    xd = jax.device_put(jnp.asarray(xr), rows)
    devices_of(xd, "prfft2 input")
    t0 = time.perf_counter()
    yp = pencil.prfft2(xd, mesh)                 # packed (W/2, H)
    jax.block_until_ready(yp)
    dt = time.perf_counter() - t0
    nd = devices_of(yp.re, "prfft2 output")
    got = as_complex(pencil.unpack_half_spectrum(yp))
    ref = np.fft.rfft2(xr.astype(np.float64)).T
    err = rel_l2(got, ref)
    log(f"prfft2 {n}x{n} fp32 on {nd} devices: rel_l2={err:.3e} "
        f"first_call_s={dt:.2f} (includes compile)")
    check(err <= FP32_BOUND, f"prfft2: relative L2 {err:.3e} > {FP32_BOUND}")

    # pfilter2, the pencil cell's step: a stack of fields through prfft2 ->
    # Helmholtz operator (DC and Nyquist columns differ) -> pirfft2
    nu_dt = 1e-5
    ky = np.fft.fftfreq(n, 1.0 / n)[:, None]
    kx = np.fft.rfftfreq(n, 1.0 / n)[None, :]
    g = (1.0 / (1.0 + nu_dt * (ky ** 2 + kx ** 2))).astype(np.float32)
    xb = rng.standard_normal((fields, n, n)).astype(np.float32)
    xs = jax.device_put(jnp.asarray(xb),
                        NamedSharding(mesh, P(None, "data", None)))
    op = pencil.shard_half_operator(g, mesh)
    t0 = time.perf_counter()
    out = jax.jit(lambda a, o: pencil.pfilter2(a, o, mesh))(xs, op)
    jax.block_until_ready(out)
    dt = time.perf_counter() - t0
    nd = devices_of(out, "pfilter2 output")
    x64 = xb.astype(np.float64)
    ref = np.fft.irfft2(np.fft.rfft2(x64) * g.astype(np.float64), s=(n, n))
    err = float(np.linalg.norm(np.asarray(out, np.float64) - ref)
                / np.linalg.norm(ref))
    log(f"pfilter2 {fields}x{n}x{n} fp32 on {nd} devices: rel_l2={err:.3e} "
        f"first_call_s={dt:.2f} (includes compile)")
    check(err <= FP32_BOUND,
          f"pfilter2: relative L2 {err:.3e} > {FP32_BOUND}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the pencil transforms on a 4-chip "
                         "mesh")
    args = ap.parse_args(argv)

    if os.environ.get("REPRO_FFT_WISDOM"):
        log("REPRO_FFT_WISDOM is set: the smoke runs on untuned plans only")
        return 2
    src = os.path.join(HERE, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        log(f"no repro package under {src}: run from a checkout")
        return 2
    sys.path.insert(0, src)

    import jax
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if device["platform"] != "tpu":
        log(f"no TPU: JAX runs on {device['platform']}; nothing was run")
        return 1
    from repro.launch import compile_cache
    log(f"device {device}; compile cache at {compile_cache.enable()}")
    try:
        t0 = time.perf_counter()
        if args.chips == 4:
            pencil_phase(chips=4)
        else:
            serve_phase()
            registry_phase()
        log(f"all phases passed in {time.perf_counter() - t0:.1f}s")
    except Exception as e:      # noqa: BLE001 — any failure fails the smoke
        traceback.print_exc()
        log(f"FAIL: {type(e).__name__}: {e}")
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
