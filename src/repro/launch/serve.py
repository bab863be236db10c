"""Serving driver: LM decode through the slot-based engine, or spectral
transforms through the continuous-batching spectral server.

``python -m repro.launch.serve --arch h2o-danube-1.8b --reduced`` serves a
reduced model with synthetic prompts on local devices.

``python -m repro.launch.serve --workload spectral --buckets 64x64,128x128``
stands up a :class:`repro.serve.spectral.SpectralServer` over the named
shape buckets (c2c + rfft per shape) and drives a closed-loop ragged mix
through it, printing throughput, tail latency and the per-bucket snapshot.
"""
from __future__ import annotations

import argparse
import time


def _parse_buckets(spec: str):
    shapes = []
    for part in spec.split(","):
        dims = tuple(int(d) for d in part.lower().split("x"))
        if len(dims) not in (1, 2):
            raise SystemExit(f"--buckets wants NxM or N entries, got {part}")
        shapes.append(dims)
    return shapes


def spectral_buckets(spec: str):
    """The serving buckets for a ``--buckets`` spec: c2c + rfft per 2-D
    shape, c2c per 1-D one (fp32, ``backend="pallas"``)."""
    from repro.serve.spectral import BucketConfig
    return [BucketConfig(s, kind=k) for s in _parse_buckets(spec)
            for k in ("c2c", "rfft") if len(s) == 2 or k == "c2c"]


def _spectral_main(args) -> None:
    from repro.serve.spectral import (MixItem, SpectralServer, closed_loop,
                                      open_loop)

    buckets = spectral_buckets(args.buckets)
    mix = [MixItem(b.shape, b.kind, inverse=b.inverse) for b in buckets]
    with SpectralServer(buckets, unmatched=args.unmatched) as srv:
        rep = srv.prewarm_report
        print(f"[serve] spectral: {len(buckets)} buckets pre-warmed in "
              f"{rep.total_s:.2f}s"
              + (f", degraded: {rep.degraded}" if rep.degraded else ""))
        if args.qps > 0:
            res = open_loop(srv, mix, qps=args.qps,
                            duration_s=args.duration, seed=0)
        else:
            res = closed_loop(srv, mix, requests=args.requests,
                              concurrency=args.batch_size, seed=0)
        print(f"[serve] {res['completed']} completed "
              f"({res['achieved_qps']:.1f} req/s), "
              f"p50={res['p50_ms']:.1f}ms p99={res['p99_ms']:.1f}ms, "
              f"rejected={res['rejected']} timed_out={res['timed_out']}")
        snap = srv.snapshot()
        for lbl in sorted(snap["buckets"]):
            c = snap["buckets"][lbl]["counters"]
            if c["admitted"]:
                print(f"[serve]   {lbl}: admitted={c['admitted']} "
                      f"completed={c['completed']} "
                      f"fallback={c['fallback_served']}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", choices=("lm", "spectral"), default="lm")
    ap.add_argument("--arch", default="h2o-danube-1.8b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch-size", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--buckets", default="64x64,128x128",
                    help="spectral: comma-separated bucket shapes (NxM)")
    ap.add_argument("--unmatched", choices=("reject", "pad_up"),
                    default="reject")
    ap.add_argument("--qps", type=float, default=0.0,
                    help="spectral: >0 switches to open-loop at this rate")
    ap.add_argument("--duration", type=float, default=5.0,
                    help="spectral: open-loop duration (seconds)")
    args = ap.parse_args()

    from . import compile_cache
    compile_cache.enable()
    if args.workload == "spectral":
        _spectral_main(args)
        return

    import jax
    import numpy as np
    import repro.configs as C
    from repro.models import model as M
    from repro.serve.engine import Engine, ServeConfig

    cfg = C.get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    eng = Engine(cfg, ServeConfig(batch_size=args.batch_size,
                                  max_len=args.max_len,
                                  temperature=args.temperature), params)
    rng = np.random.default_rng(0)
    reqs = [(i, rng.integers(0, cfg.vocab_size, size=rng.integers(4, 12))
             .astype(np.int32)) for i in range(args.requests)]
    t0 = time.time()
    out = eng.run(reqs, max_new=args.max_new)
    dt = time.time() - t0
    total = sum(len(v) for v in out.values())
    print(f"[serve] {len(out)} requests, {total} tokens in {dt:.1f}s "
          f"({total/dt:.1f} tok/s)")
    for rid in sorted(out)[:4]:
        print(f"[serve] req {rid}: {out[rid][:12]}")


if __name__ == "__main__":
    main()
