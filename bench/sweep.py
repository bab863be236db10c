"""Find a serve cell's knee: the highest offered rate with no growing
backlog, by one sweep of fixed rates on one server.

    python3 bench/sweep.py --workload serve2d.img1024.poisson \\
        --rates 10,20,40 --seconds 8 --seed 1

Each rate runs the cell's open-loop client for ``--seconds`` and prints
one JSON line: offered and completed requests per second, latency p50 and
p95 from the due time, and the p95 of the first and last third of the
requests by due time.  Under the knee the two thirds agree; above it the
queue grows all through the window and the last third reads far higher.
A rate is sustained when it completes at least 95% of its offered rate,
rejects nothing, and its last third's p95 stays under twice the first
third's plus 10 ms; the knee is the highest sustained rate.
``--record`` writes the knee, and the cell's rate at its
``share_of_knee``, into the cell's traffic file.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def thirds_p95(out) -> tuple:
    """p95 latency (ms) of the first and the last third of the requests,
    in due order (one with no answer at the longest wait)."""
    lat = out.latency_s * 1e3
    k = max(1, len(lat) // 3)
    return (float(np.percentile(lat[:k], 95)),
            float(np.percentile(lat[-k:], 95)))


def sustained(row: dict) -> bool:
    return (row["completed_per_s"] >= 0.95 * row["rate_per_s"]
            and row["rejected"] == 0
            and row["p95_last_third_ms"]
            < 2 * row["p95_first_third_ms"] + 10.0)


def knee(rows) -> float:
    """The highest sustained rate (None when none is).  Above the true
    knee no rate is sustained; below it a stall of the host can still fail
    one rate, so the highest sustained one is taken, not the first
    failure."""
    ok = [r["rate_per_s"] for r in rows if sustained(r)]
    return max(ok) if ok else None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--record", action="store_true",
                    help="write the knee and the rate into the traffic file")
    args = ap.parse_args(argv)
    from common import start_on_tpu
    if start_on_tpu("sweep") is None:
        return 1
    import run
    bench = run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))
    spec = run.cell_spec(bench, args.workload)
    drv = run.load_module(os.path.join(HERE, "drivers",
                                       "serve_open_loop.py"), "sweep_drv")
    cfg, traffic = spec["cfg"], spec["traffic"]
    rng = np.random.default_rng(args.seed)
    mix = traffic["mix"]
    pool = drv.make_pool(traffic, rng)
    srv = drv.build_server(cfg, traffic)
    rows = []
    try:
        drv.warm(srv, mix, pool)
        for rate in (float(r) for r in args.rates.split(",")):
            sched = drv.schedule(traffic, args.seconds, rng, rate=rate)
            out = drv.open_loop(srv, mix, sched, pool)
            lat = out.latency_s * 1e3
            first, last = thirds_p95(out)
            n = len(sched["due"])
            rows.append({
                "rate_per_s": rate, "requests": n,
                "completed": int(out.answered.sum()),
                "rejected": len(out.rejected),
                "completed_per_s": (int(out.answered.sum())
                                    / (out.t_close - out.t_open)),
                "p50_ms": float(np.percentile(lat, 50)),
                "p95_ms": float(np.percentile(lat, 95)),
                "p95_first_third_ms": first, "p95_last_third_ms": last,
                "lag_p99_ms": float(np.percentile(out.lag_s, 99) * 1e3),
            })
            print(json.dumps(rows[-1]), flush=True)
    finally:
        srv.close()
    k = knee(rows)
    rate = None if k is None else round(traffic["share_of_knee"] * k, 1)
    print(json.dumps({"knee_per_s": k, "rate_per_s": rate}), flush=True)
    if args.record and k is not None:
        path = os.path.join(HERE, "traffic", f"{args.workload}.json")
        with open(path) as f:
            data = json.load(f)
        data.update(knee_per_s=k, rate_per_s=rate)
        with open(path, "w") as f:
            json.dump(data, f, indent=2)
            f.write("\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
