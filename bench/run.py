"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Everything about a cell is found by name: the cell in ``BENCHMARK.json``,
its configuration file, ``bench/traffic/<cell>.json``,
``bench/limits/<cell>.json``, the driver ``bench/drivers/<entry>.py`` the
configuration names, and one reader ``bench/metrics/<metric>.py`` per
per-layer metric.  A driver builds the system and warms only its own
shapes (``setup``), runs the measured window (``window``), hands over the
sampled answers and frees the device (``finish``), and compares them with
the float64 reference (``compare``).

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiler trace of the whole window.  The last
line of standard output is the result object; the numbers compared for
``correct`` close standard error, each beside its limit.  Without a TPU,
or with fewer chips than the cell asks for, the run exits non-zero and
prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()          # set-up is counted from here

import argparse                         # noqa: E402
import importlib.util                   # noqa: E402
import json                             # noqa: E402
import os                               # noqa: E402
import shutil                           # noqa: E402
import sys                              # noqa: E402
import tempfile                         # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/core/compile/jaxpr_trace_duration")


def log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def cell_spec(bench: dict, name: str, *, bench_dir: str = HERE,
              root: str = ROOT) -> dict:
    """The cell ``name`` with its configuration, traffic and limits."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    config = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    return {"cell": cell, "config": config,
            "cfg": load_json(os.path.join(root, config["file"])),
            "traffic": load_json(os.path.join(bench_dir, "traffic",
                                              f"{name}.json")),
            "limits": load_json(os.path.join(bench_dir, "limits",
                                             f"{name}.json"))}


def metrics_for(bench: dict, cell: str, trace: bool) -> list:
    """The cell's end-to-end metrics, or its per-layer ones."""
    entries = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in entries if cell in m.get("workloads", [cell])]


class CompileCounter:
    """Counts JAX traces and backend compiles while ``active``."""

    def __init__(self):
        self.active = False
        self.counts = {e: 0 for e in COMPILE_EVENTS}

    def __call__(self, event, duration, **_kw):
        if self.active and event in self.counts:
            self.counts[event] += 1


def judge(readings: dict, limits: dict) -> dict:
    """Each number compared beside its limit; a number must not exceed
    its limit."""
    missing = sorted(set(limits) - set(readings))
    if missing:
        raise KeyError(f"no reading for the limits {missing}")
    return {k: {"value": readings[k], "limit": limits[k]["max"],
                "ok": bool(readings[k] <= limits[k]["max"])}
            for k in limits}


def read_layer(bench_dir: str, metric: dict, ctx: dict):
    """A per-layer metric's value from its reader, or None when the reader
    found nothing to read."""
    mod = load_module(os.path.join(bench_dir, "metrics",
                                   f"{metric['name']}.py"),
                      f"bench_metric_{metric['name'].replace('.', '_')}")
    got = mod.read(ctx)
    if got is None:
        return None
    out = dict(got) if isinstance(got, dict) else {"value": got}
    out["unit"] = metric["unit"]
    return out


def run_cell(bench: dict, name: str, seed: int, seconds: float,
             trace: bool, *, bench_dir: str = HERE, root: str = ROOT,
             t_start: float = None, overrides: dict = None) -> dict:
    """Set up, measure, check and report one run of cell ``name``.
    ``overrides`` updates the configuration (``cfg``) and traffic
    (``traffic``) as loaded: the tests shrink a cell to a CPU's size."""
    import jax
    import trace_reduce

    t_start = T_START if t_start is None else t_start
    spec = cell_spec(bench, name, bench_dir=bench_dir, root=root)
    cfg, traffic = spec["cfg"], spec["traffic"]
    for key, upd in (overrides or {}).items():
        spec[key].update(upd)
    driver = load_module(os.path.join(bench_dir, "drivers",
                                      f"{cfg['entry']}.py"),
                         f"bench_driver_{cfg['entry']}")
    devices = jax.devices()[:cfg["chips"]]
    counter = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)
    try:
        t_driver = time.perf_counter()
        state = driver.setup(cfg, traffic, seed, seconds)
        log_dir = None
        if trace:
            log_dir = tempfile.mkdtemp(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(log_dir, profiler_options=opts)
        counter.active = True
        try:
            with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
                win = driver.window(state, seconds)
        finally:
            counter.active = False
            if trace:
                jax.profiler.stop_trace()
    finally:
        jax.monitoring.unregister_event_duration_listener(counter)
    setup_s = win["t_open"] - t_start
    red = None
    if trace:
        try:
            events = trace_reduce.load(trace_reduce.find_xplane(log_dir))
        finally:
            shutil.rmtree(log_dir, ignore_errors=True)
        red = trace_reduce.reduce(events, win["window_s"])
    from common import memory_peak_bytes
    mem = memory_peak_bytes(devices)
    answers = driver.finish(state)
    del state
    checks = judge(driver.compare(answers), spec["limits"])

    diag = dict(win["diag"])
    diag["compiles_in_window"] = counter.counts[COMPILE_EVENTS[0]]
    diag["traces_in_window"] = counter.counts[COMPILE_EVENTS[1]]
    diag["setup_s"] = setup_s
    # process start, imports and the TPU runtime's start, before the
    # driver builds anything
    diag["setup_before_driver_s"] = t_driver - t_start
    log("diagnostics " + json.dumps(diag, sort_keys=True, default=str))

    metrics = {}
    if trace:
        ctx = {"trace": red, "window": win, "cfg": cfg, "traffic": traffic,
               "device_kind": devices[0].device_kind}
        for m in metrics_for(bench, name, True):
            got = read_layer(bench_dir, m, ctx)
            if got is not None:
                metrics[m["name"]] = got
    else:
        e2e = dict(win["e2e"], setup_s=setup_s)
        for m in metrics_for(bench, name, False):
            if m["name"] in e2e:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "device_kind": d0.device_kind, "count": len(jax.devices()),
              "memory_peak_bytes": mem}
    result = {"correct": all(c["ok"] for c in checks.values()),
              "attempted": win["attempted"], "failed": win["failed"],
              "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        result["breakdown"] = red["breakdown"]
    result["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                        for k, c in checks.items()}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from common import CACHE_DIR, start_on_tpu
    devs = start_on_tpu("bench")
    if devs is None:
        return 1
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    spec = cell_spec(bench, args.workload)
    chips = spec["cfg"]["chips"]
    if len(devs) < chips:
        log(f"{args.workload} needs {chips} chips, JAX sees {len(devs)}")
        return 1
    log(f"{args.workload} seed={args.seed} seconds={args.seconds} "
        f"trace={args.trace} on {len(devs)} x {devs[0].device_kind}; "
        f"compile cache at {CACHE_DIR}")
    result = run_cell(bench, args.workload, args.seed, args.seconds,
                      bool(args.trace))
    for k, c in result["checks"].items():
        print(f"[bench] check {k} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
