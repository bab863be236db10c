"""Open-loop arrivals against ``SpectralServer``, timed from each due time.

Copied from ``repro.serve.spectral.loadgen.open_loop`` and changed where
that one measures the wrong thing:

- payloads come from a pool built at set-up, so the generator's thread
  does no numpy work between sends;
- each request is timed from the moment it was due, so a late generator
  or a stalled server shows in the latency; the generator's lag is
  reported on its own.  A request that gets no answer (refused, timed
  out, failed or never back) counts at the longest the client waits,
  ``RESULT_GRACE_S`` past the window's close, so the tail is that of
  every request due in the window;
- the arrivals are a Poisson process conditioned on its count: the
  window's ``rate * seconds`` requests, each mix item's exact share of
  them, and one fixed set of gaps between them, all in seeded order, so
  every seed offers the same work.  A mix item
  is a kind and a shape; the server gets one bucket per distinct pair.

A collector thread takes each result as it lands (the server keeps a
record until it is read) and keeps a copy of the sampled answers only.
The server's own clock is ``time.perf_counter``; a request's result is on
the client at ``t_submitted + record.latency_s``, with ``t_submitted``
read just after ``submit`` returns, so the latency never reads short.
"""
from __future__ import annotations

import dataclasses
import gc
import queue
import threading
import time

import numpy as np

import reference

WARM_BATCHES = 2          # full batches per bucket sent before the window
START_DELAY_S = 0.05      # first due time after the window opens
RESULT_GRACE_S = 60.0     # how long past the close a result may take


def _payload(rng, kind: str, shape):
    from repro.core.complexmath import SplitComplex
    if kind == "rfft":
        return rng.standard_normal(shape, dtype=np.float32)
    return SplitComplex(rng.standard_normal(shape, dtype=np.float32),
                        rng.standard_normal(shape, dtype=np.float32))


def arrivals(spec: dict, n: int, seconds: float, rng) -> np.ndarray:
    """Sorted due offsets of ``n`` requests in ``[0, seconds)``:
    ``{"process": "poisson", "gap_seed": s}`` is a Poisson process
    conditioned on its count.  Its gaps (those of sorted uniform times)
    are drawn once from ``gap_seed``, and ``rng`` shuffles them: the gaps
    of sorted uniforms are exchangeable, so the shuffle is again such a
    process, and every seed offers the same gaps in another order."""
    if spec["process"] == "poisson":
        fixed = np.random.default_rng(spec["gap_seed"])
        gaps = np.diff(np.sort(fixed.uniform(0.0, seconds, n)),
                       prepend=0.0)
        return np.cumsum(gaps[rng.permutation(n)])
    raise ValueError(f"unknown arrival process {spec['process']!r}")


def schedule(traffic: dict, seconds: float, rng, *, rate=None) -> dict:
    """Due offsets, mix items and pool slots of the window's requests:
    exactly each item's share of ``rate * seconds`` requests, in seeded
    order."""
    rate = traffic["rate_per_s"] if rate is None else rate
    n = max(1, int(round(rate * seconds)))
    mix = traffic["mix"]
    total = float(sum(m["weight"] for m in mix))
    counts = [int(round(n * m["weight"] / total)) for m in mix]
    counts[-1] = n - sum(counts[:-1])
    order = np.repeat(np.arange(len(mix)), counts)[rng.permutation(n)]
    return {"due": arrivals(traffic["arrivals"], n, seconds, rng),
            "item": order,
            "slot": rng.integers(traffic["pool_per_item"], size=n),
            "seconds": seconds}


def make_pool(traffic: dict, rng) -> list:
    """``pool_per_item`` seeded payloads of each mix item."""
    return [[_payload(rng, m["kind"], tuple(m["shape"]))
             for _ in range(traffic["pool_per_item"])]
            for m in traffic["mix"]]


@dataclasses.dataclass
class Outcome:
    latency_s: np.ndarray       # every request, from due to result; one
                                # with no answer at the longest wait
    answered: np.ndarray        # which requests completed
    done_s: np.ndarray          # completed requests' result times
    lag_s: np.ndarray           # generator lag of every send
    rejected: set               # indices the server refused (backpressure)
    not_completed: dict         # terminal status -> count
    never_came: int
    kept: dict                  # request index -> answer copy
    t_open: float
    t_close: float              # last result on the client
    gc_pauses_s: list           # (generation, seconds) of each collection


class GcPauses:
    """Records how long each garbage collection takes while installed."""

    def __init__(self):
        self.pauses, self._t0 = [], None

    def __call__(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._t0))
            self._t0 = None


def _copy(value):
    from repro.core.complexmath import SplitComplex
    if isinstance(value, SplitComplex):
        return SplitComplex(np.array(value.re), np.array(value.im))
    return np.array(value)


def open_loop(srv, mix: list, sched: dict, pool: list, *,
              keep=frozenset()) -> Outcome:
    """Send ``sched``'s requests on time; drain them; time each from its
    due time.  ``keep`` names the request indices whose answers are
    copied for the comparison."""
    from jax.profiler import TraceAnnotation
    from repro.serve.spectral import NoBucketError

    n = len(sched["due"])
    lat = np.full(n, np.nan)
    done_at = np.full(n, np.nan)
    lag = np.zeros(n)
    status = {}
    never = [0]
    kept = {}
    t_done = [0.0]
    work: queue.Queue = queue.Queue()
    t_open = time.perf_counter() + START_DELAY_S
    deadline = t_open + sched["seconds"] + RESULT_GRACE_S

    def collect():
        while True:
            item = work.get()
            if item is None:
                return
            i, t_sent, due = item
            rec = srv.result(i, timeout=max(0.001,
                                            deadline - time.perf_counter()))
            if rec is None:
                never[0] += 1
                continue
            if rec.status != "completed":
                status[rec.status] = status.get(rec.status, 0) + 1
                continue
            done = t_sent + rec.latency_s
            lat[i] = done - due
            done_at[i] = done
            t_done[0] = max(t_done[0], done)
            if i in keep:
                kept[i] = _copy(rec.value)

    collector = threading.Thread(target=collect, name="bench-collect")
    collector.start()
    rejected = set()
    pauses = GcPauses()
    gc.callbacks.append(pauses)
    try:
        for i in range(n):
            due = t_open + sched["due"][i]
            now = time.perf_counter()
            if now < due:
                time.sleep(due - now)
            t0 = time.perf_counter()
            lag[i] = t0 - due
            item = sched["item"][i]
            kind = mix[item]["kind"]
            try:
                with TraceAnnotation("bench.submit"):
                    ok = srv.submit(i, pool[item][sched["slot"][i]],
                                    kind=kind)
            except NoBucketError:
                ok = False
            if ok:
                work.put((i, time.perf_counter(), due))
            else:
                rejected.add(i)
    finally:
        work.put(None)
        collector.join()
        gc.callbacks.remove(pauses)
    answered = ~np.isnan(lat)
    wait = deadline - (t_open + sched["due"])
    return Outcome(latency_s=np.where(answered, lat, wait),
                   answered=answered, done_s=done_at[answered], lag_s=lag,
                   rejected=rejected, not_completed=status,
                   never_came=never[0], kept=kept, t_open=t_open,
                   t_close=max(t_done[0], t_open),
                   gc_pauses_s=pauses.pauses)


def counters(srv) -> dict:
    """Per-bucket counters and histogram sums of the server's snapshot."""
    out = {}
    for lbl, b in srv.snapshot()["buckets"].items():
        c, h = b.get("counters", {}), b.get("latency", {})
        row = {k: c.get(k, 0) for k in ("batches", "batch_items",
                                        "completed", "fallback_served")}
        for name in ("queue", "service"):
            hh = h.get(name, {"count": 0, "mean_ms": 0.0})
            row[f"{name}_n"] = hh["count"]
            row[f"{name}_sum_s"] = hh["mean_ms"] * hh["count"] * 1e-3
        row["max_batch"] = b.get("max_batch", 0)
        out[lbl] = row
    return out


def delta(after: dict, before: dict) -> dict:
    return {lbl: {k: (v - before.get(lbl, {}).get(k, 0)
                      if k != "max_batch" else v)
                  for k, v in row.items()}
            for lbl, row in after.items()}


@dataclasses.dataclass
class State:
    srv: object
    mix: list
    sched: dict
    pool: list
    sample: frozenset
    degraded: list
    diag: dict
    outcome: Outcome = None
    fallback: int = 0


def build_server(cfg: dict, traffic: dict):
    """The server with one bucket per (kind, shape) of the traffic."""
    from repro.serve.spectral import BucketConfig, SpectralServer
    keys = sorted({(m["kind"], tuple(m["shape"])) for m in traffic["mix"]})
    buckets = [BucketConfig(shape, kind=k, dtype=cfg["dtype"],
                            backend=cfg["backend"],
                            max_batch=cfg["max_batch"])
               for k, shape in keys]
    return SpectralServer(buckets, tune=cfg["tune"],
                          clock=time.perf_counter)


def warm(srv, mix: list, pool: list) -> None:
    """Send full batches through every bucket: staging, dispatch and
    copy-back have each run before the window opens."""
    rids = []
    for st in srv.states.values():
        item = next(j for j, m in enumerate(mix)
                    if m["kind"] == st.cfg.kind
                    and tuple(m["shape"]) == st.cfg.shape)
        for j in range(WARM_BATCHES * st.cfg.max_batch):
            rid = f"warm/{st.label}/{j}"
            if srv.submit(rid, pool[item][0], kind=st.cfg.kind):
                rids.append(rid)
    for rid in rids:
        srv.result(rid, timeout=600)


def setup(cfg: dict, traffic: dict, seed: int, seconds: float) -> State:
    rng = np.random.default_rng(seed)
    mix = traffic["mix"]
    pool = make_pool(traffic, rng)
    sched = schedule(traffic, seconds, rng)
    n = len(sched["due"])
    sample = frozenset(int(i) for i in rng.choice(
        n, size=min(traffic["sample"], n), replace=False))
    srv = build_server(cfg, traffic)
    rep = srv.prewarm_report
    diag = {"prewarm_s": {e.label: e.compile_s for e in rep.entries},
            "plans": {lbl: f"{s.plan.backend}/{s.plan.algo}"
                      for lbl, s in srv.states.items()},
            "max_batch": {lbl: s.cfg.max_batch
                          for lbl, s in srv.states.items()}}
    warm(srv, mix, pool)
    return State(srv=srv, mix=mix, sched=sched, pool=pool, sample=sample,
                 degraded=list(srv.degraded_buckets), diag=diag)


def sched_at(state: State, i) -> float:
    """Request ``i``'s due offset in the window, in seconds."""
    return float(state.sched["due"][int(i)])


def longest_gap(out: Outcome) -> dict:
    """The longest time in the window with no result landing on the
    client, and when it began: a server stall shows here even where the
    generator kept sending."""
    t = np.sort(np.concatenate([[out.t_open], out.done_s]))
    if len(t) < 2:
        return {"ms": 0.0, "at_s": 0.0}
    k = int(np.argmax(np.diff(t)))
    return {"ms": float((t[k + 1] - t[k]) * 1e3),
            "at_s": float(t[k] - out.t_open)}


def gc_summary(pauses: list) -> dict:
    """Count, total and longest garbage-collection pause per generation."""
    out = {}
    for gen in sorted({g for g, _ in pauses}):
        d = [p for g, p in pauses if g == gen]
        out[f"gen{gen}"] = {"n": len(d), "total_ms": sum(d) * 1e3,
                            "max_ms": max(d) * 1e3}
    return out


def window(state: State, seconds: float) -> dict:
    before = counters(state.srv)
    out = open_loop(state.srv, state.mix, state.sched, state.pool,
                    keep=state.sample)
    d = delta(counters(state.srv), before)
    state.outcome = out
    fallback = sum(r["fallback_served"] for r in d.values())
    state.fallback = fallback + len(state.degraded)
    n = len(state.sched["due"])
    failed = (len(out.rejected) + sum(out.not_completed.values())
              + out.never_came + fallback)
    lat_ms = out.latency_s * 1e3
    e2e = {"latency_p50_ms": float(np.percentile(lat_ms, 50)),
           "latency_p95_ms": float(np.percentile(lat_ms, 95))}
    completed = int(out.answered.sum())
    diag = dict(state.diag)
    diag.update({
        "requests": n, "completed": completed,
        "rejected": len(out.rejected), "not_completed": out.not_completed,
        "never_came": out.never_came, "fallback_served": fallback,
        "degraded_buckets": state.degraded,
        "offered_per_s": n / seconds,
        "completed_per_s": completed / max(out.t_close - out.t_open, 1e-9),
        "latency_p95_ms": float(np.percentile(lat_ms, 95)),
        "latency_max_ms": float(lat_ms.max()),
        "generator_lag_ms": {
            "p50": float(np.percentile(out.lag_s, 50) * 1e3),
            "p99": float(np.percentile(out.lag_s, 99) * 1e3),
            "max": float(out.lag_s.max() * 1e3),
            "max_at_s": float(sched_at(state, np.argmax(out.lag_s)))},
        "longest_result_gap": longest_gap(out),
        "gc_pauses": gc_summary(out.gc_pauses_s),
    })
    return {"e2e": e2e, "attempted": n, "failed": int(failed),
            "window_s": out.t_close - out.t_open, "t_open": out.t_open,
            "counters": d, "work": {}, "diag": diag}


def finish(state: State) -> dict:
    """Stop the server; hand over the sampled answers and what produced
    them."""
    state.srv.close(timeout_s=RESULT_GRACE_S)
    state.srv = None
    return {"kept": state.outcome.kept,
            "admitted_sample": state.sample - state.outcome.rejected,
            "mix": state.mix, "sched": state.sched, "pool": state.pool,
            "fallback": state.fallback}


def reference_answer(kind: str, payload) -> np.ndarray:
    if kind == "rfft":
        return reference.rfft2(payload)
    return reference.fft2(np.asarray(payload.re, np.float64)
                          + 1j * np.asarray(payload.im, np.float64))


def compare(answers: dict) -> dict:
    """The widest relative L2 gap of a sampled answer against float64
    numpy, the sampled requests admitted whose answer never came (a
    refused request is a failure, not a wrong answer), and the requests
    served by a fallback or a degraded bucket."""
    sched, pool, kept = answers["sched"], answers["pool"], answers["kept"]
    refs, worst = {}, 0.0
    for i in sorted(kept):
        key = int(sched["item"][i]), int(sched["slot"][i])
        if key not in refs:
            refs[key] = reference_answer(answers["mix"][key[0]]["kind"],
                                         pool[key[0]][key[1]])
        v = kept[i]
        got = np.asarray(v.re, np.float64) + 1j * np.asarray(v.im,
                                                             np.float64)
        worst = max(worst, reference.rel_l2(got, refs[key]))
    return {"max_rel_l2": worst,
            "missing_answers": len(answers["admitted_sample"]) - len(kept),
            "fallback": answers["fallback"]}
