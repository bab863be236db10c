"""Reduce a ``jax.profiler`` trace to device metrics.

:func:`load` reads the ``.xplane.pb`` into plain event lists; :func:`reduce`
turns them into

- device busy time, the union of the op intervals on each device's
  ``XLA Ops`` line, averaged over the devices, and the idle share of the
  traced window (the harness's ``bench.window`` host span);
- device time and call count per op name (the HLO instruction's name:
  the Pallas kernels show as ``fft2d_gemm.N``, ``rfft2d_fused.N``,
  ``irfft2d_fused.N``);
- all-to-all time, and the part of it during which no other op runs on
  that device;
- ``breakdown``: the ops that took most device time, and the longest idle
  gaps labelled by the host activity that overlaps them most.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict

OPS_LINE = "XLA Ops"
WINDOW = "bench.window"       # host span the harness puts round the window
A2A_MARKS = ("all-to-all", "all_to_all", "alltoall")
TOP = 10


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _is_device(plane_name: str) -> bool:
    return plane_name.startswith("/device:") and \
        not plane_name.startswith("/device:CPU")


def op_name(text: str) -> str:
    """An op's name from its trace event, which on the TPU is the whole
    HLO instruction (``%fft2d_gemm.2 = (f32[...]) custom-call(...)``)."""
    return text.split(" = ", 1)[0].lstrip("%")


def from_profile(pd) -> dict:
    """Event lists of a ``jax.profiler.ProfileData``: ``devices`` maps each
    device plane to its op events ``(name, start_ns, end_ns)``; ``host``
    holds every host event ``(name, start_ns, end_ns, thread)``."""
    devices, host = {}, []
    for plane in pd.planes:
        if _is_device(plane.name):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((op_name(e.name), e.start_ns, e.end_ns)
                               for e in line.events)
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.end_ns, line.name)
                            for e in line.events if e.duration_ns > 0)
    return {"devices": devices, "host": host}


def load(path: str) -> dict:
    from jax.profiler import ProfileData
    return from_profile(ProfileData.from_file(path))


def union(intervals):
    """Merged, sorted ``[start, end]`` list of possibly overlapping
    intervals."""
    out = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def length(merged) -> float:
    return sum(e - s for s, e in merged)


def subtract(merged_a, merged_b) -> float:
    """Length of ``merged_a`` not covered by ``merged_b`` (both merged)."""
    covered, j = 0.0, 0
    for s, e in merged_a:
        while j < len(merged_b) and merged_b[j][1] <= s:
            j += 1
        k = j
        while k < len(merged_b) and merged_b[k][0] < e:
            covered += min(e, merged_b[k][1]) - max(s, merged_b[k][0])
            k += 1
    return length(merged_a) - covered


def gaps(merged, lo=None, hi=None):
    """Idle intervals between merged busy intervals (and the window's
    edges, where given)."""
    out = []
    prev = lo if lo is not None else (merged[0][0] if merged else None)
    for s, e in merged:
        if prev is not None and s > prev:
            out.append((prev, s))
        prev = e if prev is None else max(prev, e)
    if hi is not None and prev is not None and hi > prev:
        out.append((prev, hi))
    return out


def _is_a2a(name: str) -> bool:
    low = name.lower()
    return any(m in low for m in A2A_MARKS)


def _label(gap, host, skip_ns):
    """The host event that overlaps ``gap`` most, of those shorter than
    ``skip_ns`` (whole-window spans say nothing about one gap); of events
    that overlap it nearly as much, the shortest, so that a step span
    yields to the call inside it."""
    s, e = gap
    hits = []
    for name, hs, he, _thread in host:
        if he <= s or hs >= e or he - hs >= skip_ns:
            continue
        hits.append((min(e, he) - max(s, hs), he - hs, name))
    if not hits:
        return "host idle"
    best = max(ov for ov, _, _ in hits)
    return min((dur, name) for ov, dur, name in hits
               if ov >= 0.9 * best)[1]


def window_of(events: dict):
    """``(start_ns, end_ns)`` of the harness's window span, or None."""
    spans = [(s, e) for n, s, e, _t in events["host"] if n == WINDOW]
    return max(spans, key=lambda se: se[1] - se[0]) if spans else None


def reduce(events: dict, window_s: float, *, top: int = TOP) -> dict:
    """Device metrics of a traced window.  The window is the harness's
    ``bench.window`` host span where the trace has it (ops are clipped to
    it, and idle time at its edges counts), else the first to the last op
    over a host-measured ``window_s``.  Per-device numbers are averaged
    over the devices that ran an op."""
    devs = {d: ops for d, ops in events["devices"].items() if ops}
    span = window_of(events)
    if span is not None:
        lo, hi = span
        window_s = (hi - lo) * 1e-9
        devs = {d: [(n, max(s, lo), min(e, hi)) for n, s, e in ops
                    if e > lo and s < hi]
                for d, ops in devs.items()}
        devs = {d: ops for d, ops in devs.items() if ops}
    else:
        lo = hi = None
    if not devs:
        return {"devices": 0, "busy_s": 0.0, "window_s": window_s,
                "idle_share": None, "op_s": {}, "op_calls": {},
                "a2a_s": 0.0, "a2a_exposed_s": 0.0,
                "breakdown": {"device_ops": [], "idle_gaps": []}}
    nd = len(devs)
    op_ns, op_calls = defaultdict(float), defaultdict(int)
    busy = a2a = a2a_exposed = 0.0
    all_gaps = []
    for ops in devs.values():
        merged = union((s, e) for _, s, e in ops)
        busy += length(merged)
        for name, s, e in ops:
            op_ns[name] += e - s
            op_calls[name] += 1
        coll = union((s, e) for n, s, e in ops if _is_a2a(n))
        other = union((s, e) for n, s, e in ops if not _is_a2a(n))
        a2a += length(coll)
        a2a_exposed += subtract(coll, other)
        all_gaps.extend(gaps(merged, lo, hi))
    busy_s = busy / nd * 1e-9
    skip_ns = 0.5 * window_s * 1e9
    longest = sorted(all_gaps, key=lambda g: g[1] - g[0], reverse=True)[:top]
    ranked = sorted(op_ns.items(), key=lambda kv: kv[1], reverse=True)[:top]
    return {
        "devices": nd,
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_share": (max(0.0, 1.0 - busy_s / window_s)
                       if window_s > 0 else None),
        "op_s": {n: v / nd * 1e-9 for n, v in op_ns.items()},
        "op_calls": dict(op_calls),
        "a2a_s": a2a / nd * 1e-9,
        "a2a_exposed_s": a2a_exposed / nd * 1e-9,
        "breakdown": {
            "device_ops": [[n, v / nd * 1e-9] for n, v in ranked],
            "idle_gaps": [[_label(g, events["host"], skip_ns),
                           (g[1] - g[0]) * 1e-9] for g in longest],
        },
    }


def kernel_seconds(red: dict, marks) -> float:
    """Device seconds (per device) of the ops whose name contains any of
    ``marks``."""
    return sum(v for n, v in red["op_s"].items()
               if any(m in n for m in marks))
