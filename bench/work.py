"""The work a 2-D transform needs, whatever implements it, and the roofline.

Operations: 5·N·log2 N real operations per complex transform of N points,
half of that for a real-input (r2c) or real-output (c2r) one.  Bytes: the
input read plus the output written, in float32 planes (split complex is
two planes).  A faster algorithm does not change these numbers, so a
cheaper kernel reads as a *higher* share of its roofline.
"""
from __future__ import annotations

import json
import math
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")
KINDS = ("c2c", "r2c", "c2r")


def transform_work(kind: str, shape, *, batch: int = 1,
                   itemsize: int = 4) -> dict:
    """``{"flops", "bytes"}`` of ``batch`` transforms of ``kind`` over the
    2-D ``shape`` (the real-space shape for r2c and c2r)."""
    if kind not in KINDS:
        raise ValueError(f"kind must be one of {KINDS}, got {kind!r}")
    h, w = (int(d) for d in shape)
    n = h * w
    half = h * (w // 2 + 1)
    flops = 5.0 * n * math.log2(n)
    if kind == "c2c":
        nbytes = 2 * itemsize * n + 2 * itemsize * n
    else:
        flops /= 2
        nbytes = itemsize * n + 2 * itemsize * half
    return {"flops": batch * flops, "bytes": batch * float(nbytes)}


def add(*works: dict) -> dict:
    return {"flops": sum(w["flops"] for w in works),
            "bytes": sum(w["bytes"] for w in works)}


def peaks(device_kind: str, path: str = PEAKS_FILE) -> dict:
    """The peaks of ``device_kind``; a kind not in the table is an error."""
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{path}; known: {sorted(table)}")
    return table[device_kind]


def roofline(work: dict, seconds: float, peak: dict):
    """``(share, bound)``: the least time the chip could take for ``work``
    (the larger of operations over peak FLOP/s and bytes over peak bytes/s)
    over the measured ``seconds``, and which of the two bounds it.  None
    where nothing was measured."""
    if seconds <= 0 or work["flops"] <= 0:
        return None
    t_flops = work["flops"] / peak["flops_per_s"]
    t_bytes = work["bytes"] / peak["bytes_per_s"]
    bound = "memory" if t_bytes >= t_flops else "compute"
    return max(t_flops, t_bytes) / seconds, bound
