"""Small helpers the harness shares: the entry points' start, seeded
sampling and device facts."""
from __future__ import annotations

import os
import sys
import tempfile

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def start_on_tpu(tool: str):
    """Put the program and the harness on the path, keep the compile
    cache at the checkout's fixed ``.jax_cache/``, and return the devices
    JAX sees; None (after saying why) outside a checkout or without a
    TPU, so that the entry point exits non-zero and runs nothing."""
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"[{tool}] no repro package under {src}: run from a "
              "checkout", flush=True)
        return None
    for p in (HERE, src):
        if p not in sys.path:
            sys.path.insert(0, p)
    # the cache stays inside the checkout, whatever the environment names
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    # libtpu writes its logs under /tmp unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(tempfile.gettempdir(),
                                                      "tpu_logs"))
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"[{tool}] no TPU: JAX runs on {devs[0].platform}; nothing "
              "was run", flush=True)
        return None
    from repro.launch import compile_cache
    compile_cache.enable()
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return devs


class Reservoir:
    """A uniform sample of ``k`` items from a stream of unknown length,
    drawn from ``rng`` (Algorithm R).  Holding an item costs nothing on
    the device: it keeps an existing buffer alive."""

    def __init__(self, k: int, rng: np.random.Generator):
        self.k, self.rng, self.seen, self.items = k, rng, 0, []

    def offer(self, item) -> None:
        self.seen += 1
        if len(self.items) < self.k:
            self.items.append(item)
            return
        j = int(self.rng.integers(self.seen))
        if j < self.k:
            self.items[j] = item


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of ``devices`` (0 where the backend
    keeps no such count)."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0
