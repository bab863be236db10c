"""Closed-loop, device-resident batched 2-D transforms through
``repro.core.fft2d`` -- the library call of numerical codes whose data
already lives on the chip.

Even steps propagate a batch of complex fields: ``fft2`` -> Fresnel
transfer function -> ``fft2(inverse=True)``.  Odd steps filter a batch of
real images: ``rfft2`` -> Gaussian blur -> ``irfft2``.  Each step is one
jitted program; step ``i`` reads input batch ``(i // 2) % pool`` of its
kind, so consecutive steps of a kind see different data.  The inputs are
made on the device in one jitted call from the seed; the operators'
parameters are drawn from it too.

Steps are dispatched ahead, as a code that does not read every result at
once dispatches them: once more than ``ahead_steps`` are queued, the loop
waits (``block_until_ready``) on the oldest after each send, so the chip
stays fed while the host pauses.  When the window's time is up
nothing more is sent, every step sent is waited for, and the clock is read
after that wait: the window holds all the steps and all their time.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import time

import numpy as np

import reference
import work
from common import Reservoir


def _propagate(zr, zi, hr, hi, *, backend):
    from repro.core import fft2d
    from repro.core.complexmath import SplitComplex
    y = fft2d.fft2(SplitComplex(zr, zi), backend=backend)
    y = SplitComplex(y.re * hr - y.im * hi, y.re * hi + y.im * hr)
    out = fft2d.fft2(y, inverse=True, backend=backend)
    return out.re, out.im


def _filter(x, g, *, backend):
    from repro.core import fft2d
    from repro.core.complexmath import SplitComplex
    y = fft2d.rfft2(x, backend=backend)
    return fft2d.irfft2(SplitComplex(y.re * g, y.im * g), backend=backend)


def _make_inputs(key, *, pool, batch, shape):
    import jax
    kz, kx = jax.random.split(key)
    z = jax.random.normal(kz, (pool, 2, batch) + shape, "float32")
    x = jax.random.normal(kx, (pool, batch) + shape, "float32")
    return ([(z[p, 0], z[p, 1]) for p in range(pool)],
            [x[p] for p in range(pool)])


@dataclasses.dataclass
class State:
    cfg: dict
    traffic: dict
    rng: np.random.Generator
    steps: tuple            # (propagate, filter), jitted
    args: tuple             # per-kind operator arguments on the device
    ops: tuple              # the same operators on the host
    inputs: tuple           # per-kind lists of device input batches
    sampled: tuple = ()     # per-kind Reservoirs


def setup(cfg: dict, traffic: dict, seed: int, seconds: float) -> State:
    import jax
    import jax.numpy as jnp
    shape = tuple(traffic["shape"])
    rng = np.random.default_rng(seed)
    h = reference.operator("fresnel", shape,
                           rng.uniform(*traffic["fresnel_a"]))
    g = reference.operator("gaussian_blur", shape,
                           rng.uniform(*traffic["blur_sigma"]))
    make = jax.jit(functools.partial(_make_inputs, pool=traffic["pool"],
                                     batch=traffic["batch"], shape=shape))
    inputs = make(jax.random.key(seed))
    args = ((jnp.asarray(h.real), jnp.asarray(h.imag)), (jnp.asarray(g),))
    be = cfg["backend"]
    steps = (jax.jit(functools.partial(_propagate, backend=be)),
             jax.jit(functools.partial(_filter, backend=be)))
    # warm: compile both programs and run each once
    jax.block_until_ready(steps[0](*inputs[0][0], *args[0]))
    jax.block_until_ready(steps[1](inputs[1][0], *args[1]))
    return State(cfg=cfg, traffic=traffic, rng=rng, steps=steps, args=args,
                 ops=(h, g), inputs=inputs)


def _step_input(state: State, i: int):
    kind = i % 2
    p = (i // 2) % state.traffic["pool"]
    x = state.inputs[kind][p]
    return kind, p, (x if kind else tuple(x))


def by_fifth(ends: list, t_open: float, win: float) -> list:
    """Mean step time (ms) in each fifth of the window: a warm-up or a
    slow spell shows as one fifth that reads apart from the others."""
    edges = t_open + win * np.arange(6) / 5
    counts, _ = np.histogram(ends, bins=edges)
    return [win / 5 / c * 1e3 if c else None for c in counts]


def window(state: State, seconds: float) -> dict:
    import jax
    k = state.traffic["sample_steps_per_kind"]
    state.sampled = (Reservoir(k, state.rng), Reservoir(k, state.rng))
    ahead = state.traffic["ahead_steps"]
    pending = collections.deque()
    i = 0
    ends = []
    t_open = time.perf_counter()
    while time.perf_counter() - t_open < seconds:
        kind, p, x = _step_input(state, i)
        with jax.profiler.TraceAnnotation("bench.step"):
            if kind:
                out = state.steps[1](x, *state.args[1])
            else:
                out = state.steps[0](*x, *state.args[0])
        state.sampled[kind].offer((i, p, out))
        pending.append(out)
        i += 1
        if len(pending) > ahead:
            jax.block_until_ready(pending.popleft())
            ends.append(time.perf_counter())
    while pending:
        jax.block_until_ready(pending.popleft())
        ends.append(time.perf_counter())
    t = ends[-1]
    win = t - t_open
    shape, b = tuple(state.traffic["shape"]), state.traffic["batch"]
    n_c2c, n_real = (i + 1) // 2, i // 2
    done = {"c2c": work.transform_work("c2c", shape, batch=2 * b * n_c2c),
            "r2c": work.transform_work("r2c", shape, batch=b * n_real),
            "c2r": work.transform_work("c2r", shape, batch=b * n_real)}
    return {"e2e": {"step_ms": win / i * 1e3}, "attempted": i, "failed": 0,
            "window_s": win, "t_open": t_open, "counters": {},
            "work": done, "diag": {"steps": i,
                                   "step_ms_by_fifth": by_fifth(ends, t_open,
                                                                win)}}


def finish(state: State) -> dict:
    """Copy the sampled images of the sampled steps, with their inputs,
    to the host, and free the device state."""
    n_img = state.traffic["sample_images"]
    cases = []
    for kind, res in enumerate(state.sampled):
        for i, p, out in res.items:
            idx = np.sort(state.rng.choice(state.traffic["batch"],
                                           size=n_img, replace=False))
            if kind:
                got = np.asarray(out)[idx]
                x = np.asarray(state.inputs[1][p])[idx]
            else:
                got = (np.asarray(out[0])[idx]
                       + 1j * np.asarray(out[1])[idx])
                zr, zi = state.inputs[0][p]
                x = (np.asarray(zr)[idx].astype(np.complex128)
                     + 1j * np.asarray(zi)[idx])
            cases.append((kind, i, x, got))
    answers = {"cases": cases, "ops": state.ops}
    state.inputs = state.args = state.sampled = None
    return answers


def compare(answers: dict) -> dict:
    """The widest relative L2 gap of a sampled image's output against the
    float64 reference of its step, and the step kinds left unchecked."""
    h, g = answers["ops"]
    worst = 0.0
    for kind, _i, x, got in answers["cases"]:
        for j in range(len(x)):
            ref = (reference.filter_real(x[j], g) if kind
                   else reference.propagate(x[j], h))
            worst = max(worst, reference.rel_l2(got[j], ref))
    kinds = {kind for kind, _, _, _ in answers["cases"]}
    return {"max_rel_l2": worst, "kinds_unchecked": 2 - len(kinds)}
