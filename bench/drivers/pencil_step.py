"""Closed-loop pencil spectral steps over a 4-chip host through
``repro.dist.pencil`` -- the implicit viscous (Helmholtz) solve of a
pseudo-spectral 2-D solver whose fields are row-sharded over the chips.

Each step is one jitted program over the ``(chips,)`` mesh ``"data"``: a
stack of real fields, ``prfft2`` -> ``1/(1 + nu_dt*|k|^2)`` (integer
wavenumbers) -> ``pirfft2``, as ``pencil.pfilter2``: two all_to_alls, the
operator applied in the packed half-spectrum layout.  Step ``i`` reads
input stack ``i % pool``.  The inputs are made on the device from the
seed, already row-sharded; ``nu_dt`` is drawn from the seed too.  Of each
input stack, ``sample_steps_per_input`` seeded steps are kept, and every
field of them is checked against float64.

Steps are dispatched ahead as in ``lib_step``: once more than
``ahead_steps`` are queued the loop waits on the oldest after each send;
when the window's time is up every step sent is waited for, and the clock
is read after that wait.
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
from drivers.lib_step import by_fifth

AXIS = "data"


def helmholtz(shape, nu_dt: float) -> np.ndarray:
    """``1/(1 + nu_dt*(ky^2 + kx^2))`` over the (H, W/2+1) half spectrum,
    integer wavenumbers, rounded once to float32: real and even in ky, and
    its DC and Nyquist columns differ."""
    h, w = shape
    ky = np.fft.fftfreq(h, 1.0 / h)[:, None]
    kx = np.fft.rfftfreq(w, 1.0 / w)[None, :]
    return (1.0 / (1.0 + nu_dt * (ky ** 2 + kx ** 2))).astype(np.float32)


@dataclasses.dataclass
class State:
    cfg: dict
    traffic: dict
    rng: np.random.Generator
    step: object            # the jitted pencil step
    op: object              # the operator, placed on the mesh
    g: np.ndarray           # the same operator on the host
    inputs: list            # pool of row-sharded input stacks
    wire_bytes: int         # payload per chip per step (wire log)
    sampled: tuple = ()     # per-input-stack Reservoirs


def check_config(cfg: dict) -> None:
    """The configuration states what ``pfilter2`` runs: fp32 fields,
    uncompressed exchanges, jnp local passes at the registry's default
    DFT precision.  Refuse a file that states anything else."""
    import jax
    from repro.core import fft1d
    want = {"dtype": "float32", "compress": "none", "backend": "jnp"}
    wrong = {k: cfg.get(k) for k, v in want.items() if cfg.get(k) != v}
    if fft1d.DFT_PRECISION != jax.lax.Precision[cfg.get("precision", "")]:
        wrong["precision"] = cfg.get("precision")
    if wrong:
        raise ValueError(f"pencil_step runs {want} at precision "
                         f"{fft1d.DFT_PRECISION.name}; the configuration "
                         f"states {wrong}")


def setup(cfg: dict, traffic: dict, seed: int, seconds: float) -> State:
    import jax
    from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P
    from repro.dist import pencil

    check_config(cfg)
    shape = tuple(traffic["shape"])
    rng = np.random.default_rng(seed)
    g = helmholtz(shape, rng.uniform(*traffic["nu_dt"]))
    mesh = Mesh(np.asarray(jax.devices()[:cfg["chips"]]), (AXIS,),
                axis_types=(AxisType.Auto,))
    rows = NamedSharding(mesh, P(None, AXIS, None))
    make = jax.jit(lambda k: jax.random.normal(
        k, (traffic["batch"],) + shape, "float32"), out_shardings=rows)
    key = jax.random.key(seed)
    inputs = [make(jax.random.fold_in(key, p))
              for p in range(traffic["pool"])]
    op = pencil.shard_half_operator(g, mesh, AXIS)
    step = jax.jit(functools.partial(pencil.pfilter2, mesh=mesh, axis=AXIS))
    # warm: trace (the wire log counts one step's exchanges), compile, run
    pencil.reset_wire_log()
    jax.block_until_ready(step(inputs[0], op))
    wire = pencil.logged_exchange_bytes()
    if wire <= 0:
        raise RuntimeError("the pencil step logged no exchange")
    return State(cfg=cfg, traffic=traffic, rng=rng, step=step, op=op, g=g,
                 inputs=inputs, wire_bytes=wire)


def window(state: State, seconds: float) -> dict:
    import jax
    ahead, pool = state.traffic["ahead_steps"], state.traffic["pool"]
    k = state.traffic["sample_steps_per_input"]
    state.sampled = tuple(Reservoir(k, state.rng) for _ in range(pool))
    pending = collections.deque()
    i = 0
    ends = []
    t_open = time.perf_counter()
    while time.perf_counter() - t_open < seconds:
        p = i % pool
        with jax.profiler.TraceAnnotation("bench.step"):
            out = state.step(state.inputs[p], state.op)
        state.sampled[p].offer((i, p, out))
        pending.append(out)
        i += 1
        if len(pending) > ahead:
            jax.block_until_ready(pending.popleft())
            ends.append(time.perf_counter())
    while pending:
        jax.block_until_ready(pending.popleft())
        ends.append(time.perf_counter())
    win = ends[-1] - t_open
    shape, chips = tuple(state.traffic["shape"]), state.cfg["chips"]
    fields = state.traffic["batch"] * i
    done = {"r2c": work.transform_work("r2c", shape, batch=fields),
            "c2r": work.transform_work("c2r", shape, batch=fields)}
    offchip = (chips - 1) / chips * state.wire_bytes * i
    return {"e2e": {"step_ms": win / i * 1e3}, "attempted": i, "failed": 0,
            "window_s": win, "t_open": t_open,
            "counters": {"exchange_offchip_bytes": offchip},
            "work": done,
            "diag": {"steps": i, "wire_bytes_per_step": state.wire_bytes,
                     "step_ms_by_fifth": by_fifth(ends, t_open, win)}}


def finish(state: State) -> dict:
    """Copy every field of the sampled steps, with their inputs, to the
    host, and free the device state."""
    cases = [(i, np.asarray(state.inputs[p]), np.asarray(out))
             for res in state.sampled for i, p, out in res.items]
    answers = {"cases": cases, "g": state.g,
               "inputs_unchecked": sum(not res.items
                                       for res in state.sampled)}
    state.inputs = state.op = state.sampled = None
    return answers


def compare(answers: dict) -> dict:
    """The widest relative L2 gap of a sampled step's field against the
    float64 ``irfft2(rfft2(x) * g)``, and the input stacks (the step's only
    kinds) of which no step was checked."""
    worst = 0.0
    for _i, x, got in answers["cases"]:
        for j in range(len(x)):
            ref = reference.filter_real(x[j], answers["g"])
            worst = max(worst, reference.rel_l2(got[j], ref))
    return {"max_rel_l2": worst,
            "kinds_unchecked": answers["inputs_unchecked"]}


def control(answers: dict) -> dict:
    """The answers with the control -- the same step as dense DFT products
    at ``Precision.HIGH`` on one device -- in the program's place."""
    import jax
    import jax.numpy as jnp
    g = jnp.asarray(answers["g"])
    filt = jax.jit(reference.control_filter)
    shape = answers["cases"][0][1].shape[-2:]
    t = {k: tuple(jnp.asarray(a) for a in v) for k, v in
         reference.control_tables(shape, real=True).items()}
    cases = [(i, x, np.stack([np.asarray(filt(jnp.asarray(f), g, t))
                              for f in x]))
             for i, x, _got in answers["cases"]]
    return dict(answers, cases=cases)
