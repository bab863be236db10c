"""The closed-loop window dispatches ahead, with at most ``ahead_steps``
steps queued behind the newest, and waits for every step it sent, in
order, before it reads the clock."""
import jax
import numpy as np

import run

drv = run.load_module(run.os.path.join(run.HERE, "drivers", "lib_step.py"),
                      "bench_driver_lib_step")


def test_window_bounds_the_steps_in_flight(monkeypatch):
    sent, waited, depth = [], [], []

    def step(*_args):
        sent.append(object())
        depth.append(len(sent) - len(waited))
        return sent[-1]

    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: waited.append(x) or x)
    traffic = {"shape": [4, 4], "batch": 1, "pool": 2, "ahead_steps": 3,
               "sample_steps_per_kind": 1}
    inputs = ([(0, 0)] * 2, [0] * 2)
    state = drv.State(cfg={}, traffic=traffic,
                      rng=np.random.default_rng(0), steps=(step, step),
                      args=((), ()), ops=(None, None), inputs=inputs)
    win = drv.window(state, 0.05)
    assert win["attempted"] == len(sent) > 3
    assert waited == sent                    # every step, in order
    assert max(depth) == 4                   # three ahead of the newest
    assert win["window_s"] >= 0.05
