import pytest

import trace_reduce as tr

MS = 1_000_000          # ns per ms


def _events(*ops, host=()):
    return {"devices": {"/device:TPU:0": list(ops)}, "host": list(host)}


def test_union_merges_overlaps_and_touching_intervals():
    assert tr.union([(5, 7), (0, 2), (1, 3), (3, 4)]) == [[0, 4], [5, 7]]
    assert tr.length(tr.union([(0, 10), (2, 3)])) == 10


def test_busy_is_the_union_and_idle_the_rest_of_the_window():
    red = tr.reduce(_events(("a", 0, 4 * MS), ("b", 2 * MS, 6 * MS),
                            ("a", 8 * MS, 9 * MS)), window_s=0.010)
    assert red["busy_s"] == pytest.approx(0.007)
    assert red["idle_share"] == pytest.approx(0.3)
    assert red["op_s"] == pytest.approx({"a": 0.005, "b": 0.004})
    assert red["op_calls"] == {"a": 2, "b": 1}


def test_kernel_seconds_by_name():
    red = tr.reduce(_events(("fft2d_gemm.2", 0, 3 * MS),
                            ("rfft2d_fused.1", 3 * MS, 4 * MS),
                            ("irfft2d_fused.1", 4 * MS, 6 * MS),
                            ("fusion.1", 6 * MS, 7 * MS)), window_s=0.01)
    assert tr.kernel_seconds(red, ("fft2d_gemm",)) == pytest.approx(0.003)
    assert tr.kernel_seconds(red, ("rfft2d",)) == pytest.approx(0.003)


def test_all_to_all_time_and_its_exposed_part():
    # a2a 0-4 ms, compute overlaps 3-5 ms: 1 ms hidden, 3 ms exposed
    red = tr.reduce(_events(("all-to-all.1", 0, 4 * MS),
                            ("fusion.2", 3 * MS, 5 * MS)), window_s=0.005)
    assert red["a2a_s"] == pytest.approx(0.004)
    assert red["a2a_exposed_s"] == pytest.approx(0.003)


def test_per_device_numbers_are_averaged_over_devices():
    ev = {"devices": {"/device:TPU:0": [("all-to-all", 0, 2 * MS)],
                      "/device:TPU:1": [("all-to-all", 0, 4 * MS)],
                      "/device:TPU:2": []},
          "host": []}
    red = tr.reduce(ev, window_s=0.004)
    assert red["devices"] == 2
    assert red["busy_s"] == pytest.approx(0.003)
    assert red["a2a_s"] == pytest.approx(0.003)


def test_idle_gaps_are_labelled_by_overlapping_host_work():
    ev = _events(("k", 0, 1 * MS), ("k", 5 * MS, 6 * MS),
                 ("k", 7 * MS, 8 * MS),
                 host=[("device_put", int(1.5 * MS), 4 * MS, "t1"),
                       ("window", 0, 8 * MS, "t0")])
    red = tr.reduce(ev, window_s=0.008)
    gaps = red["breakdown"]["idle_gaps"]
    assert gaps[0] == ["device_put", pytest.approx(0.004)]
    assert gaps[1] == ["host idle", pytest.approx(0.001)]
    assert red["breakdown"]["device_ops"] == [["k", pytest.approx(0.003)]]


def test_window_span_clips_ops_and_counts_edge_gaps():
    ev = _events(("k", 0, 2 * MS), ("k", 5 * MS, 7 * MS),
                 ("k", 9 * MS, 12 * MS),
                 host=[(tr.WINDOW, 1 * MS, 10 * MS, "main"),
                       ("setup", 7 * MS, 9 * MS, "main")])
    red = tr.reduce(ev, window_s=99.0)
    assert red["window_s"] == pytest.approx(0.009)
    assert red["busy_s"] == pytest.approx(0.004)
    assert red["idle_share"] == pytest.approx(5 / 9)
    assert red["breakdown"]["idle_gaps"] == [
        ["host idle", pytest.approx(0.003)], ["setup", pytest.approx(0.002)]]


def test_op_name_is_the_hlo_instruction_name():
    text = ("%fft2d_gemm.2 = (f32[32,1024,1024]{2,1,0:T(8,128)}) "
            "custom-call(f32[1024,1024]{1,0} %constant.0)")
    assert tr.op_name(text) == "fft2d_gemm.2"
    assert tr.op_name("fusion.3") == "fusion.3"


def test_no_device_events_reads_nothing():
    red = tr.reduce({"devices": {}, "host": []}, window_s=1.0)
    assert red["idle_share"] is None and red["devices"] == 0


XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines {
    id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 3000000 duration_ps: 1000000 }
  }
  lines {
    id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 4000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fft2d_gemm.2 = f32[8] custom-call()" } }
  event_metadata { key: 2 value { id: 2 name: "fusion.7" } }
  event_metadata { key: 3 value { id: 3 name: "jit_step" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines {
    id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 2000000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "device_put" } }
}
"""


def test_xplane_is_read_into_op_and_host_events():
    from jax.profiler import ProfileData
    ev = tr.from_profile(ProfileData.from_text_proto(XSPACE))
    assert ev["devices"] == {"/device:TPU:0": [
        ("fft2d_gemm.2", 1000.0, 3000.0), ("fusion.7", 4000.0, 5000.0)]}
    assert ev["host"] == [("device_put", 3000.0, 4000.0, "python")]
    red = tr.reduce(ev, window_s=4e-6)
    assert red["busy_s"] == pytest.approx(3e-6)
    assert red["breakdown"]["idle_gaps"] == [["device_put",
                                              pytest.approx(1e-6)]]


def test_recorded_tpu_trace():
    """Op events recorded on a TPU v5 lite in the library cell: two c2c
    and one real round trip, with the copies and slices XLA put between
    the kernels.  Busy time matches a 1-us timeline of the same events."""
    import json
    import os
    import numpy as np
    with open(os.path.join(os.path.dirname(__file__), "data",
                           "trace_lib_tpu.json")) as f:
        rec = json.load(f)
    ops = [tuple(o) for o in rec["devices"]["/device:TPU:0"]]
    ev = {"devices": {"/device:TPU:0": ops},
          "host": [tuple(h) for h in rec["host"]]}
    lo = min(s for _, s, _ in ops)
    hi = max(e for _, _, e in ops)
    timeline = np.zeros(int((hi - lo) // 1000) + 2, bool)
    for _, s, e in ops:
        timeline[int((s - lo) // 1000):int((e - lo) // 1000)] = True
    red = tr.reduce(ev, (hi - lo) * 1e-9)
    assert red["busy_s"] == pytest.approx(timeline.sum() * 1e-6, abs=1e-4)
    assert red["idle_share"] == pytest.approx(1 - red["busy_s"] * 1e9
                                              / (hi - lo))
    gemm = sum(e - s for n, s, e in ops if n.startswith("fft2d_gemm"))
    assert tr.kernel_seconds(red, ("fft2d_gemm",)) == pytest.approx(
        gemm * 1e-9)
    assert red["op_calls"]["rfft2d_fused.1"] == 2
    assert red["op_calls"]["irfft2d_fused.1"] == 1
    assert red["a2a_s"] == 0.0
    names = [n for n, _ in red["breakdown"]["device_ops"]]
    assert names[:2] == ["fft2d_gemm.2", "fft2d_gemm.3"]


def test_gap_label_prefers_the_call_inside_a_step_span():
    ev = _events(("k", 0, 1 * MS), ("k", 5 * MS, 6 * MS),
                 host=[("bench.step", 0, 6 * MS, "main"),
                       ("PjitFunction(step)", 1 * MS, 5 * MS, "main"),
                       ("other", 2 * MS, 3 * MS, "t2")])
    red = tr.reduce(ev, window_s=0.02)
    assert red["breakdown"]["idle_gaps"][0][0] == "PjitFunction(step)"
