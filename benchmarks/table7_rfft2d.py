"""Table 7 — fused real-input 2-D FFT: the kind="rfft" hardware path.

The paper's core finding is that FFT performance is bounded by data
movement; real-input transforms were doing twice the movement they need
to (rfft2 was pinned to the jnp row-column schedule).  This table pits
the fused real-input Pallas kernel (:mod:`repro.kernels.rfft2d_fused`)
against that jnp rfft2 path on the same machine:

- measured wall time, interleaved A/B (the ratio gates the acceptance
  criterion: fused >= 1.3x at 1024x1024, rel err vs numpy <= 1e-6);
- the inverse twin (irfft2) timed the same way;
- model-predicted vs measured (operand-counted) HBM traffic: the fused
  kernel moves one real plane + one half spectrum per image — ~half the
  complex fused kernel's two full planes — and the
  :func:`repro.tt.trace.trace_plan` prediction must agree with the byte
  count the kernel's operands actually imply.

All rows land in BENCH_rfft2d.json (section "table7").
``--smoke`` runs the 256x256 case only (CI).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import clear_plan_cache, get_plan, to_complex
from repro.core.complexmath import from_complex
from repro.tt import trace as tttrace
from .common import emit, time_fn_pair, write_json

BENCH_JSON = "BENCH_rfft2d.json"


def measured_traffic_bytes(h: int, w: int, *, dtype=np.float32) -> int:
    """HBM bytes the fused rfft kernel actually stages per image, counted
    from its REAL operand buffers — the table arrays the kernel builds
    (``row_tables_np`` + ``col_tables_np``, cast to the working dtype)
    plus the input plane and output spectrum ShapeDtypeStructs.
    Deliberately independent of :mod:`repro.tt.trace`'s accounting, so a
    model drift (forgotten table, wrong spectrum width) shows up as
    model_vs_measured != 1."""
    from repro.kernels.rfft2d_fused import col_tables_np, row_tables_np
    tables = sum(np.asarray(t, dtype).nbytes
                 for t in row_tables_np(w, False) + col_tables_np(h, False))
    itemsize = np.dtype(dtype).itemsize
    plane_in = h * w * itemsize                      # real input
    spec_out = 2 * h * (w // 2 + 1) * itemsize       # re+im half spectrum
    return plane_in + spec_out + tables


def run(sizes=(256, 1024)):
    sink = {}
    rng = np.random.default_rng(0)
    for n in sizes:
        x = jnp.asarray(rng.standard_normal((n, n)).astype(np.float32))
        ref = np.fft.rfft2(np.asarray(x))

        def _err(out):
            return np.abs(np.asarray(to_complex(out))
                          - ref).max() / np.abs(ref).max()

        clear_plan_cache()
        plan_jnp = get_plan((n, n), kind="rfft", backend="jnp")
        plan_pal = get_plan((n, n), kind="rfft", backend="pallas")
        assert plan_pal.algo == "fused" and plan_pal.backend == "pallas"
        fn_jnp = jax.jit(lambda q: plan_jnp(q))
        fn_pal = jax.jit(lambda q: plan_pal(q))

        # interleaved A/B — the ratio gates the acceptance criterion, so
        # spend extra alternating samples to push the shared-box noise
        # floor down before taking the median
        us_jnp, us_pal = time_fn_pair(fn_jnp, fn_pal, x, iters=11)
        err_jnp, err_pal = _err(fn_jnp(x)), _err(fn_pal(x))
        emit(f"table7/rfft2_{n}_jnp", us_jnp,
             f"rel_err={err_jnp:.1e};rfft rows + transpose + c2c cols",
             sink)
        emit(f"table7/rfft2_{n}_pallas_fused", us_pal,
             f"rel_err={err_pal:.1e};one kernel, row-pair packing, "
             "half-width column pass", sink)
        emit(f"table7/rfft2_{n}_fused_speedup_vs_jnp", us_jnp / us_pal,
             "ratio(us_jnp/us_pallas);acceptance >= 1.3 at 1024", sink)

        # inverse twin
        xf = from_complex(jnp.asarray(ref.astype(np.complex64)))
        pi_jnp = get_plan((n, n), kind="rfft", backend="jnp", inverse=True)
        pi_pal = get_plan((n, n), kind="rfft", backend="pallas",
                          inverse=True)
        fni_jnp = jax.jit(lambda q: pi_jnp(q))
        fni_pal = jax.jit(lambda q: pi_pal(q))
        us_ij, us_ip = time_fn_pair(fni_jnp, fni_pal, xf)
        ierr = np.abs(np.asarray(fni_pal(xf)) - np.asarray(x)).max()
        emit(f"table7/irfft2_{n}_jnp", us_ij, "inverse twin", sink)
        emit(f"table7/irfft2_{n}_pallas_fused", us_ip,
             f"roundtrip_err={ierr:.1e}", sink)
        emit(f"table7/irfft2_{n}_fused_speedup_vs_jnp", us_ij / us_ip,
             "ratio(us_jnp/us_pallas)", sink)

        # model-predicted vs measured (operand-counted) HBM traffic
        tr = tttrace.trace_plan(plan_pal, arch="tpu_v5e")
        tc = tttrace.trace_plan(
            get_plan((n, n), backend="pallas"), arch="tpu_v5e")
        measured = measured_traffic_bytes(n, n)
        emit(f"table7/rfft2_{n}_traffic_model_bytes", tr.dram_bytes,
             f"measured_operand_bytes={measured:.0f};"
             f"model_vs_measured={tr.dram_bytes / measured:.4f}", sink)
        regime = "~0.5 — half the plane bytes" if n > 256 else \
            "dense-DFT leaf tables dominate below the four-step split"
        emit(f"table7/rfft2_{n}_traffic_vs_complex_fused",
             tr.dram_bytes / tc.dram_bytes,
             f"ratio(rfft_fused/c2c_fused);{regime}", sink)
        emit(f"table7/rfft2_{n}_vmem_high_water", tr.sram_high_water,
             f"fits_16MiB_v5e={tr.fits};complex_fused="
             f"{tc.sram_high_water} (fits={tc.fits})", sink)
    clear_plan_cache()
    write_json(BENCH_JSON, "table7", sink)
    return sink


def main():
    import argparse
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="256x256 only (CI)")
    args = ap.parse_args()
    run(sizes=(256,) if args.smoke else (256, 1024))


if __name__ == "__main__":
    main()
