"""GEMM-formulated fused 2-D FFT kernel: correctness vs numpy, agreement
with the Stockham oracle, the precision-compensated bf16 variant's error
bounds, and the variant plumbing through the plan registry."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fft2, from_complex, to_complex
from repro.core import plan as P
from repro.core.complexmath import SplitComplex
from repro.kernels import ops
from repro.kernels.fft2d_gemm import gemm_tables, split_table_np


@pytest.fixture(autouse=True)
def _fresh_registry():
    P.clear_plan_cache()
    yield
    P.clear_plan_cache()


def _rand2d(shape, seed=0):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal(shape)
            + 1j * rng.standard_normal(shape)).astype(np.complex64)


def _rel(got, ref):
    return np.abs(got - ref).max() / np.abs(ref).max()


@pytest.mark.parametrize("hw", [(8, 4), (8, 8), (32, 8), (8, 32), (64, 64),
                                (64, 128), (128, 64), (256, 256), (512, 512)])
def test_gemm_kernel_matches_numpy(hw):
    z = _rand2d(hw, seed=sum(hw))
    got = np.asarray(to_complex(ops.fft2d_gemm(from_complex(jnp.asarray(z)))))
    ref = np.fft.fft2(z)
    assert _rel(got, ref) < 1e-5


def test_gemm_kernel_leading_batch_and_padding():
    """Leading batch dims flatten, and batch=3 with block_batch=2 exercises
    the pad/unpad path."""
    z = _rand2d((2, 3, 16, 32), seed=7)
    got = np.asarray(to_complex(ops.fft2d_gemm(from_complex(jnp.asarray(z)))))
    assert _rel(got, np.fft.fft2(z)) < 1e-5
    z = _rand2d((3, 32, 32), seed=9)
    got = np.asarray(to_complex(
        ops.fft2d_gemm(from_complex(jnp.asarray(z)), block_batch=2)))
    assert _rel(got, np.fft.fft2(z)) < 1e-5


def test_gemm_empty_batch():
    x = from_complex(jnp.zeros((0, 16, 16), jnp.complex64))
    out = ops.fft2d_gemm(x)
    assert out.shape == (0, 16, 16)


def test_gemm_inverse_roundtrip():
    z = _rand2d((2, 64, 64), seed=3)
    x = from_complex(jnp.asarray(z))
    back = ops.fft2d_gemm(ops.fft2d_gemm(x), inverse=True)
    assert np.abs(np.asarray(to_complex(back)) - z).max() < 1e-4


def test_gemm_matches_stockham_oracle():
    """The GEMM kernel and the demoted Stockham-stage kernel are the same
    transform: bit-different, value-identical to fp32 noise."""
    z = _rand2d((2, 128, 64), seed=5)
    x = from_complex(jnp.asarray(z))
    gemm = np.asarray(to_complex(ops.fft2d_gemm(x)))
    stock = np.asarray(to_complex(ops.fft2d_fused(x)))
    assert _rel(gemm, stock) < 1e-4


def test_fft2_algo_names_route_to_each_kernel():
    """algo="fused" is now the GEMM kernel, "fused_stockham" the oracle —
    and both agree with numpy through the direct fft2 path."""
    z = _rand2d((64, 64), seed=4)
    x = from_complex(jnp.asarray(z))
    ref = np.fft.fft2(z)
    for algo in ("fused", "fused_stockham", "row_col"):
        got = np.asarray(to_complex(fft2(x, backend="pallas", algo=algo)))
        assert _rel(got, ref) < 1e-4, algo
    with pytest.raises(ValueError, match="pallas"):
        fft2(x, backend="jnp", algo="fused_stockham")


def test_split_table_reconstruction_accuracy():
    """The split hi/lo pair recovers the float64 table to ~bf16-eps^2: two
    orders of magnitude tighter than the straight bf16 cast."""
    rng = np.random.default_rng(0)
    t = rng.uniform(-1.0, 1.0, size=(64, 64))
    pair = np.asarray(split_table_np(t, jnp.bfloat16), np.float64)
    recon = pair[0] + pair[1]
    plain = np.asarray(jnp.asarray(t, jnp.bfloat16), np.float64)
    assert np.abs(recon - t).max() < 1e-4
    assert np.abs(recon - t).max() < 0.01 * np.abs(plain - t).max()


def test_gemm_tables_operand_count_and_shapes():
    plain = gemm_tables(64, 128, False, jnp.float32, "plain")
    comp = gemm_tables(64, 128, False, jnp.bfloat16, "compensated")
    assert len(plain) == len(comp) == 4        # F_W (re, im), F_H (re, im)
    assert [p.shape for p in plain] == [(128, 128)] * 2 + [(64, 64)] * 2
    for p, c in zip(plain, comp):
        assert c.shape == (2,) + p.shape       # stacked (hi, lo)
        assert c.dtype == jnp.bfloat16


def test_block_batch_shrinks_to_vmem_budget():
    """A block of images larger than VMEM holds shrinks to the largest
    batch divisor that fits; small images keep the requested block."""
    from repro.kernels.fft2d_gemm import VMEM_CAP, fit_block_batch
    img = 8 << 20                          # one fp32 1024^2 split-complex
    need = lambda bb: 2 * 2 * bb * img + (40 << 20)
    assert need(2) > VMEM_CAP >= need(1)
    assert fit_block_batch(2, 8, need) == 1
    assert fit_block_batch(8, 8, lambda bb: bb) == 8
    assert fit_block_batch(4, 6, lambda bb: bb) == 3   # divisor of batch
    assert fit_block_batch(4, 8, lambda bb: VMEM_CAP + 1) == 1


@pytest.mark.parametrize("hw", [(256, 256), (512, 512)])
def test_bf16_compensated_error_bound(hw):
    """The acceptance bound: compensated bf16 stays within 5e-3 relative of
    the fp64 reference, and beats the plain bf16 cast — the split-twiddle
    correction is what buys the margin at these sizes."""
    rng = np.random.default_rng(sum(hw))
    zr = rng.standard_normal(hw)
    zi = rng.standard_normal(hw)
    ref = np.fft.fft2(zr + 1j * zi)            # float64 reference
    x = SplitComplex(jnp.asarray(zr[None], jnp.bfloat16),
                     jnp.asarray(zi[None], jnp.bfloat16))
    errs = {}
    for variant in ("plain", "compensated"):
        out = ops.fft2d_gemm(x, variant=variant)
        got = (np.asarray(out.re, np.float64)
               + 1j * np.asarray(out.im, np.float64))[0]
        errs[variant] = np.linalg.norm(got - ref) / np.linalg.norm(ref)
    assert errs["compensated"] <= 5e-3, errs
    assert errs["compensated"] < errs["plain"], errs


def test_bf16_compensated_roundtrip():
    z = _rand2d((2, 128, 128), seed=6)
    x = SplitComplex(jnp.asarray(z.real, jnp.bfloat16),
                     jnp.asarray(z.imag, jnp.bfloat16))
    back = ops.fft2d_gemm(ops.fft2d_gemm(x, variant="compensated"),
                          inverse=True, variant="compensated")
    got = (np.asarray(back.re, np.float64)
           + 1j * np.asarray(back.im, np.float64))
    assert np.linalg.norm(got - z) / np.linalg.norm(z) < 1e-2
    assert back.re.dtype == jnp.bfloat16


def test_registry_variant_resolution_and_execution():
    """auto-variant: fp32 GEMM plans stay plain, bf16 ones resolve to
    compensated — and the compensated plan executes to the 5e-3 bound."""
    f32 = P.get_plan((128, 128), backend="pallas")
    assert (f32.algo, f32.variant) == ("fused", "plain")
    bf16 = P.get_plan((128, 128), backend="pallas", dtype=jnp.bfloat16)
    assert (bf16.algo, bf16.variant) == ("fused", "compensated")
    # explicit variants intern separately and never displace the auto plan
    explicit = P.get_plan((128, 128), backend="pallas", dtype=jnp.bfloat16,
                          variant="plain")
    assert explicit.variant == "plain"
    assert P.get_plan((128, 128), backend="pallas",
                      dtype=jnp.bfloat16) is bf16
    rng = np.random.default_rng(1)
    zr, zi = rng.standard_normal((128, 128)), rng.standard_normal((128, 128))
    x = SplitComplex(jnp.asarray(zr, jnp.bfloat16),
                     jnp.asarray(zi, jnp.bfloat16))
    y = bf16(x)
    got = (np.asarray(y.re, np.float64) + 1j * np.asarray(y.im, np.float64))
    ref = np.fft.fft2(zr + 1j * zi)
    assert np.linalg.norm(got - ref) / np.linalg.norm(ref) <= 5e-3


def test_autotune_grid_includes_variant_and_oracle():
    """The bf16 2-D pallas candidate grid measures both precision variants
    plus the Stockham oracle and the row-column baseline."""
    plan = P.FFTPlan(shape=(32, 32), dtype="bfloat16", algo="fused",
                     backend="pallas", block_batch=1, variant="compensated")
    labels = [lbl for lbl, _ in P._candidates(plan)]
    assert "fused/plain/bb1" in labels
    assert "fused_stockham/bb1" in labels
    assert "row_col" in labels
    cfgs = {(c.algo, c.block_batch, c.variant)
            for _, c in P._candidates(plan)}
    assert ("fused", 1, "plain") in cfgs
    # fixed_variant (an explicit variant= request) drops the other variant
    fixed = [lbl for lbl, _ in P._candidates(plan, fixed_variant=True)]
    assert "fused/plain/bb1" not in fixed
    # 3-D grids have no Stockham oracle
    plan3 = dataclasses.replace(plan, shape=(16, 16, 16))
    labels3 = [lbl for lbl, _ in P._candidates(plan3)]
    assert "fused_stockham/bb1" not in labels3
    # the plan's own config (fused/bb1) is the "default" candidate
    assert "default" in labels3 and "row_col" in labels3
    assert "fused/bb2" in labels3
