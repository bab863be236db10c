"""Compile rehearsals: the main-path Pallas kernels compile for a TPU v5e.

Each test compiles one kernel (``interpret=False``) at serving size for a
described, unattached v5e chip and asserts that the compiled program
holds the Mosaic kernel (``tpu_custom_call``).  Nothing runs; the TPU
compiler's verdict — scoped VMEM, shape casts, gathers — is what these
guard.  The topology is described inside a fixture, never at import, so
every test worker collects the same tests; where it cannot be described
the tests skip from that fixture.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.complexmath import SplitComplex
from repro.kernels import ops

BATCH = 2          # > block_batch, so the grid pipelines (double buffers)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compilation cache off: a
    compile for an unattached chip cannot be read back from it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _planes(shape, dtype, sharding):
    sds = jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    return SplitComplex(sds, sds)


@pytest.mark.parametrize("n,inverse,dtype,variant", [
    (256, False, jnp.float32, "plain"),
    (256, True, jnp.float32, "plain"),
    (1024, False, jnp.float32, "plain"),
    (1024, True, jnp.float32, "plain"),
    (1024, False, jnp.bfloat16, "compensated"),
    (1024, True, jnp.bfloat16, "compensated"),
])
def test_fft2d_gemm_compiles(one_chip, n, inverse, dtype, variant):
    x = _planes((BATCH, n, n), dtype, one_chip)
    txt = _compiled_text(
        lambda x: ops.fft2d_gemm(x, inverse=inverse, variant=variant,
                                 interpret=False), x)
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("n", [256, 1024])
def test_rfft2d_fused_compiles(one_chip, n):
    x = jax.ShapeDtypeStruct((BATCH, n, n), jnp.float32, sharding=one_chip)
    txt = _compiled_text(lambda x: ops.rfft2d_fused(x, interpret=False), x)
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("n", [256, 1024])
def test_irfft2d_fused_compiles(one_chip, n):
    xf = _planes((BATCH, n, n // 2 + 1), jnp.float32, one_chip)
    txt = _compiled_text(lambda x: ops.irfft2d_fused(x, interpret=False), xf)
    assert "tpu_custom_call" in txt
