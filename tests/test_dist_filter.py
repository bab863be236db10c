"""Batched real-input pencil FFTs and the spectral operator between them:
``prfft2`` / ``pirfft2`` on stacks of fields, and ``pfilter2`` against the
float64 ``numpy.fft.irfft2(rfft2(x) * g)``, on 4 fake devices in one
subprocess."""
from _subproc import run_with_devices

CODE = r"""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core.complexmath import SplitComplex
from repro.dist import pencil
from repro.launch.mesh import make_mesh

rng = np.random.default_rng(5)
mesh = make_mesh((4,), ("data",))


def rel_l2(got, ref):
    return float(np.linalg.norm(np.asarray(got, np.float64) - ref)
                 / np.linalg.norm(ref))


def rows_of(x):
    spec = P(*([None] * (x.ndim - 2)), "data", None)
    return jax.device_put(jnp.asarray(x), NamedSharding(mesh, spec))


def helmholtz(h, w, nu_dt):
    ky = np.fft.fftfreq(h, 1.0 / h)[:, None]
    kx = np.fft.rfftfreq(w, 1.0 / w)[None, :]
    return 1.0 / (1.0 + nu_dt * (ky ** 2 + kx ** 2))


def d_dy(h, w):
    ky = np.fft.fftfreq(h, 1.0 / h)
    ky[h // 2] = 0.0                 # the ky = -H/2 row: not Hermitian
    return np.broadcast_to(1j * ky[:, None], (h, w // 2 + 1)).copy()


def naive(z, op, axis):
    # the trap: the packed row 0 (DC + i*Nyquist) multiplied as it stands
    return pencil._times(z, op.rows)


def traced_bytes(fn, *args):
    # the wire log counts at trace time: bytes of one traced call
    pencil.reset_wire_log()
    out = fn(*args)
    return out, pencil.logged_exchange_bytes()


fwd = jax.jit(lambda a: pencil.prfft2(a, mesh, "data"))
inv = jax.jit(lambda z: pencil.pirfft2(z, mesh, "data"))
filt = jax.jit(lambda a, g: pencil.pfilter2(a, g, mesh, "data"))
naive_filt = jax.jit(lambda a, g: pencil.pfilter2(a, g, mesh, "data"))

for H, W in ((64, 64), (128, 256)):
    for B in (1, 3):
        x = rng.standard_normal((B, H, W)).astype(np.float32)
        xs = rows_of(x)
        wire = 2 * pencil.exchange_bytes(H, W, 4, real=True, batch=B)

        # batched prfft2 / pirfft2 == the per-field calls
        spec, w_fwd = traced_bytes(fwd, xs)
        assert spec.shape == (B, W // 2, H), spec.shape
        back, w_inv = traced_bytes(inv, spec)
        assert w_fwd + w_inv == wire, (w_fwd, w_inv, wire)
        for b in range(B):
            one = fwd(rows_of(x[b]))
            np.testing.assert_allclose(np.asarray(spec.re[b]),
                                       np.asarray(one.re), rtol=0, atol=1e-5)
            np.testing.assert_allclose(np.asarray(spec.im[b]),
                                       np.asarray(one.im), rtol=0, atol=1e-5)
            np.testing.assert_allclose(np.asarray(back[b]),
                                       np.asarray(inv(one)), rtol=0,
                                       atol=1e-5)
        assert rel_l2(back, x.astype(np.float64)) < 1e-6

        x64 = x.astype(np.float64)
        hm = helmholtz(H, W, 3e-3)
        for name, g in (("helmholtz", hm), ("d_dy", d_dy(H, W)),
                        ("d_dy helmholtz", d_dy(H, W) * hm)):
            op = pencil.shard_half_operator(g, mesh, "data")
            ref = np.fft.irfft2(np.fft.rfft2(x64) * g, s=(H, W))
            out, w_filt = traced_bytes(filt, xs, op)
            if name == "helmholtz":          # the shape's first trace
                assert w_filt == wire, (w_filt, wire)
            err = rel_l2(out, ref)
            assert err <= 1e-6, (H, W, B, name, err)
            # the output stays row-sharded on 4 distinct devices
            shards = out.addressable_shards
            assert len({s.device for s in shards}) == 4
            assert {s.data.shape for s in shards} == {(B, H // 4, W)}
            if name == "d_dy":
                continue             # equal DC and Nyquist columns: no trap
            # the trap: where the DC and Nyquist columns differ, multiplying
            # the packed row 0 as it stands misses by orders of magnitude
            real_apply = pencil._apply_half_operator
            pencil._apply_half_operator = naive
            try:
                bad = rel_l2(naive_filt(xs, op), ref)
            finally:
                pencil._apply_half_operator = real_apply
            assert bad > 1e3 * 1e-6, (H, W, B, name, bad)

# the stage names reach the compiled program's op metadata
x = rows_of(rng.standard_normal((2, 64, 64)).astype(np.float32))
op = pencil.shard_half_operator(helmholtz(64, 64, 1e-3), mesh, "data")
hlo = filt.lower(x, op).compile().as_text()
for scope in ("pencil.row_rfft", "pencil.a2a", "pencil.col_fft",
              "pencil.operator", "pencil.col_ifft", "pencil.row_irfft"):
    assert scope in hlo, scope
assert hlo.count("all-to-all") >= 4
print("DIST_FILTER_OK")
"""


def test_pfilter2_and_batched_prfft2_4dev():
    out = run_with_devices(CODE, 4)
    assert "DIST_FILTER_OK" in out


def test_exchange_bytes_counts_the_batch():
    from repro.dist import pencil
    one = pencil.exchange_bytes(4096, 4096, 4, real=True)
    assert one == 16 * 2 ** 20                  # 16 MiB per field per leg
    assert pencil.exchange_bytes(4096, 4096, 4, real=True, batch=8) \
        == 8 * one
