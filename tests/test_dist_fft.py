"""Distributed pencil FFT == single-device FFT (8 fake devices, subprocess)."""
from _subproc import run_with_devices

# Mesh construction goes through repro.launch.mesh.make_mesh and shard_map
# through repro.dist._compat, the repo's single entry points for both.
CODE = r"""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core.complexmath import from_complex, to_complex, SplitComplex
from repro.core import fft2d
from repro.dist import pencil
from repro.launch.mesh import make_mesh

rng = np.random.default_rng(0)
mesh = make_mesh((8,), ("data",))
H = W = 128
x = (rng.standard_normal((H, W)) + 1j*rng.standard_normal((H, W))).astype(np.complex64)
sh = NamedSharding(mesh, P("data", None))
xs = from_complex(jnp.asarray(x))
xs = SplitComplex(jax.device_put(xs.re, sh), jax.device_put(xs.im, sh))
ref = np.fft.fft2(x)

for chunks in (1, 4):
    got = np.asarray(to_complex(pencil.pfft2(xs, mesh, "data", chunks=chunks))).T
    assert np.abs(got - ref).max()/np.abs(ref).max() < 1e-4, chunks
got = np.asarray(to_complex(pencil.pfft2(xs, mesh, "data", transposed_output=False)))
assert np.abs(got - ref).max()/np.abs(ref).max() < 1e-4
back = pencil.pfft2(pencil.pfft2(xs, mesh, "data", transposed_output=False),
                    mesh, "data", inverse=True, transposed_output=False)
assert np.abs(np.asarray(to_complex(back)) - x).max() < 1e-3

# distributed path is pinned to the single-chip plan-registry path too, not
# just to numpy: pfft2 == core.fft2 on the same split-complex input
loc = np.asarray(to_complex(fft2d.fft2(from_complex(jnp.asarray(x)))))
assert np.abs(got - loc).max()/np.abs(loc).max() < 1e-5

# hierarchical two-hop (2 pods x 4)
mesh2 = make_mesh((2, 4), ("pod", "data"))
shp = NamedSharding(mesh2, P(("pod", "data"), None))
xs2 = SplitComplex(jax.device_put(jnp.real(jnp.asarray(x)), shp),
                   jax.device_put(jnp.imag(jnp.asarray(x)), shp))
got = np.asarray(to_complex(pencil.pfft2_hierarchical(xs2, mesh2))).T
assert np.abs(got - ref).max()/np.abs(ref).max() < 1e-4

# 3-D pencil FFT over a 2-D process grid (the paper's future-work case)
mesh3 = make_mesh((2, 4), ("data", "model"))
X = Y = 16; Z = 32
x3 = (rng.standard_normal((X, Y, Z)) + 1j*rng.standard_normal((X, Y, Z))).astype(np.complex64)
sh3 = NamedSharding(mesh3, P("data", "model", None))
z3 = from_complex(jnp.asarray(x3))
z3 = SplitComplex(jax.device_put(z3.re, sh3), jax.device_put(z3.im, sh3))
out3 = pencil.pfft3(z3, mesh3)
got3 = np.asarray(to_complex(out3)).transpose(2, 1, 0)   # (Z,Y,X) -> (X,Y,Z)
ref3 = np.fft.fftn(x3)
assert np.abs(got3 - ref3).max()/np.abs(ref3).max() < 1e-4

# backend= plumbs through pfft3's local passes to the plan registry (the
# same routing the single-chip fft3 has).  Z=32 resolves to "naive" and
# demotes (interned under the jnp key); Z=512 has a four_step kernel path
# and must intern a live pallas plan.
from repro.core import plan as plan_lib
out3p = pencil.pfft3(z3, mesh3, backend="pallas")
got3p = np.asarray(to_complex(out3p)).transpose(2, 1, 0)
assert np.abs(got3p - ref3).max()/np.abs(ref3).max() < 1e-4
pk = plan_lib._plan_key((Z,), jnp.float32, False, "jnp", "c2c")
assert pk in plan_lib._PLAN_CACHE, "pfft3 demoted Z pass missing from registry"

Zk = 512
xk = (rng.standard_normal((X, Y, Zk)) + 1j*rng.standard_normal((X, Y, Zk))).astype(np.complex64)
zk = from_complex(jnp.asarray(xk))
zk = SplitComplex(jax.device_put(zk.re, sh3), jax.device_put(zk.im, sh3))
outk = pencil.pfft3(zk, mesh3, backend="pallas")
gotk = np.asarray(to_complex(outk)).transpose(2, 1, 0)
refk = np.fft.fftn(xk)
assert np.abs(gotk - refk).max()/np.abs(refk).max() < 1e-4
pk = plan_lib._plan_key((Zk,), jnp.float32, False, "pallas", "c2c")
assert pk in plan_lib._PLAN_CACHE, "pfft3 local Z pass never hit the registry"
assert plan_lib._PLAN_CACHE[pk].backend == "pallas"

# distributed 1-D four-step, forward + inverse roundtrip
mesh = make_mesh((8,), ("data",))
n = 1 << 14
v = (rng.standard_normal(n) + 1j*rng.standard_normal(n)).astype(np.complex64)
sh1 = NamedSharding(mesh, P("data"))
vs = from_complex(jnp.asarray(v))
vs = SplitComplex(jax.device_put(vs.re, sh1), jax.device_put(vs.im, sh1))
out = pencil.pfft1d(vs, mesh, "data")
p, h, w = 8, 8, n // 8
while (w > 2*h) and (w % 2 == 0) and ((w//2) % p == 0): h, w = h*2, w//2
assert (h, w) == pencil.fourstep_split(n, p)
got = np.asarray(to_complex(out)).reshape(h, w).T.reshape(-1)
ref1 = np.fft.fft(v)
assert np.abs(got - ref1).max()/np.abs(ref1).max() < 1e-4
back = pencil.pfft1d(out, mesh, "data", inverse=True)
assert np.abs(np.asarray(to_complex(back)) - v).max() < 1e-3
print("DIST_FFT_OK")
"""


def test_pencil_fft_8dev():
    out = run_with_devices(CODE, 8)
    assert "DIST_FFT_OK" in out
