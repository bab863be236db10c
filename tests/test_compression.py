"""Compressed gradient collectives (4 fake devices, subprocess) and the
error-feedback residual in the train step."""
import jax
import jax.numpy as jnp
import numpy as np

from _subproc import run_with_devices
from repro.dist.compression import wire_bytes

# shard_map comes from repro.dist._compat and the mesh from
# repro.launch.mesh.make_mesh (Auto axis types).
CODE = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.dist._compat import shard_map
from repro.dist.compression import psum_compressed
from repro.launch.mesh import make_mesh

mesh = make_mesh((4,), ("data",))
x = jax.random.normal(jax.random.PRNGKey(0), (4, 64)) * 3.0
want = np.asarray(x.sum(0))
for method, tol in (("none", 1e-6), ("bf16", 0.1), ("int8", 0.3)):
    fn = shard_map(lambda v: psum_compressed(v[0], "data", method),
                   mesh=mesh, in_specs=(P("data", None),), out_specs=P())
    got = np.asarray(fn(x))
    err = np.abs(got - want).max() / (np.abs(want).max() + 1e-9)
    assert err < tol, (method, err)
print("COMPRESS_OK")
"""


def test_psum_compressed_4dev():
    assert "COMPRESS_OK" in run_with_devices(CODE, 4)


def test_wire_bytes():
    tree = {"a": jnp.zeros((4, 8)), "b": jnp.zeros((16,))}
    assert wire_bytes(tree, "none") == 48 * 4
    assert wire_bytes(tree, "bf16") == 48 * 2
    assert wire_bytes(tree, "int8") == 48


def test_wire_bytes_mixed_dtypes():
    """bf16 compression never inflates an already-narrow leaf."""
    tree = {"w": jnp.zeros((4, 8), jnp.float32),        # 32 elems x 4 B
            "b": jnp.zeros((16,), jnp.bfloat16)}        # 16 elems x 2 B
    assert wire_bytes(tree, "none") == 32 * 4 + 16 * 2
    assert wire_bytes(tree, "bf16") == 32 * 2 + 16 * 2  # bf16 leaf unchanged
    assert wire_bytes(tree, "int8") == 48


def test_error_feedback_residual_carries():
    """bf16 compression keeps the quantisation error and replays it."""
    from repro.models.config import ModelConfig
    from repro.models import model as M
    from repro.train import optimizer as opt_lib
    from repro.train.train_step import make_train_step, init_opt_state

    cfg = ModelConfig(name="t", family="dense", d_model=32, n_heads=2,
                      n_kv_heads=2, d_ff=64, vocab_size=64,
                      block_pattern=("attn_mlp",), repeat=1, head_dim=16,
                      attn_chunk=8, vocab_pad_multiple=32)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    ocfg = opt_lib.AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=10)
    step = make_train_step(cfg, ocfg, compress="bf16")
    state = init_opt_state(cfg, ocfg, params, compress="bf16")
    assert "ef_residual" in state
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 64)
    batch = {"tokens": toks, "labels": toks}
    p2, s2, m = jax.jit(step)(params, state, batch)
    assert jnp.isfinite(m["loss"])
    resid_norm = sum(float(jnp.abs(r.astype(jnp.float32)).sum())
                     for r in jax.tree.leaves(s2["ef_residual"]))
    assert resid_norm > 0.0          # quantisation error was captured
