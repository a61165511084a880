"""Multi-device sharding tests.

These run in SUBPROCESSES with XLA_FLAGS=--xla_force_host_platform_device_count=8
because the main pytest process must keep seeing exactly 1 CPU device (the
smoke tests and benches depend on it, and jax locks the device count at
first init).
"""
import json
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(code: str) -> subprocess.CompletedProcess:
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=SRC)
    return subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=900)


def test_debug_mesh_train_prefill_decode_lower():
    """Every family lowers+compiles train/prefill/decode on a 2x4 mesh."""
    code = """
import dataclasses, jax
from repro.configs import get_config
from repro.launch.shapes import InputShape, pad_vocab
from repro.launch import dryrun as DR
from repro.launch.compat import named_shardings
from repro.launch.mesh import make_debug_mesh
from repro.launch.sharding import launch_cfg

mesh = make_debug_mesh((2, 4), ("data", "model"))
shapes = [InputShape("t", 256, 8, "train"), InputShape("p", 256, 8, "prefill"),
          InputShape("d", 256, 8, "decode")]
for arch in ["tinyllama_1_1b", "qwen3_moe_30b_a3b", "mamba2_370m",
             "hymba_1_5b", "whisper_medium"]:
    c0 = get_config(arch)
    c0 = dataclasses.replace(
        c0, n_layers=2, encoder_layers=min(c0.encoder_layers, 2), d_model=512,
        n_heads=8 if c0.n_heads else 0,
        n_kv_heads=(4 if c0.n_kv_heads >= 4 else c0.n_kv_heads) if c0.n_heads else 0,
        head_dim=64 if c0.n_heads else 0,
        d_ff=min(c0.d_ff, 1024) if c0.d_ff else 0, vocab=1024,
        n_experts=min(c0.n_experts, 8),
        window=min(c0.window, 64) if c0.window else 0,
        n_frontend_tokens=min(c0.n_frontend_tokens, 16))
    for shape in shapes:
        cfg = launch_cfg(pad_vocab(c0), mesh, shape)
        fn, args, in_s, out_s = DR.build_step(cfg, shape, mesh)
        with jax.set_mesh(mesh):
            jax.jit(fn, in_shardings=named_shardings(mesh, in_s),
                    out_shardings=named_shardings(mesh, out_s)
                    ).lower(*args).compile()
        print("OK", arch, shape.name)
print("ALL_LOWERED")
"""
    p = _run(code)
    assert "ALL_LOWERED" in p.stdout, p.stdout + p.stderr


def test_sharded_execution_matches_single_device():
    """A sharded train step produces the same loss as unsharded (8 devices)."""
    code = """
import dataclasses, jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.launch.shapes import InputShape, pad_vocab
from repro.launch import dryrun as DR
from repro.launch.mesh import make_debug_mesh
from repro.launch.sharding import launch_cfg
from repro.models.lm import model as M

c0 = get_config("tinyllama_1_1b").reduced()
c0 = dataclasses.replace(c0, vocab=512, dtype="float32")
key = jax.random.key(0)
params = M.init_params(c0, key)
batch = {"tokens": jax.random.randint(key, (8, 64), 0, c0.vocab)}
loss_single = float(M.loss_fn(c0, params, batch))

mesh = make_debug_mesh((2, 4), ("data", "model"))
shape = InputShape("t", 64, 8, "train")
cfg = launch_cfg(c0, mesh, shape)
with jax.set_mesh(mesh):
    loss_sharded = float(jax.jit(lambda p, b: M.loss_fn(cfg, p, b))(params, batch))
print("SINGLE", loss_single, "SHARDED", loss_sharded)
assert abs(loss_single - loss_sharded) < 1e-3, (loss_single, loss_sharded)
print("MATCH")
"""
    p = _run(code)
    assert "MATCH" in p.stdout, p.stdout + p.stderr


def test_parallel_client_round_lowers_on_mesh():
    """The client-parallel FL round shards over the data axis."""
    code = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.federated.client import ClientConfig
from repro.federated.sim import parallel_client_round
from repro.launch.compat import named_shardings
from repro.launch.mesh import make_debug_mesh
from repro.models.mlp_cnn import make_mlp

mesh = make_debug_mesh((8,), ("data",))
model = make_mlp(input_dim=32, hidden=(16,), n_classes=4)
ccfg = ClientConfig(epochs=1, batches_per_epoch=1, batch_size=4)
key = jax.random.key(0)
params = model.init(key)
M_sel, cap = 8, 16
xs = jax.random.normal(key, (M_sel, cap, 32))
ys = jax.random.randint(key, (M_sel, cap), 0, 4)
nv = jnp.full((M_sel,), cap)
ek = jnp.full((M_sel,), 1)
sg = jnp.zeros((M_sel,))
keys = jax.random.split(key, M_sel)

with jax.set_mesh(mesh):
    fn = jax.jit(lambda *a: parallel_client_round(model, ccfg, *a),
                 in_shardings=named_shardings(
                     mesh, (None, P("data"), P("data"), P("data"),
                            P("data"), P("data"), P("data"))))
    stacked, new_params = fn(params, xs, ys, nv, ek, sg, keys)
hlo = jax.jit(lambda *a: parallel_client_round(model, ccfg, *a)).lower(
    params, xs, ys, nv, ek, sg, keys).as_text()
assert np.isfinite(np.asarray(jax.tree.leaves(new_params)[0])).all()
print("PARALLEL_ROUND_OK")
"""
    p = _run(code)
    assert "PARALLEL_ROUND_OK" in p.stdout, p.stdout + p.stderr
