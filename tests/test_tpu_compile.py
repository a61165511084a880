"""Compile every Pallas kernel for a described TPU v5e, with no chip.

The TPU compiler (Mosaic, via libtpu) is installed even where no chip is
attached, and it compiles for a topology that is only described.  These
tests compile each kernel of the main path at the widths of the paper's
MNIST MLP (784-200-100-10: D = 178,110 parameters, 178,176 padded to the
2048-wide tiles; largest leaf 156,800) for one v5e chip, so a block shape
or a VMEM footprint the chip refuses fails here instead of on the chip.
They compile only: nothing runs, and no number is checked.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and test collection must not
depend on which process got it.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.ce_loss.kernel import ce_loss_kernel
from repro.kernels.cohort_gather.kernel import cohort_gather_kernel
from repro.kernels.delta_codec.kernel import delta_codec_kernel
from repro.kernels.delta_codec.ops import MAX_KERNEL_D
from repro.kernels.prefix_avg.kernel import prefix_avg_kernel
from repro.kernels.weighted_avg.kernel import weighted_avg_kernel

D_PAD = 178_176        # MLP parameter count padded to the 2048-wide tile
W1 = 156_800           # 784 x 200, the MLP's largest leaf
N_CLIENTS = 300        # the paper's full protocol
N_VAL = 5000


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    saved_log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"   # no compiler logs under /tmp
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    saved_cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", saved_cache)
        compilation_cache.reset_cache()
        if saved_log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = saved_log_dir


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(one_chip, fn, *shapes, **static):
    """Compile fn(*args, **static) for one described chip; returns the
    HLO text, which must hold the kernel as a TPU custom call."""
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(lambda *a: fn(*a, **static)).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    return text


@pytest.mark.parametrize("m,r,dtype", [(3, 150, jnp.float32),
                                       (10, 500, jnp.float32),
                                       (3, 150, jnp.bfloat16)])
def test_prefix_avg_compiles_for_v5e(one_chip, m, r, dtype):
    """The paper's M = 3 (M % 8 != 0) with its default 50*M walks, M = 10,
    and 16-bit client models."""
    _compile(one_chip, prefix_avg_kernel,
             ((m, D_PAD), dtype), ((r, m), jnp.int32),
             ((m,), jnp.float32))


def test_cohort_gather_compiles_for_v5e(one_chip):
    _compile(one_chip, cohort_gather_kernel,
             ((N_CLIENTS, D_PAD), jnp.float32), ((3,), jnp.int32))


@pytest.mark.parametrize("d", [W1, MAX_KERNEL_D])
@pytest.mark.parametrize("codec", ["quant8", "topk", "quant8_topk"])
def test_delta_codec_compiles_for_v5e(one_chip, codec, d):
    """Every codec fits VMEM at the MLP's largest leaf and at the largest
    row the ops wrapper routes to the kernel."""
    k = max(1, d // 100) if codec != "quant8" else 0
    _compile(one_chip, delta_codec_kernel, ((3, d), jnp.float32),
             codec=codec, k=k)


def test_weighted_avg_compiles_for_v5e(one_chip):
    """The dense GTG oracle: 150 walks x M = 3 prefix-weight rows."""
    _compile(one_chip, weighted_avg_kernel, ((3, D_PAD), jnp.float32),
             ((450, 3), jnp.float32))


def test_ce_loss_compiles_for_v5e(one_chip):
    """The utility's per-model loss over the validation set: 10 classes,
    one block as wide as the logits."""
    _compile(one_chip, ce_loss_kernel, ((N_VAL, 10), jnp.float32),
             ((N_VAL,), jnp.int32), block_v=10)
