"""Compile every Pallas kernel for a described TPU v5e, with no chip.

The TPU compiler (Mosaic, via libtpu) is installed even where no chip is
attached, and it compiles for a topology that is only described.  These
tests compile each kernel of the main path at the widths of the paper's
MNIST MLP (784-200-100-10: D = 178,110 parameters, 178,176 padded to the
2048-wide tiles; largest leaf 156,800) for one v5e chip, so a block shape
or a VMEM footprint the chip refuses fails here instead of on the chip.
They compile only: nothing runs, and no number is checked.  The cohort
gather, which is XLA's own, is checked in its compiled round loop: no
op there may be as large as the client table.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and test collection must not
depend on which process got it.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.ce_loss.kernel import ce_loss_kernel
from repro.kernels.cohort_gather import cohort_take
from repro.kernels.delta_codec.kernel import delta_codec_kernel
from repro.kernels.delta_codec.ops import MAX_KERNEL_D
from repro.kernels.prefix_avg.kernel import prefix_avg_kernel
from repro.kernels.weighted_avg.kernel import weighted_avg_kernel

D_PAD = 178_176        # MLP parameter count padded to the 2048-wide tile
W1 = 156_800           # 784 x 200, the MLP's largest leaf
N_CLIENTS = 300        # the paper's full protocol
CAP, F = 53, 784       # rows of the largest client, MNIST features
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


# ops that only name or forward a loop operand: they move no bytes
_NO_COPY = ("tuple", "get-tuple-element", "parameter", "bitcast")
_INSTR = re.compile(r"\s*(?:ROOT\s+)?%?\S+\s*=\s*\w+\[([0-9,]*)\]"
                    r"(?:\{[^}]*\})?\s+([\w-]+)\(")


def _while_body_ops(hlo: str) -> list[tuple[str, int]]:
    """(opcode, element count) of every array-shaped instruction in the
    body of each while loop of a compiled program (the round loop, and
    any loop nested in it)."""
    bodies = re.findall(r"\bwhile\(.*\bbody=%?([\w.-]+)", hlo)
    assert bodies, "no while loop in the compiled program"
    ops = []
    for body in bodies:
        comp = re.search(r"^%?" + re.escape(body) + r" [^\n]*\{\n(.*?)^\}",
                         hlo, re.M | re.S)
        for line in comp.group(1).splitlines():
            m = _INSTR.match(line)
            if m:
                dims = [int(d) for d in m.group(1).split(",") if d]
                ops.append((m.group(2), int(np.prod(dims, dtype=np.int64))))
    return ops


@pytest.mark.parametrize("replicas", [8, None])
def test_cohort_gather_touches_no_whole_table_per_round(
        one_chip, monkeypatch, replicas):
    """A 4-round scan of the dense cohort gather, as the chip compiles it:
    the grid's replica vmap (8 replica stacks, (8, 3) ids a round) and
    the solo scan ((3,) ids).  Only the cohort's rows may move inside the
    loop: no reshape, pad or per-replica slice of the whole
    (N, cap, F) stack."""
    # trace as on the chip: code that routes on the backend sees a TPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    lead = () if replicas is None else (replicas,)
    take = (cohort_take if replicas is None
            else jax.vmap(cohort_take))

    def run(stack, ids):
        def step(acc, ids_t):
            return acc + jnp.sum(take(stack, ids_t)), None
        return jax.lax.scan(step, jnp.float32(0), ids)[0]

    args = [jax.ShapeDtypeStruct(lead + (N_CLIENTS, CAP, F), jnp.float32,
                                 sharding=one_chip),
            jax.ShapeDtypeStruct((4,) + lead + (3,), jnp.int32,
                                 sharding=one_chip)]
    hlo = jax.jit(run).lower(*args).compile().as_text()
    table = N_CLIENTS * CAP * F
    big = [(op, n) for op, n in _while_body_ops(hlo)
           if n >= table and op not in _NO_COPY]
    assert big == [], big


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
