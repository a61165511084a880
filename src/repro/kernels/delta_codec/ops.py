"""Public wrapper: fused upload-codec roundtrip on a stacked cohort pytree.

`delta_codec_roundtrip(stacked, params, codec)` replaces the engines' old
per-client `vmap(codec_roundtrip)` chain: for each leaf, the (M, *s)
stacked client weights minus the (*s,) reference become an (M, d) delta
matrix, roundtripped in one fused pass, and added back.  Per-leaf k for
the sparse codecs follows the oracle's rule (`leaf_topk_k`), so results
match `federated.compression` bitwise up to jit fusion of the final add.

Routing: on TPU (`use_kernel=None`) every leaf of MIN_KERNEL_D to
MAX_KERNEL_D columns runs the Pallas kernel, compiled natively.  The
kernel keeps a whole row resident in VMEM as one (8, d/8) tile.  VMEM is
the limit, found by compiling f32 rows of 3 clients for a v5e: all three
codecs compile at 2^19 columns; at 2^20 quant8_topk is refused
(RESOURCE_EXHAUSTED in vmem), at 2^21 all three are.  MAX_KERNEL_D =
2^18 stays one halving below that and above the MNIST MLP's largest leaf
(156,800); tests/test_tpu_compile.py compiles every codec at both sizes.
Tiny leaves, leaves above the gate, and non-TPU backends take the rowwise
jnp ref — one XLA fusion per leaf (the interpret-mode emulation of the
in-kernel MSB-descent select would be pure overhead off-TPU).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any

import jax

from repro.kernels import default_interpret
from repro.kernels.delta_codec.kernel import delta_codec_kernel
from repro.kernels.delta_codec.ref import delta_codec_ref

PyTree = Any

MIN_KERNEL_D = 2048     # below this the ref fusion wins
MAX_KERNEL_D = 1 << 18  # VMEM fit on a v5e: see the module docstring


@partial(jax.jit, static_argnames=("codec", "frac", "use_kernel",
                                   "interpret"))
def delta_codec_roundtrip(stacked: PyTree, params: PyTree, codec: str, *,
                          frac: float | None = None,
                          use_kernel: bool | None = None,
                          interpret: bool | None = None) -> PyTree:
    """stacked leaves (M, *s), params leaves (*s,) -> roundtripped stack.

    `frac=None` takes the oracle's `TOPK_FRAC`; `interpret=None` derives
    from the backend; `use_kernel=None` enables the Pallas kernel exactly
    where it compiles natively (TPU).
    """
    # deferred: compression sits under repro.federated, whose __init__
    # pulls in the engines — which import this package at module scope
    from repro.federated.compression import TOPK_FRAC, leaf_topk_k

    if codec == "identity":
        return stacked
    if frac is None:
        frac = TOPK_FRAC
    if interpret is None:
        interpret = default_interpret()
    if use_kernel is None:
        use_kernel = not interpret

    def one(leaf: jax.Array, ref_leaf: jax.Array) -> jax.Array:
        m = leaf.shape[0]
        d = math.prod(leaf.shape[1:])
        delta = leaf.reshape(m, d) - ref_leaf.reshape(1, d)
        k = leaf_topk_k(d, frac) if codec != "quant8" else 0
        if use_kernel and MIN_KERNEL_D <= d <= MAX_KERNEL_D:
            rt = delta_codec_kernel(delta, codec=codec, k=k,
                                    interpret=interpret)
        else:
            rt = delta_codec_ref(delta, codec, k=k)
        return (ref_leaf.reshape(1, d) + rt).reshape(leaf.shape)

    return jax.tree.map(one, stacked, params)
