"""Pallas TPU kernel: streaming prefix-subset averaging (DESIGN.md §14).

Along one GTG permutation walk the prefix ModelAverage is a running sum,

    S_j = S_{j-1} + n_{pi(j)} * W[pi(j)],     wbar_j = S_j / N_j,

so the dense `(R*M, M) x (M, D)` contraction of `kernels/weighted_avg`
(O(R*M^2*D) FLOPs for the full prefix family) collapses to one gather +
cumulative sum per walk: O(R*M*D) FLOPs, the minimum to materialise the
R*M prefix models at all.

Layout:
    stacked (M, D)    — client models flattened to one parameter axis
    idx     (R*M,)    — permutations flattened walk-major (scalar prefetch)
    scale   (R*M,)    — n_k gathered in walk order (scalar prefetch)
    ncum    (R*M,)    — running subset sizes N_j per position (prefetch)
    out     (R, M, D) — out[r, j] = prefix-average model j of walk r,
                        returned reshaped (free) to (R*M, D) walk-major

Grid: (R, D // BLOCK_D).  Program (r, i) keeps the (M, BLOCK_D) tile of W
resident in VMEM and walks permutation r front to back, accumulating the
running sum in f32 and emitting one averaged row per step; the row gather
is a dynamic VMEM slice driven by the prefetched indices (SMEM).  The
output block is (squeezed, M, BLOCK_D) on the 3-D (R, M, D) array: Mosaic
requires a block's last two dims to be (8k, 128k) or the array's own, so
an (M, BLOCK_D) block of a flat (R*M, D) output is refused whenever
M % 8 != 0 (the paper's M = 3), while the walk axis as a squeezed leading
dim leaves M equal to the array's second-minor dim at any M.  The
j-loop is strictly left-to-right — that accumulation order is the
contract that makes chunked and unchunked evaluation bit-identical
(`core/shapley_batched.gtg_shapley_streaming`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_D = 2048  # lane-dim tile; multiple of 128 (MXU) and 8*128 (VREG)


def _prefix_kernel(idx_ref, scale_ref, ncum_ref, stacked_ref, out_ref):
    # idx/scale/ncum: (R*M,) in SMEM; stacked_ref: (M, BLOCK_D) in VMEM;
    # out_ref: (M, BLOCK_D) — walk r's M prefix models for this D-block
    # (the walk axis of the (R, M, D) output is squeezed away)
    r = pl.program_id(0)
    m = stacked_ref.shape[0]

    def step(j, acc):
        p = r * m + j
        row = stacked_ref[pl.ds(idx_ref[p], 1), :].astype(jnp.float32)
        acc = acc + scale_ref[p] * row
        out_ref[pl.ds(j, 1), :] = (acc / ncum_ref[p]).astype(out_ref.dtype)
        return acc

    jax.lax.fori_loop(0, m, step,
                      jnp.zeros((1, out_ref.shape[1]), jnp.float32))


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def prefix_avg_kernel(stacked: jax.Array, perms: jax.Array, n_k: jax.Array,
                      *, block_d: int = BLOCK_D,
                      interpret: bool = False) -> jax.Array:
    """stacked (M, D) x perms (R, M) x n_k (M,) -> (R*M, D) prefix models.

    D % block_d == 0 (callers pad; see ops.py).  Row r*M + j holds the
    ModelAverage of the walk prefix perms[r, :j+1].
    """
    m, d = stacked.shape
    r = perms.shape[0]
    assert perms.shape == (r, m), (perms.shape, (r, m))
    assert d % block_d == 0, (d, block_d)
    if stacked.dtype.itemsize < 4:
        # Mosaic cannot prove a dynamic row slice of a packed 16-bit tile
        # aligned and refuses it; the walk accumulates in f32 anyway, so
        # widening first and rounding the output once changes no bit
        return prefix_avg_kernel(stacked.astype(jnp.float32), perms, n_k,
                                 block_d=block_d,
                                 interpret=interpret).astype(stacked.dtype)

    scale2 = jnp.take(n_k, perms).astype(jnp.float32)      # (R, M)
    ncum = jnp.cumsum(scale2, axis=1).reshape(-1)          # (R*M,)
    scale = scale2.reshape(-1)
    idx = perms.reshape(-1).astype(jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(r, d // block_d),
        in_specs=[
            pl.BlockSpec((m, block_d), lambda ri, i, *_: (0, i)),  # W tiles
        ],
        out_specs=pl.BlockSpec((pl.Squeezed(), m, block_d),
                               lambda ri, i, *_: (ri, 0, i)),
    )
    out = pl.pallas_call(
        _prefix_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r, m, d), stacked.dtype),
        interpret=interpret,
    )(idx, scale, ncum, stacked)
    return out.reshape(r * m, d)
