"""Pallas TPU kernel: fused single-pass delta-codec roundtrip.

The scan engine's cohort stage compresses every client's update delta each
round (round_engine.py).  The old path was a per-leaf chain of XLA kernels
— abs-max pass, quant pass, dequant pass, full-row `lax.top_k` (a sort)
plus a dense zeros+scatter — each materialising an (M, D) intermediate in
HBM.  Here the whole roundtrip is ONE pass: each grid step DMAs one row
into VMEM, computes abs-max -> int8 quantise -> dequantise (and the exact
top-k keep mask for the sparse codecs) entirely on-chip, and writes the
reconstructed row back.  HBM traffic is the floor: read D, write D.

Row layout: a row of d_pad = 8 * C columns (d_pad a multiple of
ROW_ALIGN = 8 * 128) is viewed as an (8, C) tile — the row-major reshape
of the (rows, d_pad) matrix to (rows, 8, C), so flat column
s * C + c sits at (s, c).  A (1, d_pad) block fills 1 of the 8 sublanes of
every vreg and costs 8x its bytes in VMEM: on a v5e the topk codec's
temporaries then exceed the scoped VMEM at the MNIST MLP's 156,800-wide
leaf, and Mosaic refuses a (1, d_pad) block of a (rows, d_pad) array
outright (last two block dims must be (8k, 128k) or the array's own).
The (squeezed, 8, C) block of the (rows, 8, C) view fills every sublane;
at that layout all three codecs compile for a v5e up to 2^19 columns
(the VMEM limit and the ops wrapper's gate are in ops.py).  Every
reduction below is order-free (max, integer counts), so the view changes
no bit of the result.

Top-k without a sort: |x| >= 0, so the f32 bit pattern reinterpreted as
int32 is monotone in the float value (sign bit clear => signed compare ==
float compare) and bit-equality == float equality.  The k-th largest key
is found by MSB descent — build the largest threshold t, bit by bit from
bit 30 down, keeping a bit iff count(key >= t|bit) >= k; each step is one
compare+sum over the VMEM-resident row.  Ties at the threshold are broken
lowest-index-first (the `lax.top_k` contract) by a second MSB descent over
the tied column indices.  ~2*31 vector passes over VMEM, zero HBM traffic
beyond the single streaming read/write.

Padding: rows are zero-padded to a ROW_ALIGN multiple here; a static
`d_true` masks pad columns out of the abs-max and the top-k candidate
pool (a pad key of -1 sorts below every valid key, so padding never
steals a keep slot from a real element).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

SUBLANES = 8
LANES = 128
ROW_ALIGN = SUBLANES * LANES  # a row is one (8, d_pad / 8) tile


def _kth_largest(key: jax.Array, k: jax.Array | int, nbits: int) -> jax.Array:
    """k-th largest entry of int32 `key` (values in [-1, 2^nbits)): the
    largest t with count(key >= t) >= k, found by MSB descent.  Exact;
    requires at least k entries >= 0."""
    def body(i, t):
        cand = t | jnp.int32(1 << (nbits - 1 - i))
        cnt = jnp.sum((key >= cand).astype(jnp.int32))
        return jnp.where(cnt >= k, cand, t)

    return jax.lax.fori_loop(0, nbits, body, jnp.int32(0))


def _codec_kernel(x_ref, out_ref, *, codec: str, k: int, d_true: int):
    x = x_ref[...].astype(jnp.float32)                      # (8, C)
    width = x.shape[-1]
    d_pad = x.size
    # flat column of element (s, c) of the row-major (8, C) row view
    col = (jax.lax.broadcasted_iota(jnp.int32, x.shape, 0) * width
           + jax.lax.broadcasted_iota(jnp.int32, x.shape, 1))
    valid = col < d_true
    absx = jnp.where(valid, jnp.abs(x), 0.0)
    if codec in ("quant8", "quant8_topk"):
        scale = jnp.maximum(jnp.max(absx), 1e-12) / 127.0
        q = jnp.clip(jnp.round(x / scale), -127.0, 127.0) * scale
    if codec == "quant8":
        out = q
    else:
        key = jnp.where(valid,
                        jax.lax.bitcast_convert_type(absx, jnp.int32),
                        -1)
        # finite f32 bit patterns are < 2^31, so 31 bits cover every key
        thr = _kth_largest(key, k, 31)
        above = key > thr
        r = k - jnp.sum(above.astype(jnp.int32))            # ties to keep
        tie = key == thr
        # r-th smallest tied column == d_pad minus the r-th largest of
        # (d_pad - col) over the ties
        tkey = jnp.where(tie, d_pad - col, -1)
        u = d_pad - _kth_largest(tkey, r, max(1, d_pad.bit_length()))
        keep = above | (tie & (col <= u))
        out = jnp.where(keep, x if codec == "topk" else q, 0.0)
    out_ref[...] = out.astype(out_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("codec", "k", "d_true", "interpret"))
def delta_codec_kernel(x: jax.Array, *, codec: str, k: int = 0,
                       d_true: int | None = None,
                       interpret: bool = False) -> jax.Array:
    """Roundtrip each row of x (rows, d) through `codec`.

    Columns >= d_true (default d) are padding: passed through the
    quantiser but excluded from abs-max and top-k.  Rows are zero-padded
    to a ROW_ALIGN multiple for the (8, C) row view and sliced back, so
    the result has x's shape.  `k` is the static per-row keep count for
    the sparse codecs.
    """
    rows, d = x.shape
    if d_true is None:
        d_true = d
    assert 0 < d_true <= d, (d_true, d)
    pad = (-d) % ROW_ALIGN
    xp = jnp.pad(x, ((0, 0), (0, pad))) if pad else x
    width = (d + pad) // SUBLANES

    kernel = functools.partial(_codec_kernel, codec=codec, k=k, d_true=d_true)
    block = pl.BlockSpec((pl.Squeezed(), SUBLANES, width),
                         lambda i: (i, 0, 0))
    out = pl.pallas_call(
        kernel,
        grid=(rows,),
        in_specs=[block],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct((rows, SUBLANES, width), x.dtype),
        interpret=interpret,
    )(xp.reshape(rows, SUBLANES, width))
    return out.reshape(rows, d + pad)[:, :d]
