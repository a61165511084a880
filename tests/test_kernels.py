"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode;
the cohort gather vs numpy indexing."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.ce_loss.kernel import ce_loss_kernel
from repro.kernels.ce_loss.ops import ce_loss
from repro.kernels.ce_loss.ref import ce_loss_ref
from repro.kernels.cohort_gather.ops import cohort_gather, cohort_take
from repro.kernels.delta_codec.kernel import LANES, delta_codec_kernel
from repro.kernels.delta_codec.ops import delta_codec_roundtrip
from repro.kernels.delta_codec.ref import delta_codec_ref
from repro.kernels.flash_attention.ops import flash_attention_tpu
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.prefix_avg.kernel import prefix_avg_kernel
from repro.kernels.prefix_avg.ops import prefix_avg
from repro.kernels.prefix_avg.ref import prefix_avg_ref
from repro.kernels.weighted_avg.kernel import weighted_avg_kernel
from repro.kernels.weighted_avg.ops import weighted_avg
from repro.kernels.weighted_avg.ref import weighted_avg_ref
from repro.models.lm.attention import dense_attention


def _perms(key, r, m):
    return jnp.stack([jax.random.permutation(jax.random.fold_in(key, i), m)
                      for i in range(r)])


# ------------------------------------------------------- weighted_avg ------
@pytest.mark.parametrize("m,d,r", [(2, 2048, 4), (5, 4096, 3), (8, 6144, 16),
                                   (20, 2048, 50)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_weighted_avg_kernel_matches_ref(m, d, r, dtype, key):
    stacked = jax.random.normal(key, (m, d), dtype)
    w = jax.random.dirichlet(key, jnp.ones(m), (r,)).astype(dtype)
    got = weighted_avg_kernel(stacked, w, block_d=2048, interpret=True)
    want = weighted_avg_ref(stacked, w)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=2e-2 if dtype == jnp.bfloat16 else 1e-5)


def test_weighted_avg_tree_wrapper_pads_ragged_leaves(key):
    tree = {"a": jax.random.normal(key, (4, 100, 33)),
            "b": jax.random.normal(key, (4, 5000))}
    w = jax.random.dirichlet(key, jnp.ones(4), (6,))
    got = weighted_avg(tree, w, use_kernel=True, interpret=True)
    for name, leaf in tree.items():
        want = jnp.einsum("rm,m...->r...", w, leaf)
        np.testing.assert_allclose(np.asarray(got[name]), np.asarray(want),
                                   atol=1e-4)


def test_weighted_avg_subset_masks_recover_members(key):
    """One-hot weight rows must return the individual client models."""
    stacked = jax.random.normal(key, (4, 4096))
    w = jnp.eye(4)
    got = weighted_avg_kernel(stacked, w, block_d=2048, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(stacked), atol=1e-6)


# -------------------------------------------------------- prefix_avg ------
@pytest.mark.parametrize("m,d,r", [(3, 2048, 4), (5, 4096, 7),
                                   (8, 2048, 16), (20, 2048, 11),
                                   (3, 4096, 150)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_prefix_avg_kernel_matches_ref(m, d, r, dtype, key):
    stacked = jax.random.normal(key, (m, d), dtype)
    n_k = jnp.arange(1.0, m + 1.0) * 10
    perms = _perms(key, r, m)
    got = prefix_avg_kernel(stacked, perms, n_k, block_d=2048,
                            interpret=True)
    want = prefix_avg_ref(stacked, perms, n_k)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=2e-2 if dtype == jnp.bfloat16 else 1e-5)


def test_prefix_avg_matches_dense_prefix_weights(key):
    """The running-sum walk equals the dense prefix-weight contraction —
    the §8 oracle the streaming estimator replaces."""
    from repro.core.shapley_batched import prefix_weight_matrix

    m, d, r = 6, 512, 5
    stacked = jax.random.normal(key, (m, d))
    n_k = jnp.arange(1.0, m + 1.0) * 7
    perms = _perms(key, r, m)
    got = prefix_avg_ref(stacked, perms, n_k)
    w = prefix_weight_matrix(perms, n_k).reshape(r * m, m)
    want = weighted_avg_ref(stacked, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_prefix_avg_tree_wrapper_pads_ragged_leaves(key):
    """Non-divisible D: big leaves are padded to the kernel tile and
    sliced back; small leaves route to the jnp reference."""
    from repro.core.shapley_batched import prefix_weight_matrix

    m, r = 4, 6
    tree = {"a": jax.random.normal(key, (m, 100, 33)),
            "b": jax.random.normal(key, (m, 5000))}
    n_k = jnp.array([5.0, 10.0, 15.0, 20.0])
    perms = _perms(key, r, m)
    got = prefix_avg(tree, perms, n_k, use_kernel=True, interpret=True)
    w = prefix_weight_matrix(perms, n_k).reshape(r * m, m)
    for name, leaf in tree.items():
        want = jnp.einsum("rm,m...->r...", w, leaf)
        assert got[name].shape == (r * m,) + leaf.shape[1:]
        np.testing.assert_allclose(np.asarray(got[name]), np.asarray(want),
                                   atol=1e-4)


def test_prefix_avg_identity_walk_recovers_running_average(key):
    """First position of every walk must be exactly that client's model."""
    m, d = 4, 2048
    stacked = jax.random.normal(key, (m, d))
    n_k = jnp.ones((m,))
    perms = jnp.stack([jnp.roll(jnp.arange(m), -i) for i in range(m)])
    got = prefix_avg_kernel(stacked, perms, n_k, block_d=2048,
                            interpret=True).reshape(m, m, d)
    for i in range(m):
        np.testing.assert_allclose(np.asarray(got[i, 0]),
                                   np.asarray(stacked[i]), atol=1e-6)


# ------------------------------------------------------------ ce_loss ------
@pytest.mark.parametrize("r,v", [(4, 2048), (16, 4096), (8, 10240)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ce_loss_kernel_matches_ref(r, v, dtype, key):
    logits = jax.random.normal(key, (r, v), dtype) * 4
    labels = jax.random.randint(key, (r,), 0, v)
    got = ce_loss_kernel(logits, labels, block_v=2048, interpret=True)
    want = ce_loss_ref(logits, labels)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-2 if dtype == jnp.bfloat16 else 1e-5)


def test_ce_loss_wrapper_handles_unaligned_vocab(key):
    logits = jax.random.normal(key, (6, 5001))
    labels = jax.random.randint(key, (6,), 0, 5001)
    got = ce_loss(logits, labels, use_kernel=True, interpret=True)
    want = jnp.mean(ce_loss_ref(logits, labels))
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)


# ------------------------------------------------------ cohort_gather ------
# A gather copies bits, so every comparison below is exact equality
# against numpy indexing of the stack as stored (N, cap, F) — including
# bf16, int tables and repeated/boundary ids.
def _assert_rows(got, table, ids):
    assert got.dtype == table.dtype
    assert got.shape == ids.shape + table.shape[1:]
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(table)[np.asarray(ids)])


@pytest.mark.parametrize("n,m,cap,f", [(7, 3, 16, 128), (16, 5, 32, 128),
                                       (100, 20, 8, 256), (33, 8, 48, 128),
                                       (300, 3, 53, 784)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_cohort_take_matches_numpy(n, m, cap, f, dtype, key):
    """Up to the paper's MNIST client stacks: N = 300, 53 rows of 784."""
    table = jax.random.normal(key, (n, cap, f), dtype)
    ids = jax.random.randint(key, (m,), 0, n)
    _assert_rows(jax.jit(cohort_take)(table, ids), table, ids)


def test_cohort_take_repeated_and_boundary_ids(key):
    n = 9
    table = jax.random.normal(key, (n, 12, 40))
    ids = jnp.array([0, n - 1, 3, 3, 0], jnp.int32)
    _assert_rows(cohort_take(table, ids), table, ids)


def test_cohort_take_unaligned_trailing_dims(key):
    """Trailing dims off the (8, 128) tile are gathered as stored."""
    table = jax.random.normal(key, (11, 37, 95))
    ids = jnp.array([10, 0, 4], jnp.int32)
    _assert_rows(cohort_take(table, ids), table, ids)


def test_cohort_take_integer_table(key):
    table = jax.random.randint(key, (13, 5, 784), -1000, 1000, jnp.int32)
    ids = jnp.array([12, 12, 1, 0], jnp.int32)
    _assert_rows(cohort_take(table, ids), table, ids)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32])
def test_cohort_take_vmap_per_replica_ids(dtype, key):
    """The grid's replica vmap: (R, N, cap, F) stacks, (R, M) ids."""
    r, n, m = 8, 30, 3
    table = (jax.random.normal(key, (r, n, 7, 130)) * 100).astype(dtype)
    ids = jax.random.randint(key, (r, m), 0, n)
    got = jax.jit(jax.vmap(cohort_take))(table, ids)
    assert got.shape == (r, m, 7, 130)
    for i in range(r):
        _assert_rows(got[i], table[i], ids[i])


def test_cohort_gather_tree_wrapper_ragged_leaves(key):
    """Pytree wrapper: ragged leaves (incl. a 1-D per-client vector) all
    gathered along axis 0, each bit-identical to jnp.take."""
    tree = {"a": jax.random.normal(key, (10, 100, 33)),
            "b": jax.random.normal(key, (10, 5000)),
            "nv": jax.random.randint(key, (10,), 0, 64, jnp.int32)}
    ids = jnp.array([9, 2, 2, 0, 7], jnp.int32)
    got = cohort_gather(tree, ids)
    for name, leaf in tree.items():
        assert got[name].shape == (5,) + leaf.shape[1:]
        np.testing.assert_array_equal(np.asarray(got[name]),
                                      np.asarray(leaf)[np.asarray(ids)])


# -------------------------------------------------------- delta_codec ------
# The fused upload-codec roundtrip (DESIGN.md §18).  Parity is BITWISE
# against the jnp rowwise oracle — quantisation grids and the exact
# (sort-free) top-k must agree bit for bit, so compression error in an
# engine run is attributable to the codec's math, never to the kernel.
# Comparisons jit the ref: XLA lowers `x / scale` to reciprocal-multiply
# under jit but true division eagerly, so eager-vs-jit differs by design.
_jit_ref = jax.jit(functools.partial(delta_codec_ref),
                   static_argnames=("codec", "k"))


def _pad_lanes(x):
    d = x.shape[-1]
    pad = (-d) % LANES
    return jnp.pad(x, ((0, 0), (0, pad))) if pad else x


@pytest.mark.parametrize("m,d", [(3, 128), (4, 640), (2, 1000), (5, 4096),
                                 (1, 130)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("codec", ["quant8", "topk", "quant8_topk"])
def test_delta_codec_kernel_matches_ref(m, d, codec, dtype, key):
    """4+ shapes (incl. non-LANES-divisible D: 1000, 130) x 2 dtypes:
    the single-pass kernel equals the jitted rowwise oracle bitwise."""
    x = (jax.random.normal(key, (m, d)) * 3).astype(dtype)
    k = max(1, d // 10)
    got = delta_codec_kernel(_pad_lanes(x), codec=codec, k=k, d_true=d,
                             interpret=True)[:, :d]
    want = _jit_ref(x, codec=codec, k=k)
    assert got.dtype == x.dtype
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32),
                                  err_msg=f"{codec} {m}x{d} {dtype}")
    # padding lanes must not leak into the kept set or the quant scale
    np.testing.assert_array_equal(
        np.asarray(delta_codec_kernel(_pad_lanes(x), codec=codec, k=k,
                                      d_true=d, interpret=True)[:, d:]),
        0.0)


def test_delta_codec_topk_tie_semantics(key):
    """Injected magnitude ties resolve lowest-index-first — the lax.top_k
    contract the per-leaf oracle inherits; exact count always == k."""
    d = 256
    x = jnp.zeros((2, d)).at[:, [3, 7, 100, 200]].set(
        jnp.asarray([[2.0, -2.0, 2.0, 1.0], [-5.0, 5.0, 5.0, 5.0]]))
    for k in (1, 2, 3):
        got = delta_codec_kernel(x, codec="topk", k=k, d_true=d,
                                 interpret=True)
        want = _jit_ref(x, codec="topk", k=k)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        assert int(jnp.count_nonzero(got[1])) == k


def test_delta_codec_tie_break_follows_flat_column_order(key):
    """The kernel sees a row as an (8, d_pad/8) tile; ties must still
    resolve by flat column, lowest first.  d = 5000 is not a multiple of
    the 1024-wide row alignment (5120 padded, 640 columns per sublane);
    the tied columns sit in sublanes 0, 1, 2 and 4 in an order where the
    within-sublane column alone would rank them differently."""
    d = 5000
    cols = [5, 600, 1100, 3000]       # (s, c): (0,5) (0,600) (1,460) (4,440)
    x = jnp.zeros((2, d)).at[:, cols].set(3.0)
    x = x.at[1, 4999].set(-7.0)       # the last real column, in sublane 7
    for k in (1, 2, 3, 4):
        got = delta_codec_kernel(x, codec="topk", k=k, interpret=True)
        want = _jit_ref(x, codec="topk", k=k)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                      err_msg=f"k={k}")


def test_delta_codec_zero_rows(key):
    """All-zero rows: quant8 must not divide by zero; top-k keeps k
    (zero-valued) slots, matching lax.top_k on a constant vector."""
    x = jnp.zeros((3, 512))
    for codec in ("quant8", "topk", "quant8_topk"):
        got = delta_codec_kernel(x, codec=codec, k=8, d_true=512,
                                 interpret=True)
        assert np.isfinite(np.asarray(got)).all()
        np.testing.assert_array_equal(np.asarray(got), 0.0)


def test_delta_codec_ops_matches_legacy_tree_map(key):
    """The pytree wrapper (what round_engine now calls) reproduces the
    legacy per-leaf chain `vmap(codec_roundtrip)` it replaced, at ragged
    MLP-like shapes — both jitted, same lowering regime."""
    from repro.federated.compression import codec_roundtrip

    params = {"w1": jax.random.normal(key, (784, 32)) * 0.1,
              "b1": jnp.zeros((32,)),
              "w2": jax.random.normal(key, (32, 10)) * 0.3}
    stacked = jax.tree.map(
        lambda p: p[None] + 0.01 * jax.random.normal(
            jax.random.fold_in(key, p.ndim), (4,) + p.shape), params)
    for codec in ("quant8", "topk", "quant8_topk"):
        got = delta_codec_roundtrip(stacked, params, codec)
        legacy = jax.jit(lambda s, p, c=codec: jax.vmap(
            lambda w: codec_roundtrip(c, w, p))(s))(stacked, params)
        for name in params:
            np.testing.assert_allclose(
                np.asarray(got[name]), np.asarray(legacy[name]),
                atol=1e-6, err_msg=f"{codec} {name}")


def test_delta_codec_ops_kernel_path_matches_ref_path(key):
    """use_kernel=True (interpret) and the fused-ref fallback agree
    through the jitted wrapper to jit-fusion tolerance (the ref branch
    FMA-fuses the trailing `ref + rt` add; the kernel boundary blocks
    that fusion — one-ulp shifts, the repo-wide parity contract), and
    the size gate keeps the small 32-wide leaf on the ref path in both:
    that leaf must stay bitwise."""
    params = {"big": jax.random.normal(key, (64, 48)),   # d=3072: kernel
              "small": jax.random.normal(key, (32,))}    # d=32: ref
    stacked = jax.tree.map(
        lambda p: p[None] + 0.05 * jax.random.normal(
            jax.random.fold_in(key, p.size), (3,) + p.shape), params)
    for codec in ("quant8", "topk", "quant8_topk"):
        a = delta_codec_roundtrip(stacked, params, codec,
                                  use_kernel=True, interpret=True)
        b = delta_codec_roundtrip(stacked, params, codec,
                                  use_kernel=False, interpret=True)
        np.testing.assert_array_equal(np.asarray(a["small"]),
                                      np.asarray(b["small"]),
                                      err_msg=f"{codec} small")
        np.testing.assert_allclose(np.asarray(a["big"]),
                                   np.asarray(b["big"]),
                                   atol=1e-6, err_msg=f"{codec} big")


def test_delta_codec_identity_passthrough(key):
    stacked = {"w": jax.random.normal(key, (2, 100, 33))}
    out = delta_codec_roundtrip(stacked, {"w": jnp.zeros((100, 33))},
                                "identity")
    np.testing.assert_array_equal(np.asarray(out["w"]),
                                  np.asarray(stacked["w"]))


# ---------------------------------------------------- flash_attention ------
@pytest.mark.parametrize("b,s,hq,kh,hd,win", [
    (2, 256, 4, 2, 64, 0),
    (1, 512, 8, 8, 32, 128),
    (2, 256, 6, 2, 64, 64),
    (1, 256, 2, 1, 128, 0),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_dense(b, s, hq, kh, hd, win, dtype, key):
    k1, k2, k3 = jax.random.split(key, 3)
    q = jax.random.normal(k1, (b, s, hq, hd), dtype)
    k = jax.random.normal(k2, (b, s, kh, hd), dtype)
    v = jax.random.normal(k3, (b, s, kh, hd), dtype)
    got = flash_attention_tpu(q, k, v, causal=True, window=win,
                              block_q=128, block_k=128, interpret=True)
    want = dense_attention(q, k, v, q_pos=jnp.arange(s), kv_pos=jnp.arange(s),
                           causal=True, window=win)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=3e-2 if dtype == jnp.bfloat16 else 2e-5)


def test_flash_kernel_vs_kernel_ref(key):
    """ops-level oracle (attention_ref) agrees with model-level dense."""
    q = jax.random.normal(key, (3, 128, 64))
    k = jax.random.normal(key, (3, 128, 64))
    v = jax.random.normal(key, (3, 128, 64))
    a = attention_ref(q, k, v, causal=True)
    b2 = dense_attention(q[:, :, None], k[:, :, None], v[:, :, None],
                         q_pos=jnp.arange(128), kv_pos=jnp.arange(128),
                         causal=True)[:, :, 0]
    np.testing.assert_allclose(np.asarray(a), np.asarray(b2), atol=1e-5)
