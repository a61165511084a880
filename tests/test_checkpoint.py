"""Checkpoint roundtrip: pytrees and FL server state — plus the §19
integrity contract (atomic writes, sha256 digests, corrupt-checkpoint
fallback and bounded segment retry)."""
import glob
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.ckpt import (
    CheckpointCorruptError, load_pytree, load_server_state, save_pytree,
    save_server_state,
)


def test_pytree_roundtrip(tmp_path, key):
    tree = {"layer0": {"w": jax.random.normal(key, (4, 5)),
                       "b": jnp.zeros(5)},
            "head": {"w": jnp.ones((5, 2), jnp.float32)}}
    path = str(tmp_path / "ckpt.npz")
    save_pytree(path, tree)
    out = load_pytree(path, tree)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_structure_mismatch_raises(tmp_path, key):
    tree = {"a": jnp.zeros(3)}
    path = str(tmp_path / "c.npz")
    save_pytree(path, tree)
    with pytest.raises(ValueError):
        load_pytree(path, {"b": jnp.zeros(3)})


def test_segment_carry_roundtrip(tmp_path, key):
    """A scan-segment carry — params + device selector state + typed rng
    key — survives save/load bit-exactly (the resume contract of
    DESIGN.md §12), including the typed-PRNG-key encode/decode."""
    from repro.checkpoint.ckpt import load_carry, save_carry
    from repro.core.selection_jax import (
        init_device_state, make_selector_spec,
    )
    from repro.engine.round_engine import SegmentCarry

    spec = make_selector_spec("greedyfed", n_clients=6, m=2)
    state = init_device_state(spec, seed=3)
    state = state._replace(
        valuation=state.valuation._replace(
            sv=jax.random.normal(key, (6,))))
    carry = SegmentCarry(
        params={"w": jax.random.normal(key, (4, 2)), "b": jnp.zeros(2)},
        sel_state=state,
        key=jax.random.split(jax.random.key(7), 3),
        eval_slot=jnp.asarray(2, jnp.int32))
    path = str(tmp_path / "carry.npz")
    save_carry(path, carry)
    out = load_carry(path, carry)
    assert jax.dtypes.issubdtype(out.key.dtype, jax.dtypes.prng_key)
    np.testing.assert_array_equal(jax.random.key_data(out.key),
                                  jax.random.key_data(carry.key))
    for a, b in zip(jax.tree.leaves(carry), jax.tree.leaves(out)):
        np.testing.assert_array_equal(
            np.asarray(jax.random.key_data(a) if hasattr(a, "dtype")
                       and jax.dtypes.issubdtype(a.dtype,
                                                 jax.dtypes.prng_key)
                       else a),
            np.asarray(jax.random.key_data(b) if hasattr(b, "dtype")
                       and jax.dtypes.issubdtype(b.dtype,
                                                 jax.dtypes.prng_key)
                       else b))


def test_server_state_roundtrip(tmp_path, key):
    params = {"w": jax.random.normal(key, (3, 3))}
    path = str(tmp_path / "server.npz")
    save_server_state(path, params=params, sv=np.arange(5.0),
                      counts=np.arange(5), round_idx=17, seed=3)
    st = load_server_state(path, params)
    assert st["round"] == 17 and st["seed"] == 3
    np.testing.assert_array_equal(st["sv"], np.arange(5.0))
    np.testing.assert_array_equal(np.asarray(st["params"]["w"]),
                                  np.asarray(params["w"]))


# ------------------------------------------------ §19 integrity contract --
def _tree(key):
    return {"w": jax.random.normal(key, (4, 5)), "b": jnp.zeros(5)}


def test_atomic_write_leaves_no_tmp_and_stamps_digests(tmp_path, key):
    tree = _tree(key)
    path = str(tmp_path / "c.npz")
    save_pytree(path, tree)
    assert not glob.glob(str(tmp_path / "*.tmp"))
    with open(str(tmp_path / "c.manifest.json")) as f:
        manifest = json.load(f)
    assert sorted(manifest["digests"]) == sorted(manifest["keys"])
    assert len(manifest["digests"]) == len(jax.tree.leaves(tree))


def test_truncated_npz_raises_corrupt_not_valueerror(tmp_path, key):
    """A kill mid-write (simulated by truncation) must surface as
    CheckpointCorruptError — the fallback signal — not a generic error."""
    tree = _tree(key)
    path = str(tmp_path / "c.npz")
    save_pytree(path, tree)
    with open(path, "r+b") as f:
        f.truncate(os.path.getsize(path) // 2)
    with pytest.raises(CheckpointCorruptError):
        load_pytree(path, tree)


def test_digest_tamper_detected(tmp_path, key):
    """Bit rot that still parses as a valid npz is caught by the per-leaf
    sha256: flip the recorded digest and the load must refuse."""
    tree = _tree(key)
    path = str(tmp_path / "c.npz")
    save_pytree(path, tree)
    mpath = str(tmp_path / "c.manifest.json")
    with open(mpath) as f:
        manifest = json.load(f)
    k = sorted(manifest["digests"])[0]
    manifest["digests"][k] = "0" * 64
    with open(mpath, "w") as f:
        json.dump(manifest, f)
    with pytest.raises(CheckpointCorruptError):
        load_pytree(path, tree)


def test_missing_checkpoint_is_not_corrupt(tmp_path, key):
    with pytest.raises(FileNotFoundError):
        load_pytree(str(tmp_path / "absent.npz"), _tree(key))


def test_digestless_manifest_tolerated(tmp_path, key):
    """Pre-§19 checkpoints carry no digests: they load (unverified)."""
    tree = _tree(key)
    path = str(tmp_path / "c.npz")
    save_pytree(path, tree)
    mpath = str(tmp_path / "c.manifest.json")
    with open(mpath) as f:
        manifest = json.load(f)
    del manifest["digests"]
    with open(mpath, "w") as f:
        json.dump(manifest, f)
    out = load_pytree(path, tree)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _tiny_grid_spec():
    from repro.federated.client import ClientConfig
    from repro.federated.server import FLConfig
    from repro.grid import GridSpec

    cfg = FLConfig(
        dataset="mnist", selector="greedyfed", engine="scan",
        shapley_max_iters=10, n_clients=8, m=3, rounds=6, n_train=600,
        n_val=100, n_test=100, eval_every=3,
        client=ClientConfig(epochs=2, batches_per_epoch=2, batch_size=16))
    return GridSpec.product(cfg, selectors=["greedyfed"], seeds=[0, 1])


def test_corrupt_segment_checkpoint_falls_back_bit_identical(tmp_path):
    """Kill-mid-write drill: corrupt the LAST segment checkpoint, resume.
    The loader must flag it (`checkpoint_corrupt`), fall back to the
    previous boundary, recompute forward, and end bit-identical to the
    uninterrupted run."""
    from repro.grid import run_grid
    from repro.telemetry import Telemetry, validate_events

    spec = _tiny_grid_spec()
    d = str(tmp_path / "ck")
    whole = run_grid(spec, rounds_per_segment=3, checkpoint_dir=d)
    ckpts = sorted(glob.glob(os.path.join(d, "*.npz")))
    assert ckpts
    with open(ckpts[-1], "r+b") as f:
        f.truncate(64)
    tel = Telemetry()
    resumed = run_grid(spec, rounds_per_segment=3, checkpoint_dir=d,
                       telemetry=tel)
    for a, b in zip(whole.results, resumed.results):
        np.testing.assert_array_equal(
            np.asarray(a.sv_final), np.asarray(b.sv_final))
        for la, lb in zip(jax.tree.leaves(a.params),
                          jax.tree.leaves(b.params)):
            np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
        assert a.final_acc == b.final_acc
    validate_events(tel.events)
    assert any(ev["event"] == "checkpoint_corrupt" for ev in tel.events)


def test_segment_retry_bounded(monkeypatch):
    """A transient executor failure inside a segment dispatch is retried
    (with a `segment_retry` event) up to `retries`; past the budget the
    error propagates."""
    import repro.grid.segments as segments
    from repro.grid import run_grid
    from repro.telemetry import Telemetry

    spec = _tiny_grid_spec()
    real = segments.jitted_segment_step

    def flaky_factory(fails: int):
        state = {"left": fails}

        def factory(model, ccfg, seg_spec, vmapped=False):
            step = real(model, ccfg, seg_spec, vmapped=vmapped)

            def wrapped(*args):
                if state["left"] > 0:
                    state["left"] -= 1
                    raise RuntimeError("transient executor failure")
                return step(*args)

            return wrapped

        return factory

    clean = run_grid(spec)
    monkeypatch.setattr(segments, "jitted_segment_step", flaky_factory(1))
    tel = Telemetry()
    retried = run_grid(spec, retries=1, telemetry=tel)
    for a, b in zip(clean.results, retried.results):
        np.testing.assert_array_equal(
            np.asarray(a.sv_final), np.asarray(b.sv_final))
    assert sum(ev["event"] == "segment_retry" for ev in tel.events) == 1

    monkeypatch.setattr(segments, "jitted_segment_step", flaky_factory(2))
    with pytest.raises(RuntimeError, match="transient"):
        run_grid(spec, retries=1, isolate_cells=False)


def test_segment_retry_after_donated_carry(monkeypatch):
    """A dispatch that fails after consuming its (donated) carry is
    retried from a copy taken before the launch: the retry never
    re-dispatches deleted buffers, and the run matches a clean one."""
    import repro.grid.segments as segments
    from repro.grid import run_grid

    spec = _tiny_grid_spec()
    real = segments.jitted_segment_step
    state = {"left": 1}

    def factory(model, ccfg, seg_spec, vmapped=False):
        step = real(model, ccfg, seg_spec, vmapped=vmapped)

        def wrapped(carry, *rest):
            if state["left"] > 0:
                state["left"] -= 1
                # what donation does to the carry on TPU/GPU
                for leaf in jax.tree.leaves(carry):
                    leaf.delete()
                raise RuntimeError("failed after launch")
            return step(carry, *rest)

        return wrapped

    clean = run_grid(spec)
    monkeypatch.setattr(segments, "jitted_segment_step", factory)
    retried = run_grid(spec, retries=1, isolate_cells=False)
    assert state["left"] == 0
    for a, b in zip(clean.results, retried.results):
        assert [list(r) for r in a.selections] == \
            [list(r) for r in b.selections]
        for la, lb in zip(jax.tree.leaves(a.params),
                          jax.tree.leaves(b.params)):
            np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
