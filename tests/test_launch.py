"""Launch-layer unit tests that don't need multiple devices."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCH_IDS, TUNED_OVERRIDES, get_config
from repro.launch.roofline import collective_bytes_from_text, model_flops
from repro.launch.shapes import (
    SHAPES, batch_struct, decode_structs, pad_vocab, shape_applicable,
)


def test_shapes_registry_matches_assignment():
    assert SHAPES["train_4k"].seq_len == 4096
    assert SHAPES["train_4k"].global_batch == 256
    assert SHAPES["prefill_32k"].global_batch == 32
    assert SHAPES["decode_32k"].global_batch == 128
    assert SHAPES["long_500k"].seq_len == 524288
    assert SHAPES["long_500k"].kind == "decode"


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_long500k_applicability_rule(arch):
    cfg = get_config(arch)
    ok, why = shape_applicable(cfg, SHAPES["long_500k"])
    expect = arch in ("mamba2_370m", "hymba_1_5b", "h2o_danube_3_4b")
    assert ok == expect, (arch, why)


def test_pad_vocab_multiple_and_identity():
    cfg = get_config("mamba2_370m")
    padded = pad_vocab(cfg)
    assert padded.vocab % 16 == 0 and padded.vocab >= cfg.vocab
    cfg2 = get_config("kimi_k2_1t_a32b")
    assert pad_vocab(cfg2).vocab == cfg2.vocab  # already divisible


@pytest.mark.parametrize("arch", ["internvl2_76b", "whisper_medium",
                                  "tinyllama_1_1b"])
def test_batch_struct_has_frontend_inputs(arch):
    cfg = get_config(arch)
    bs = batch_struct(cfg, SHAPES["prefill_32k"])
    assert bs["tokens"].shape == (32, 32768)
    if cfg.frontend == "vision":
        assert bs["patches"].shape == (32, 256, cfg.d_model)
    if cfg.frontend == "audio":
        assert bs["frames"].shape == (32, 1500, cfg.d_model)


def test_decode_structs_ring_cache_is_window_bounded():
    cfg = get_config("h2o_danube_3_4b")           # SWA window 4096
    cache, batch = decode_structs(cfg, SHAPES["long_500k"])
    assert cache["k"].shape[2] == cfg.window, "ring cache must be O(window)"
    cfg2 = get_config("tinyllama_1_1b")           # full attention
    cache2, _ = decode_structs(cfg2, SHAPES["decode_32k"])
    assert cache2["k"].shape[2] == 32768


def test_collective_parser_counts_and_weights():
    hlo = """
  %ar = f32[16,128]{1,0} all-reduce(f32[16,128]{1,0} %x), replica_groups={}
  %ag.1 = bf16[4,256]{1,0} all-gather-start(bf16[2,256]{1,0} %y), dim=0
  %ag.2 = bf16[4,256]{1,0} all-gather-done(bf16[4,256]{1,0} %ag.1)
  %a2a = f32[8,8]{1,0} all-to-all(f32[8,8]{1,0} %z)
  %other = f32[4]{0} add(f32[4]{0} %a, f32[4]{0} %b)
"""
    out = collective_bytes_from_text(hlo)
    assert out["counts"]["all-reduce"] == 1
    assert out["counts"]["all-gather"] == 1        # -done not double-counted
    assert out["by_kind"]["all-reduce"] == 16 * 128 * 4
    assert out["by_kind"]["all-gather"] == 4 * 256 * 2
    # weighted total doubles the all-reduce
    assert out["weighted_total"] == (2 * 16 * 128 * 4 + 4 * 256 * 2
                                     + 8 * 8 * 4)


def test_model_flops_train_vs_decode_scaling():
    cfg = get_config("tinyllama_1_1b")
    t = model_flops(cfg, SHAPES["train_4k"])
    d = model_flops(cfg, SHAPES["decode_32k"])
    # train: 6*N*B*S; decode: 2*N*B
    assert t / d == pytest.approx(3 * 256 * 4096 / 128, rel=1e-6)


def test_tuned_configs_apply_perf_overrides():
    cfg = get_config("hymba_1_5b", tuned=True)
    assert cfg.parallelism == "dp" and cfg.attn_remat and cfg.ssm_chunk == 64
    base = get_config("hymba_1_5b")
    assert base.parallelism == "tp", "baseline must stay paper-literal"
    for arch in TUNED_OVERRIDES:
        get_config(arch, tuned=True)  # all resolvable


_CACHE_PROBE = """
import os, sys, jax, jax.numpy as jnp
import repro.launch.compile_cache as cc
cc.REPO_CACHE = sys.argv[1]          # stand-in for <checkout>/.jax_cache
print("DIR", cc.use_compile_cache())
jax.jit(lambda x: x * 3 + 1)(jnp.ones(7)).block_until_ready()
"""


@pytest.mark.parametrize("env_set", [True, False])
def test_compile_cache_lands_only_in_its_one_directory(tmp_path, env_set):
    """JAX_COMPILATION_CACHE_DIR, when set, is the only place entries
    land; unset, they land only in the fixed in-checkout directory."""
    import os
    import subprocess
    import sys

    from repro.launch import compile_cache

    env_dir, repo_dir = tmp_path / "env", tmp_path / "repo"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_set:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    p = subprocess.run([sys.executable, "-c", _CACHE_PROBE, str(repo_dir)],
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr
    used, unused = (env_dir, repo_dir) if env_set else (repo_dir, env_dir)
    assert f"DIR {used}" in p.stdout
    assert any(used.iterdir()) and not unused.exists()

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert compile_cache.REPO_CACHE == os.path.join(root, ".jax_cache")
    with open(os.path.join(root, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_peaks_keyed_by_device_kind():
    """v5e peaks are the published ones; an unknown kind is an error."""
    from repro.launch.roofline import peaks_for

    v5e = peaks_for("TPU v5 lite")
    assert (v5e.flops, v5e.hbm_bw) == (197e12, 819e9)
    with pytest.raises(KeyError, match="no published peaks"):
        peaks_for("TPU v9 imaginary")
