import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) combination.

MUST be run as its own process (``python -m repro.launch.dryrun``): the two
lines above execute before any other import so the forced 512 host devices
are locked in before jax initialises.  Never set that flag globally — smoke
tests and benches must keep seeing 1 device.

For each combination this produces:
  * compiled.memory_analysis()  -> per-device bytes (does the step fit HBM?)
  * compiled.cost_analysis()    -> HLO FLOPs / bytes (roofline §compute/§memory)
  * HLO-text collective parse   -> collective bytes   (roofline §collective)
plus an "assembled" per-layer x trip-count roofline (launch/roofline.py) since
XLA's HloCostAnalysis counts a scanned while-body once, not n_layers times.

Artifacts land in experiments/dryrun/<arch>__<shape>__<mesh>.json.
"""
import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, get_config
from repro.launch.compat import named_shardings
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import (
    assembled_roofline, collective_bytes_from_text, roofline_report,
)
from repro.launch.shapes import (
    SHAPES, batch_struct, decode_structs, pad_vocab, params_struct,
    shape_applicable,
)
from repro.launch.sharding import (
    batch_specs, cache_specs, launch_cfg, logits_spec, opt_specs, param_specs,
)
from repro.models.lm import model as M
from repro.optim import make_optimizer

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


def build_step(cfg, shape, mesh):
    """Return (fn, example_args, in_shardings, out_shardings)."""
    from jax.sharding import PartitionSpec as P

    pshape = params_struct(cfg)
    pspecs = param_specs(cfg, mesh, pshape)

    if shape.kind == "train":
        opt_init, step = M.make_train_step(cfg)
        oshape = jax.eval_shape(opt_init, pshape)
        ospecs = opt_specs(cfg, pspecs)
        bstruct = batch_struct(cfg, shape)
        bspecs = batch_specs(cfg, mesh, bstruct)

        def fn(params, opt_state, batch):
            return step(params, opt_state, batch)

        args = (pshape, oshape, bstruct)
        in_s = (pspecs, ospecs, bspecs)
        out_s = (pspecs, ospecs, P())
        return fn, args, in_s, out_s

    if shape.kind == "prefill":
        bstruct = batch_struct(cfg, shape)
        bspecs = batch_specs(cfg, mesh, bstruct)
        cshape = jax.eval_shape(
            lambda: M.init_cache(cfg, shape.global_batch, shape.seq_len))
        cspecs = cache_specs(cfg, mesh, cshape)

        def fn(params, batch):
            return M.prefill_step(cfg, params, batch,
                                  cache_len=shape.seq_len)

        args = (pshape, bstruct)
        in_s = (pspecs, bspecs)
        out_s = (cspecs, logits_spec(cfg, mesh, shape.global_batch))
        return fn, args, in_s, out_s

    # decode
    cshape, bstruct = decode_structs(cfg, shape)
    cspecs = cache_specs(cfg, mesh, cshape)
    bspecs = batch_specs(cfg, mesh, bstruct)

    def fn(params, cache, batch):
        return M.decode_step(cfg, params, cache, batch)

    args = (pshape, cshape, bstruct)
    in_s = (pspecs, cspecs, bspecs)
    out_s = (cspecs, logits_spec(cfg, mesh, shape.global_batch))
    return fn, args, in_s, out_s


def run_one(arch: str, shape_name: str, multi_pod: bool,
            assemble: bool = True, save: bool = True,
            cfg_override=None) -> dict:
    shape = SHAPES[shape_name]
    base = cfg_override if cfg_override is not None else get_config(arch)
    applicable, why = shape_applicable(base, shape)
    mesh_name = "multi" if multi_pod else "single"
    tag = f"{base.name}__{shape_name}__{mesh_name}"
    if not applicable:
        rec = {"tag": tag, "status": "skipped", "reason": why}
        if save:
            _save(tag, rec)
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    cfg = pad_vocab(base)
    cfg = launch_cfg(cfg, mesh, shape)

    t0 = time.time()
    fn, args, in_s, out_s = build_step(cfg, shape, mesh)
    with jax.set_mesh(mesh):
        lowered = jax.jit(fn, in_shardings=named_shardings(mesh, in_s),
                          out_shardings=named_shardings(mesh, out_s)
                          ).lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        coll = collective_bytes_from_text(compiled.as_text())

    rec = {
        "tag": tag,
        "status": "ok",
        "arch": base.name,
        "shape": shape_name,
        "mesh": list(mesh.devices.shape),
        "n_devices": int(mesh.devices.size),
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory": _mem_dict(mem),
        "hlo_cost": {"flops": cost.get("flops", -1.0),
                     "bytes_accessed": cost.get("bytes accessed", -1.0)},
        "collective_bytes_toplevel": coll,
    }
    if assemble:
        with jax.set_mesh(mesh):
            rec["assembled"] = assembled_roofline(cfg, shape, mesh)
        rec["roofline"] = roofline_report(cfg, shape, rec,
                                          n_devices=int(mesh.devices.size))
    if save:
        _save(tag, rec)
    return rec


def _mem_dict(mem) -> dict:
    out = {}
    for k in ("temp_size_in_bytes", "argument_size_in_bytes",
              "output_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes"):
        try:
            out[k] = int(getattr(mem, k))
        except Exception:
            pass
    return out


def _save(tag: str, rec: dict) -> None:
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, tag + ".json"), "w") as f:
        json.dump(rec, f, indent=1)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all",
                    help="arch id or 'all'")
    ap.add_argument("--shape", default="all",
                    help=f"one of {sorted(SHAPES)} or 'all'")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--no-assemble", action="store_true")
    args = ap.parse_args()

    archs = ARCH_IDS if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    failures = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                label = f"{arch} x {shape} x {'multi' if mp else 'single'}"
                try:
                    rec = run_one(arch, shape, mp, assemble=not args.no_assemble)
                    if rec["status"] == "ok":
                        mem_gb = rec["memory"].get("temp_size_in_bytes", 0) / 2**30
                        print(f"[ok]   {label}: compile={rec['compile_s']}s "
                              f"temp/device={mem_gb:.2f}GiB "
                              f"flops={rec['hlo_cost']['flops']:.3e}")
                    else:
                        print(f"[skip] {label}: {rec['reason']}")
                except Exception as e:  # noqa: BLE001
                    failures.append((label, repr(e)))
                    print(f"[FAIL] {label}: {e}")
                    traceback.print_exc()
    if failures:
        raise SystemExit(f"{len(failures)} dry-run failures")
    print("dry-run complete: all combinations lowered and compiled")


if __name__ == "__main__":
    main()
