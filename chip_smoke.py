#!/usr/bin/env python3
"""Smoke test: the GreedyFed scan path end to end on a TPU.

Runs the paper's full MNIST-MLP protocol (784-200-100-10 MLP; N = 300
clients, M = 3 per round, 12,000 / 5,000 / 5,000 train / val / test
examples, E = 5 local epochs of B = 5 batches of 32, GTG-Shapley with the
default 50 * M permutation walks) for a few rounds, with synthetic data
and random weights made from seed 0, through the entry points a user
calls.  One process does everything.

    python3 chip_smoke.py             # one chip
    python3 chip_smoke.py --chips 4   # client-sharded run on four chips

One chip, three phases:
  1. each of the four Pallas kernels against its ref.py on the chip, at
     the shapes the run uses, within the bounds in BOUNDS, and the cohort
     gather exactly against numpy indexing of the stacks as stored;
  2. the whole-run scan, `run_federated(FLConfig(engine="scan",
     selector="greedyfed", ...))`, with compile and execute timed apart
     (execute ends in block_until_ready) and its HLO checked for
     `tpu_custom_call`, i.e. natively compiled kernels;
  3. one `run_grid` call with two partitions: greedyfed with the
     quant8_topk upload codec (so delta_codec runs) and random with the
     identity codec.
With --chips 4, only: the same greedyfed scan with clients_shards = 4 on
a (1, 4) run mesh, and the dense one-chip run it is compared with.

Every check prints its value on its own line.  The last line of stdout
is {"ok": true, "device": {...}} only when every check passed on a TPU;
any failed check, or a platform other than TPU, exits non-zero without
that line.  Segment retries and grid cell isolation stay off, so no
failure is caught and turned into a result.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROUNDS = 4
EVAL_EVERY = 2
# max |kernel - ref| on the chip, relative to max |ref| of the compared
# leaf, per kernel.  prefix_avg and ce_loss do the ref's f32 arithmetic in
# another order; weighted_avg's ref einsum runs the MXU at the default
# (bf16-pass) f32 precision, 2^-8 relative per product; delta_codec's
# quantising codecs may round x / scale the other way at an exact .5,
# one step of max|x| / 127.
BOUNDS = {
    "prefix_avg": 1e-5,
    "weighted_avg": 1e-2,
    "ce_loss": 1e-5,
    "delta_codec:topk": 1e-6,
    "delta_codec:quant8": 1.0 / 127 + 1e-6,
    "delta_codec:quant8_topk": 1.0 / 127 + 1e-6,
}
# final params of the client-sharded run vs the dense run, max abs
# difference: the cohort gathers copy bits, but the two programs are
# fused differently by XLA, so float sums may associate differently
SHARDED_PARAMS_ATOL = 1e-5


class Checks:
    """Every check of the run: printed as it is made, summed at the end."""

    def __init__(self):
        self.failed: list[str] = []

    def bound(self, name: str, value: float, limit: float) -> None:
        ok = value <= limit
        print(f"check {name}: {value!r} <= {limit!r} "
              f"{'ok' if ok else 'FAILED'}", flush=True)
        if not ok:
            self.failed.append(name)

    def true(self, name: str, ok: bool, detail="") -> None:
        print(f"check {name}: {detail} {'ok' if ok else 'FAILED'}",
              flush=True)
        if not ok:
            self.failed.append(name)


def full_config(**kw):
    from repro.federated.client import ClientConfig
    from repro.federated.server import FLConfig

    return FLConfig(
        engine="scan", selector="greedyfed", n_clients=300, m=3,
        rounds=ROUNDS, eval_every=EVAL_EVERY, n_train=12000, n_val=5000,
        n_test=5000, seed=0,
        client=ClientConfig(epochs=5, batches_per_epoch=5, batch_size=32),
        **kw)


def _rel_diff(got, want) -> float:
    import numpy as np

    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)),
                                                  1e-30))


def check_kernels(checks: Checks, cfg, s) -> None:
    """Each kernel against its ref.py, natively on the chip, at the shapes
    of the run of `cfg` (`s` is its RunSetup)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.shapley_batched import _draw_perms, prefix_weight_matrix
    from repro.kernels import default_interpret
    from repro.kernels.ce_loss.kernel import ce_loss_kernel
    from repro.kernels.ce_loss.ref import ce_loss_ref
    from repro.kernels.cohort_gather import cohort_take
    from repro.kernels.delta_codec import delta_codec_roundtrip
    from repro.kernels.prefix_avg.ops import prefix_avg
    from repro.kernels.weighted_avg.ops import weighted_avg

    m, n_walks = cfg.m, 50 * cfg.m     # GTG's default walks per round
    key = jax.random.key(0)
    params = s.params
    stacked = jax.tree.map(
        lambda p: p[None] + 0.01 * jax.random.normal(
            jax.random.fold_in(key, p.size), (m,) + p.shape), params)
    n_k = jnp.arange(17.0, 17.0 + 13 * m, 13.0)
    perms = _draw_perms(key, m, n_walks)

    def per_leaf(name, got, want):
        worst = max(_rel_diff(g, w) for g, w in
                    zip(jax.tree.leaves(got), jax.tree.leaves(want)))
        checks.bound(f"{name} max rel diff", worst, BOUNDS[name])

    per_leaf("prefix_avg", prefix_avg(stacked, perms, n_k, use_kernel=True),
             prefix_avg(stacked, perms, n_k, use_kernel=False))
    w = prefix_weight_matrix(perms, n_k).reshape(n_walks * m, m)
    per_leaf("weighted_avg", weighted_avg(stacked, w, use_kernel=True),
             weighted_avg(stacked, w, use_kernel=False))

    logits = 3.0 * jax.random.normal(key, (s.y_val.shape[0], 10))
    checks.bound("ce_loss max rel diff", _rel_diff(
        ce_loss_kernel(logits, s.y_val, block_v=10,
                       interpret=default_interpret()),
        ce_loss_ref(logits, s.y_val)), BOUNDS["ce_loss"])

    n = cfg.n_clients
    ids = jnp.asarray([0, n - 1, n // 2], jnp.int32)
    # the run's client data, and a table as wide as the model
    table = jax.random.normal(key, (n, 178_176))
    for name, arr in (("xs", s.xs), ("table", table)):
        got = np.asarray(cohort_take(arr, ids))
        want = np.asarray(arr)[np.asarray(ids)]
        checks.true(f"cohort_gather {name} {arr.shape} exact",
                    np.array_equal(got, want),
                    f"{int(np.sum(got != want))} elements differ")

    for codec in ("topk", "quant8", "quant8_topk"):
        got = delta_codec_roundtrip(stacked, params, codec, use_kernel=True)
        want = delta_codec_roundtrip(stacked, params, codec,
                                     use_kernel=False)
        # relative to the delta's magnitude: the roundtrip codes w - w_ref
        worst = max(
            float(np.max(np.abs(np.asarray(g) - np.asarray(wt))))
            / max(float(np.max(np.abs(np.asarray(st) - np.asarray(p)))),
                  1e-30)
            for g, wt, st, p in zip(jax.tree.leaves(got),
                                    jax.tree.leaves(want),
                                    jax.tree.leaves(stacked),
                                    jax.tree.leaves(params)))
        checks.bound(f"delta_codec:{codec} max rel diff", worst,
                     BOUNDS[f"delta_codec:{codec}"])


def check_result(checks: Checks, label: str, res, cfg, model_bytes: int,
                 upload_per_client: int) -> None:
    """Sanity of one FLResult: valid distinct cohorts, finite SV and
    params, and the byte ledger."""
    import jax
    import numpy as np

    ok_sel = len(res.selections) == cfg.rounds and all(
        len(set(int(i) for i in row)) == cfg.m
        and all(0 <= int(i) < cfg.n_clients for i in row)
        for row in res.selections)
    checks.true(f"{label} selections", ok_sel,
                [list(map(int, r)) for r in res.selections])
    checks.true(f"{label} sv finite",
                bool(np.isfinite(np.asarray(res.sv_final)).all()),
                f"sum |sv| {float(np.abs(res.sv_final).sum())!r}")
    checks.true(f"{label} params finite", all(
        bool(np.isfinite(np.asarray(x)).all())
        for x in jax.tree.leaves(res.params)))
    want = upload_per_client * cfg.m * cfg.rounds
    checks.true(f"{label} upload bytes", res.upload_bytes == want,
                f"{res.upload_bytes} == {want}")
    want = model_bytes * cfg.m * cfg.rounds
    checks.true(f"{label} download bytes", res.download_bytes == want,
                f"{res.download_bytes} == {want}")
    checks.true(f"{label} final test accuracy",
                0.0 <= res.final_acc <= 1.0, repr(res.final_acc))


def one_chip(checks: Checks) -> None:
    import jax
    import numpy as np

    from repro.engine.round_engine import jitted_run_scan
    from repro.engine.scan_engine import make_scan_spec, scan_operands
    from repro.federated.compression import codec_nbytes
    from repro.federated.server import run_federated, setup_run
    from repro.grid import GridSpec, run_grid
    from repro.grid.spec import GridCell

    cfg = full_config()
    s = setup_run(cfg)
    print(f"client stacks: xs {s.xs.shape} ys {s.ys.shape}; "
          f"model {s.model_bytes} bytes", flush=True)

    print("== phase 1: kernels vs ref.py on the chip", flush=True)
    check_kernels(checks, cfg, s)

    print("== phase 2: whole-run scan (greedyfed, identity codec)",
          flush=True)
    run = jitted_run_scan(s.model, cfg.client, make_scan_spec(
        cfg, (s.sel_spec,)))
    operands = scan_operands(cfg, s)
    t0 = time.perf_counter()
    compiled = run.lower(s.params, *operands).compile()
    compile_s = time.perf_counter() - t0
    n_kernels = compiled.as_text().count("tpu_custom_call")
    checks.true("scan HLO holds tpu_custom_call", n_kernels > 0,
                f"{n_kernels} custom calls")
    t0 = time.perf_counter()
    out = jax.block_until_ready(compiled(s.params, *operands))  # donates
    execute_s = time.perf_counter() - t0
    print(f"scan: compile_s {compile_s!r} execute_s {execute_s!r} "
          f"rounds {cfg.rounds}", flush=True)
    checks.true("scan per-round sv finite",
                bool(np.isfinite(np.asarray(out.sv)).all()),
                np.asarray(out.sv).tolist())

    res = run_federated(cfg)
    print(f"run_federated: compile_time_s {res.compile_time_s!r} "
          f"wall_time_s {res.wall_time_s!r} final_acc {res.final_acc!r} "
          f"test_acc {res.test_acc}", flush=True)
    checks.true("run_federated selections == timed scan selections",
                np.array_equal(np.stack(res.selections),
                               np.asarray(out.selections)))
    check_result(checks, "solo", res, cfg, s.model_bytes, s.model_bytes)

    print("== phase 3: run_grid, two partitions", flush=True)
    spec = GridSpec(cfg, (GridCell("greedyfed", 0,
                                   {"upload_codec": "quant8_topk"}),
                          GridCell("random", 0)))
    t0 = time.perf_counter()
    grid = run_grid(spec, isolate_cells=False, retries=0)
    print(f"run_grid: wall_s {time.perf_counter() - t0!r} partitions "
          f"{[p.label for p in grid.partitions]}", flush=True)
    checks.true("grid partitions", len(grid.partitions) == 2,
                len(grid.partitions))
    for cell, r in zip(spec.cells, grid.results):
        c = cell.config(cfg)
        label = f"grid {cell.selector}/{c.upload_codec}"
        print(f"{label}: compile_time_s {r.compile_time_s!r} "
              f"final_acc {r.final_acc!r}", flush=True)
        check_result(checks, label, r, c, s.model_bytes,
                     codec_nbytes(c.upload_codec, r.params))


def four_chips(checks: Checks) -> None:
    import jax
    import numpy as np

    from repro.federated.server import run_federated, setup_run
    from repro.launch.mesh import make_run_mesh

    cfg = full_config()
    sharded_cfg = dataclasses.replace(cfg, clients_shards=4)
    mesh = make_run_mesh(1, 4)
    ids = sorted(d.id for d in mesh.devices.flat)
    checks.true("run mesh spans four devices",
                len(ids) == 4 and len(set(ids)) == 4
                and mesh.shape["clients"] == 4,
                f"shape {dict(mesh.shape)} device ids {ids}")

    s = setup_run(sharded_cfg, client_mesh=mesh)
    for name in ("xs", "ys", "n_valid"):
        arr = getattr(s, name)
        per_dev = {sh.device.id: sh.data.nbytes
                   for sh in arr.addressable_shards}
        print(f"client state {name} {arr.shape}: total {arr.nbytes} "
              f"bytes, per device {per_dev}", flush=True)
    checks.true("client stacks sharded over four devices",
                len({sh.device.id for sh in s.xs.addressable_shards}) == 4)
    del s

    t0 = time.perf_counter()
    sharded = run_federated(sharded_cfg)
    print(f"sharded run: wall_s {time.perf_counter() - t0!r} "
          f"compile_time_s {sharded.compile_time_s!r} "
          f"final_acc {sharded.final_acc!r}", flush=True)
    t0 = time.perf_counter()
    dense = run_federated(cfg)
    print(f"dense run: wall_s {time.perf_counter() - t0!r} "
          f"compile_time_s {dense.compile_time_s!r} "
          f"final_acc {dense.final_acc!r}", flush=True)
    checks.true("sharded selections == dense selections",
                np.array_equal(np.stack(sharded.selections),
                               np.stack(dense.selections)),
                [list(map(int, r)) for r in sharded.selections])
    diff = max(float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
               for a, b in zip(jax.tree.leaves(sharded.params),
                               jax.tree.leaves(dense.params)))
    checks.bound("sharded vs dense params max abs diff", diff,
                 SHARDED_PARAMS_ATOL)
    checks.true("sharded params finite", all(
        bool(np.isfinite(np.asarray(x)).all())
        for x in jax.tree.leaves(sharded.params)))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    src = os.path.join(HERE, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: no repro package under {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, src)

    from repro.launch.compile_cache import use_compile_cache
    cache_dir = use_compile_cache()

    import jax

    devices = jax.devices()
    # after backend start-up, which itself adds to LIBTPU_INIT_ARGS
    env_before = {k: os.environ.get(k)
                  for k in ("XLA_FLAGS", "LIBTPU_INIT_ARGS")}
    dev = devices[0]
    print(f"device: platform {dev.platform} kind {dev.device_kind!r} "
          f"count {len(devices)}; jax {jax.__version__}; "
          f"compile cache {cache_dir}", flush=True)
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {dev.platform}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs that many TPU "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 1

    checks = Checks()
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(checks)
    else:
        one_chip(checks)
    print(f"total_s {time.perf_counter() - t0!r}", flush=True)

    # nothing on this path may rewrite the device flags (launch.dryrun
    # and launch.hillclimb overwrite XLA_FLAGS when imported)
    env_after = {k: os.environ.get(k) for k in env_before}
    checks.true("XLA_FLAGS / LIBTPU_INIT_ARGS untouched",
                env_before == env_after
                and "repro.launch.dryrun" not in sys.modules
                and "repro.launch.hillclimb" not in sys.modules,
                "" if env_before == env_after
                else f"{env_before} -> {env_after}")
    if checks.failed:
        print(f"chip_smoke: {len(checks.failed)} checks failed: "
              f"{checks.failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
