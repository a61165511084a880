"""Shared by the `test_bench_check_*` files: a cell at a size a CPU
holds, the control, and the faults that are planted under its timed
path.

Each cell keeps its own limits (`checks/<cell>.json`) and its own path,
at tiny sizes: a sound run has to come out correct; the control (the
reference in bfloat16, in the system's place) must not; nor may a run
whose timed path is broken underneath by each fault a one-chip cell can
have: a step that returns its state unchanged, half of each batch left
out, an answer altered where it is produced, and (GreedyFed) a greedy
phase that takes the clients of least value.
"""
import copy
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import compare, harness, reference
from bench.calibrate import as_prog

TINY = dict(n_clients=8, m=2, rounds=4, n_train=240, n_val=24, n_test=24,
            epochs=1, batches_per_epoch=2, batch_size=8, walks_per_client=3,
            eval_every=2)
# per cell, sizes at which its control reads past its limits on a CPU:
# the MLP's bfloat16 control drifts off only after some hundreds of SGD
# steps (20 rounds of the paper's 5x5 steps of 32), and GreedyFed's greedy
# phase starts after 20 round-robin rounds
SIZES = {"mnist-mlp.greedyfed": dict(
    n_clients=40, m=2, rounds=30, n_train=1600, n_val=500, n_test=500,
    epochs=5, batches_per_epoch=5, batch_size=32, walks_per_client=2,
    eval_every=10),
    "mnist-mlp.grid-fedavg-q8": dict(
    n_clients=40, m=2, rounds=20, n_train=1600, n_val=500, n_test=500,
    epochs=5, batches_per_epoch=5, batch_size=32, walks_per_client=2,
    eval_every=10)}
SEED = 3_000_000_019


def tiny(name):
    """The cell at a CPU's size: its traffic, limits and path, with two
    runs to a pass, valuing 2 rounds of each where it values any."""
    c = copy.deepcopy(harness.find_cell(name))
    c["config"]["fl"].update(SIZES.get(name, TINY))
    c["traffic"]["program_seeds"] = c["traffic"]["program_seeds"][:2]
    c["check"]["sv_rounds"] = min(c["check"]["sv_rounds"], 2)
    return c


def clear_programs():
    """Drop compiled programs, so that a planted fault is traced in."""
    from repro.engine import round_engine
    round_engine._jitted_run_scan_cached.cache_clear()
    round_engine._jitted_segment_step_cached.cache_clear()
    jax.clear_caches()


_REFS = {}
_RUN = reference.run


def cached_reference(config, traffic, data, seed, **kw):
    """`reference.run`, once per cell, seed and setting in this process:
    the sound run and every planted fault compare with the same one."""
    key = (config["name"], traffic["runner"], seed, repr(sorted(
        (k, np.asarray(v).tolist()) for k, v in kw.items())))
    if key not in _REFS:
        _REFS[key] = _RUN(config, traffic, data, seed, **kw)
    return _REFS[key]


def run(name, monkeypatch, fault=None):
    """One run of the tiny cell, with `fault` planted under its path."""
    monkeypatch.setattr(reference, "run", cached_reference)
    if fault is not None:
        FAULTS[fault](monkeypatch)
    clear_programs()
    try:
        return harness.run_cell(name, SEED, 0.0, False,
                                t_start=time.perf_counter(),
                                require_tpu=False, cell=tiny(name))
    finally:
        clear_programs()


def control_numbers(name):
    """The control's numbers against the reference, at the tiny size: the
    reference in the system's place makes the cohorts and values; the
    control follows them in bfloat16."""
    from repro.data.synth import make_dataset
    c = tiny(name)
    fl = c["config"]["fl"]
    data = make_dataset(c["config"]["model"]["dataset"],
                        n_train=fl["n_train"], n_val=fl["n_val"],
                        n_test=fl["n_test"],
                        difficulty=c["config"]["data"]["difficulty"],
                        seed=SEED)
    proto = reference.Protocol.of(c["config"], c["traffic"])
    greedy = proto.selector == "greedyfed"
    seed = c["traffic"]["program_seeds"][0]
    system = as_prog(cached_reference(c["config"], c["traffic"], data, seed),
                     compare)
    kw = dict(valued=compare.valued_rounds(system, proto,
                                           c["check"]["sv_rounds"], SEED,
                                           seed),
              cohorts=system.selections if greedy else None)
    ref = cached_reference(c["config"], c["traffic"], data, seed, **kw)
    ctl = reference.run(c["config"], c["traffic"], data, seed,
                        precision="bfloat16", **kw)
    nums = compare.numbers(as_prog(ctl, compare), ref, proto)
    if greedy:
        nums["greedy_gap"] = compare.greedy_gap(
            system.selections, system.sv, proto.n_clients, proto.rr_rounds)
    nums["window_mismatch"] = 0
    return nums, c["check"]["limits"]


def _frozen(monkeypatch):
    import repro.engine.batch_client as bc
    monkeypatch.setattr(bc, "client_update",
                        lambda model, cfg, params0, *a: params0)


def _half_batch(monkeypatch):
    import repro.engine.batch_client as bc
    orig = bc.client_update
    monkeypatch.setattr(
        bc, "client_update", lambda model, cfg, *a: orig(
            model, cfg._replace(batch_size=cfg.batch_size // 2), *a))


def _altered(monkeypatch):
    import repro.core.selection_jax as sj
    for name, fn in list(sj._SELECT_FNS.items()):
        def altered(spec, state, key, ctx, fn=fn):
            sel, state = fn(spec, state, key, ctx)
            return sel.at[0].set((sel[0] + 1) % spec.n_clients), state
        monkeypatch.setitem(sj._SELECT_FNS, name, altered)


def _argmin(monkeypatch):
    import repro.core.selection_jax as sj

    def argmin(spec, state, key, ctx):
        low = jnp.argsort(state.valuation.sv)[: spec.m].astype(jnp.int32)
        sel = jnp.where(state.round < spec.rr_rounds,
                        sj._rr_select(spec, state), low)
        return sel, state
    monkeypatch.setitem(sj._SELECT_FNS, "greedyfed", argmin)


FAULTS = {"frozen": _frozen, "half_batch": _half_batch, "altered": _altered,
          "argmin": _argmin}


def faults(name):
    """The faults cell `name` can have: a greedy phase only where the
    cell's selector has one."""
    c = harness.find_cell(name)
    greedy = {**c["config"]["fl"], **c["traffic"]["fl"]}["selector"] \
        == "greedyfed"
    return sorted(f for f in FAULTS if greedy or f != "argmin")
