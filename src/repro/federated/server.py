"""The federated server loop — GreedyFed Alg. 1 plus all baselines.

One function, `run_federated`, drives T communication rounds:
  select clients -> ClientUpdate at each -> ModelAverage -> GTG-Shapley
  -> cumulative-SV update -> next round.
Strategy behaviour is fully encapsulated in a `SelectorSpec` + device
selector state (`repro.core.selection_jax` — the single runtime selector
implementation, DESIGN.md §13), so FedAvg / FedProx / Power-of-Choice /
S-FedAvg / UCB / GreedyFed all share this loop (the paper's experimental
protocol).

Round execution is pluggable (``cfg.engine``, DESIGN.md §6, §11):
  * "loop"    — the paper-faithful per-client Python loop (M dispatches per
                round); kept verbatim as the parity oracle;
  * "batched" — `repro.engine.RoundEngine`: the whole round (cohort gather,
                vmapped local training, upload codec, GTG-Shapley,
                ModelAverage) fused into ONE jitted dispatch;
  * "scan"    — `repro.engine.scan_engine`: the whole T-round RUN as one
                `lax.scan` dispatch, with selection and valuation living
                on-device (`repro.core.selection_jax`).

With ``cfg.schedule`` set, stragglers stop being randomly drawn: a virtual
clock derives each client's E_k from the round deadline
(`repro.engine.schedule`, DESIGN.md §9) and the run reports simulated
wall-clock time.  `run_federated_replicated` vmaps the fused round over a
seed axis so multi-seed benchmark tables amortise one compilation.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.aggregation import normalized_weights, tree_stack, weighted_average
from repro.core.selection_jax import (
    DeviceSelectionContext, DeviceSelectorState, SelectorSpec,
    init_device_state, jitted_selector, make_selector_spec, poc_d_schedule,
)
from repro.core.shapley import gtg_shapley
from repro.data.synth import SynthDataset, make_dataset
from repro.engine.schedule import (
    ScheduleConfig, VirtualClock, deadline_epochs, eval_mask,
    make_client_clock, round_duration_s,
)
from repro.federated.client import ClientConfig, client_update, local_loss
from repro.federated.compression import compress_update
from repro.federated.partition import (
    client_cap, dirichlet_partition, padded_x_block, padded_y_block,
    power_law_fractions, valid_counts,
)
from repro.models.mlp_cnn import ClassifierModel, make_classifier

PyTree = Any


@dataclass(frozen=True)
class FLConfig:
    dataset: str = "mnist"
    n_clients: int = 50          # N
    m: int = 5                   # M: clients selected per round
    rounds: int = 50             # T: communication budget
    selector: str = "greedyfed"
    selector_kwargs: dict = field(default_factory=dict)
    client: ClientConfig = ClientConfig()
    # round-execution engine: "loop" (per-client dispatches, parity oracle),
    # "batched" (fused single-dispatch round), or "scan" (whole run as one
    # lax.scan dispatch with device-resident selection)
    engine: str = "loop"
    # heterogeneity knobs (paper Section IV)
    dirichlet_alpha: float = 1e-4
    straggler_frac: float = 0.0  # x
    privacy_sigma: float = 0.0   # sigma
    # first-class privacy-noise grid axis (related repo's `noise_level`,
    # ROADMAP scenario diversity): each client gets an EXTRA uniform
    # [0, noise_level) update-noise sigma, folded into its per-client
    # sigma on the host before the run so the on-device round body is
    # unchanged.  0.0 (default) draws nothing — rng-stream neutral.
    noise_level: float = 0.0
    # random-straggler E_k stream revision (DESIGN.md §12):
    #   1 (default) — all engines draw the whole (T, N) budget table up
    #     front (engine.schedule.straggler_epochs_table), so loop/batched/
    #     scan are STREAM-identical under straggler_frac > 0;
    #   0 — legacy: loop/batched lazily draw per selected straggler in
    #     selection order (the paper-faithful stream the seed shipped
    #     with); scan stays table-driven, distribution-identical only.
    straggler_rev: int = 1
    # virtual-clock timing model; when set, E_k is deadline-derived and
    # straggler_frac is ignored (DESIGN.md §9)
    schedule: Optional[ScheduleConfig] = None
    # GTG-Shapley
    shapley_eps: float = 1e-4
    shapley_max_iters: Optional[int] = None   # default 50*M
    # "streaming" (DESIGN.md §14 incremental prefix walk — the default
    # device SV path for every engine) | "batched" (§8 dense oracle) |
    # "serial" (Alg. 2, within-round truncation; degrades under the
    # scan/replica-vmap engines, where lax.cond runs both branches)
    shapley_impl: str = "streaming"
    # streaming SV: prefix models materialised + evaluated per step,
    # rounded up to whole M-model walks — the memory knob that lets GTG
    # run inside replica-sharded grids at paper scale (peak SV memory
    # O(max(sv_chunk, M) * D) instead of O(R*M*D)).  0 = auto (one walk
    # off-TPU, all R*M on TPU), < 0 forces the all-resident pass; every
    # chunking is bit-identical, so the knob never changes results.
    sv_chunk: int = 0
    sv_averaging: str = "mean"   # "mean" | "exponential"
    sv_alpha: float = 0.5
    # upload compression (paper Related-Work contrast; see
    # federated/compression.py): applied to the client->PS delta
    upload_codec: str = "identity"
    # fault injection + hardened execution (repro.faults, DESIGN.md §19):
    # `faults` (a repro.faults.FaultSpec) pre-draws a (T, N) fault-code
    # table in setup_run — NaN/Inf poison, sign-flip/scaled byzantine
    # updates, mid-round crash dropout — consumed identically by all
    # engines; `quarantine` enables the in-round screen that masks
    # non-finite / norm-outlier updates out of aggregation, SV walks, and
    # the byte ledger.  Quarantine-on over a clean run is bit-identical
    # to off.  All three are grid-static (one executable per setting).
    faults: Optional[Any] = None
    quarantine: bool = False
    quarantine_z: float = 8.0
    # bookkeeping
    eval_every: int = 5
    seed: int = 0
    n_train: int = 6000
    n_val: int = 500
    n_test: int = 1000
    # client-axis sharding (DESIGN.md §16, engine="scan" only): shard the
    # (N, cap, ...) client stacks + per-client selector state over this
    # many devices, making per-device client memory O(N / clients_shards).
    # Bit-identical to the dense run at any value; 1 = dense (default).
    clients_shards: int = 1


class FLResult(NamedTuple):
    config: FLConfig
    test_acc: list            # [(round, acc)]
    val_loss: list            # [(round, loss)]
    final_acc: float
    sv_final: np.ndarray      # (N,)
    selection_counts: np.ndarray
    selections: list          # [np.ndarray (M,)] per round
    shapley_evals: int        # total utility evaluations spent
    wall_time_s: float
    params: PyTree
    upload_bytes: int = 0     # total client->PS traffic over the run
    download_bytes: int = 0   # total PS->client traffic (model broadcasts)
    sim_time_s: float = 0.0   # virtual-clock seconds (0 without schedule)
    dispatches: int = 0       # host->device program launches issued
    # wall_time_s split (DESIGN.md §15): jit trace+lower+compile seconds
    # attributed via jax.monitoring vs everything else.  A warm executable
    # (cached round/scan programs) reports compile_time_s ~ 0, so the
    # headline timing no longer silently includes first-dispatch compiles.
    compile_time_s: float = 0.0
    execute_time_s: float = 0.0
    # total cohort rows masked by the fault/quarantine stage (§19); 0 on
    # fault-free runs and whenever hardening is off
    quarantined_total: int = 0


def _pad_clients(x, y, parts):
    cap = client_cap(parts)
    n = len(parts)
    return (jnp.asarray(padded_x_block(x, parts, cap, 0, n)),
            jnp.asarray(padded_y_block(y, parts, cap, 0, n)),
            jnp.asarray(valid_counts(parts, 0, n)))


def _shard_clients(x, y, parts, mesh):
    """Client-axis-sharded padded stacks, materialised lazily per shard.

    Each device's rows of the (N_pad, cap, ...) stacks are built from the
    partition indices via `jax.make_array_from_callback`, so the host
    never holds the dense O(N) stacks — only one shard block at a time
    (DESIGN.md §16).  Rows [n_clients, N_pad) are zero pad clients.
    """
    from repro.grid.shard import clients_padded
    from repro.launch.mesh import CLIENT_AXIS
    n_pad = clients_padded(len(parts), mesh.shape[CLIENT_AXIS])
    cap = client_cap(parts)

    def build(shape, dtype, block):
        spec = jax.sharding.PartitionSpec(
            CLIENT_AXIS, *([None] * (len(shape) - 1)))
        sharding = jax.sharding.NamedSharding(mesh, spec)

        def cb(index):
            lo = index[0].start or 0
            hi = shape[0] if index[0].stop is None else index[0].stop
            return block(lo, hi).astype(dtype)

        return jax.make_array_from_callback(shape, sharding, cb)

    xs = build((n_pad, cap) + x.shape[1:], np.float32,
               lambda lo, hi: padded_x_block(x, parts, cap, lo, hi))
    ys = build((n_pad, cap), np.int32,
               lambda lo, hi: padded_y_block(y, parts, cap, lo, hi))
    nv = build((n_pad,), np.int32,
               lambda lo, hi: valid_counts(parts, lo, hi))
    return xs, ys, nv


class RunSetup(NamedTuple):
    """Everything `run_federated` derives from an FLConfig before round 0.

    Shared with `engine.replicated` so the multi-seed path reproduces the
    exact same rng/key streams as a solo run at the same seed.
    """
    data: SynthDataset
    model: ClassifierModel
    rng: np.random.Generator
    key: jax.Array
    fractions: np.ndarray
    xs: jax.Array
    ys: jax.Array
    n_valid: jax.Array
    n_k_all: jax.Array
    straggler_ids: set
    sigma_k_all: np.ndarray
    params: PyTree
    sel_spec: SelectorSpec               # the run's selection strategy
    sel_state: DeviceSelectorState       # its initial device state
    x_val: jax.Array
    y_val: jax.Array
    x_test: jax.Array
    y_test: jax.Array
    model_bytes: int
    clock: Any                # engine.schedule.ClientClock | None
    # (T, N) pre-drawn random-straggler budgets (straggler_rev >= 1 only;
    # None under a schedule, without stragglers, or at straggler_rev=0)
    epochs_table: Any = None
    # (T, N) pre-drawn int32 fault-code table (cfg.faults only, §19)
    fault_table: Any = None


def setup_run(cfg: FLConfig, data: Optional[SynthDataset] = None,
              model: Optional[ClassifierModel] = None, *,
              client_mesh=None) -> RunSetup:
    """Partition data, assign heterogeneity, init model/selector state.

    Draw order on `rng`/`key` is frozen (parity across engines and with the
    seed history); anything new must draw strictly after the existing calls.
    `client_mesh` (a mesh with a CLIENT_AXIS, DESIGN.md §16) switches the
    padded stacks to lazily-materialised client-axis-sharded arrays; the
    rng/key streams and every derived value are unchanged (the stacks just
    gain zero pad rows that nothing reads).
    """
    from repro.telemetry.trace import stage

    with stage("setup"):
        return _setup_run(cfg, data, model, client_mesh)


def _setup_run(cfg: FLConfig, data: Optional[SynthDataset],
               model: Optional[ClassifierModel], client_mesh) -> RunSetup:
    rng = np.random.default_rng(cfg.seed)
    key = jax.random.key(cfg.seed)

    if data is None:
        data = make_dataset(cfg.dataset, n_train=cfg.n_train, n_val=cfg.n_val,
                            n_test=cfg.n_test, seed=cfg.seed)
    if model is None:
        model = make_classifier(cfg.dataset)

    # ---- partition data across clients (Dirichlet x power-law) ----------
    fractions = power_law_fractions(cfg.n_clients, rng)
    parts = dirichlet_partition(data.y_train, cfg.n_clients,
                                cfg.dirichlet_alpha, rng, fractions)
    if client_mesh is not None:
        xs, ys, n_valid = _shard_clients(data.x_train, data.y_train, parts,
                                         client_mesh)
    else:
        xs, ys, n_valid = _pad_clients(data.x_train, data.y_train, parts)
    n_k_all = n_valid.astype(jnp.float32)

    # ---- heterogeneity assignments --------------------------------------
    n_stragglers = int(round(cfg.straggler_frac * cfg.n_clients))
    straggler_ids = set(rng.choice(cfg.n_clients, n_stragglers,
                                   replace=False).tolist())
    noise_perm = rng.permutation(cfg.n_clients)  # sigma_k = rank * sigma / N
    sigma_k_all = np.zeros(cfg.n_clients, np.float32)
    for rank, k in enumerate(noise_perm):
        sigma_k_all[k] = rank * cfg.privacy_sigma / cfg.n_clients

    # ---- model / selector setup ------------------------------------------
    key, init_key = jax.random.split(key)
    params = model.init(init_key)
    # sv_averaging/sv_alpha reach GreedyFed-family selectors through the
    # spec (explicit selector_kwargs win) — never by mutating state after
    # construction.  selection_jax is the single runtime implementation
    # (DESIGN.md §13); neither call consumes the run's rng/key streams.
    sel_kwargs = dict(cfg.selector_kwargs)
    if cfg.selector in ("greedyfed", "greedyfed_dropout"):
        sel_kwargs.setdefault("averaging", cfg.sv_averaging)
        sel_kwargs.setdefault("alpha", cfg.sv_alpha)
    sel_spec = make_selector_spec(cfg.selector, cfg.n_clients, cfg.m,
                                  **sel_kwargs)
    sel_state = init_device_state(sel_spec, cfg.seed)

    model_bytes = sum(int(x.size) * x.dtype.itemsize
                      for x in jax.tree.leaves(params))

    # ---- virtual clock (draws AFTER all legacy consumption of rng) ------
    clock = None
    if cfg.schedule is not None:
        clock = make_client_clock(cfg.schedule, cfg.n_clients, model_bytes,
                                  rng, n_k=np.asarray(n_valid)[:cfg.n_clients])

    # ---- straggler_rev >= 1: pre-draw the (T, N) budget table -----------
    # Drawn at the exact stream position where the scan engine used to
    # draw it (first consumption of rng after setup), so rev=1 keeps the
    # scan engine's tables bitwise unchanged while making loop/batched
    # consume the SAME table — all three engines stream-identical.
    epochs_table = None
    if cfg.straggler_rev >= 1 and clock is None and straggler_ids:
        from repro.engine.schedule import straggler_epochs_table
        epochs_table = straggler_epochs_table(
            rng, cfg.rounds, cfg.n_clients, straggler_ids,
            cfg.client.epochs)

    # ---- noise_level: extra per-client update-noise sigma (gated) -------
    # Folded into sigma_k_all on the host so the device round body is
    # untouched; sqrt(sigma^2 + 0^2) is NOT bitwise sigma in f32, hence
    # the gate — noise_level=0.0 configs keep the exact legacy sigmas
    # AND an untouched rng stream.
    if cfg.noise_level > 0:
        extra = rng.uniform(0.0, cfg.noise_level, cfg.n_clients)
        sigma_k_all = np.sqrt(sigma_k_all.astype(np.float64) ** 2
                              + extra ** 2).astype(np.float32)

    # ---- faults: pre-draw the (T, N) fault-code table (gated, §19) ------
    # Same discipline as the straggler table: drawn strictly AFTER every
    # other consumer of `rng`, gated on cfg.faults, so fault-free configs
    # are rng-stream (and therefore bitwise) unchanged.
    fault_table = None
    if cfg.faults is not None:
        from repro.faults import draw_fault_table
        fault_table = draw_fault_table(cfg.faults, cfg.rounds,
                                       cfg.n_clients, rng)

    return RunSetup(
        data=data, model=model, rng=rng, key=key, fractions=fractions,
        xs=xs, ys=ys, n_valid=n_valid, n_k_all=n_k_all,
        straggler_ids=straggler_ids, sigma_k_all=sigma_k_all, params=params,
        sel_spec=sel_spec, sel_state=sel_state,
        x_val=jnp.asarray(data.x_val), y_val=jnp.asarray(data.y_val),
        x_test=jnp.asarray(data.x_test), y_test=jnp.asarray(data.y_test),
        model_bytes=model_bytes, clock=clock, epochs_table=epochs_table,
        fault_table=fault_table,
    )


def round_epochs(cfg: FLConfig, s: RunSetup, sel: np.ndarray,
                 t: int = 0) -> np.ndarray:
    """(M,) int32 local-epoch budget E_k for the selected cohort at round t.

    Deadline-derived when a schedule is set (DESIGN.md §9); otherwise a
    gather from the pre-drawn (T, N) straggler table (straggler_rev >= 1,
    stream-identical across all engines), falling back to the legacy
    per-selection draw from `s.rng` at straggler_rev=0.
    """
    e = cfg.client.epochs
    if s.clock is not None:
        return deadline_epochs(s.clock, cfg.schedule, sel, e)
    if s.epochs_table is not None:
        return s.epochs_table[t][np.asarray(sel)].astype(np.int32)
    out = np.full(len(sel), e, np.int32)
    for i, k_id in enumerate(sel):
        if int(k_id) in s.straggler_ids:
            out[i] = int(s.rng.integers(1, e + 1))
    return out


def _make_round_engine(cfg: FLConfig, s: RunSetup, needs_sv: bool,
                       max_iters: int):
    from repro.engine.round_engine import RoundEngine, RoundSpec
    spec = RoundSpec(needs_sv=needs_sv, shapley_impl=cfg.shapley_impl,
                     shapley_eps=cfg.shapley_eps, shapley_max_iters=max_iters,
                     sv_chunk=cfg.sv_chunk, upload_codec=cfg.upload_codec,
                     faults=cfg.faults, quarantine=cfg.quarantine,
                     quarantine_z=cfg.quarantine_z)
    return RoundEngine(s.model, cfg.client, spec, s.xs, s.ys, s.n_valid,
                       jnp.asarray(s.sigma_k_all), s.x_val, s.y_val)


def run_federated(cfg: FLConfig, data: Optional[SynthDataset] = None,
                  model: Optional[ClassifierModel] = None, *,
                  telemetry=None) -> FLResult:
    """Drive one federated run; `telemetry` (repro.telemetry.Telemetry)
    opts into the structured event stream of DESIGN.md §15 — the default
    None path adds zero dispatches and leaves every output bit-identical.
    With `telemetry.trace_dir` set, one profiler capture window (§17)
    spans set-up and the rounds, so it holds the `repro.setup` span too.
    """
    from repro.telemetry.profile import trace_capture
    from repro.telemetry.trace import CompileTimer

    t_start = time.perf_counter()
    if cfg.engine not in ("loop", "batched", "scan"):
        raise ValueError(f"unknown engine {cfg.engine!r}; "
                         "options: 'loop', 'batched', 'scan'")
    from repro.engine.round_engine import SHAPLEY_IMPLS
    if cfg.shapley_impl not in SHAPLEY_IMPLS:
        raise ValueError(f"unknown shapley_impl {cfg.shapley_impl!r}; "
                         f"options: {SHAPLEY_IMPLS}")
    client_mesh = None
    if cfg.clients_shards > 1:
        if cfg.engine != "scan":
            raise ValueError("clients_shards > 1 requires engine='scan' "
                             "(the loop/batched engines are host-driven "
                             "and hold dense stacks by design)")
        from repro.launch.mesh import make_run_mesh
        client_mesh = make_run_mesh(1, cfg.clients_shards)
    ctimer = CompileTimer()
    label = f"run_{cfg.engine}" + ("_client_sharded"
                                   if client_mesh is not None else "")
    with trace_capture(telemetry, label=label):
        with ctimer:
            s = setup_run(cfg, data, model, client_mesh=client_mesh)
        if telemetry is not None:
            from repro.telemetry.events import provenance
            telemetry.emit("run_start", run_id=telemetry.run_id,
                           kind="solo", engine=cfg.engine,
                           selector=cfg.selector, n_clients=cfg.n_clients,
                           m=cfg.m, rounds=cfg.rounds, seed=cfg.seed,
                           eval_every=cfg.eval_every,
                           provenance=provenance())
        if cfg.engine == "scan":
            from repro.engine.scan_engine import run_federated_scan
            return run_federated_scan(cfg, s, t_start, telemetry=telemetry,
                                      ctimer=ctimer)
        return _run_host_rounds(cfg, s, t_start, telemetry=telemetry,
                                ctimer=ctimer)


def _run_host_rounds(cfg: FLConfig, s: RunSetup, t_start: float, *,
                     telemetry, ctimer) -> FLResult:
    """The loop and batched engines: each round driven from the host."""
    model, params, key = s.model, s.params, s.key
    sel_spec, sstate = s.sel_spec, s.sel_state
    dev_select, dev_update = jitted_selector(sel_spec)

    def utility_fn(p):  # U(w) = -L(w; D_val)
        return -model.loss(p, s.x_val, s.y_val)

    batched_utility_fn = None
    if cfg.shapley_impl in ("batched", "streaming"):
        from repro.core.shapley_batched import make_batched_mlp_utility
        batched_utility_fn = make_batched_mlp_utility(model, s.x_val, s.y_val)

    needs_sv = sel_spec.uses_shapley
    max_iters = cfg.shapley_max_iters or 50 * cfg.m

    # §19 hardening for the host engines: the loop engine runs the exact
    # same jitted harden_cohort ops the fused/scan engines trace inline,
    # so all engines agree on what gets quarantined
    hardened = cfg.faults is not None or cfg.quarantine
    harden = None
    if hardened:
        from repro.faults import jitted_harden
        harden = jitted_harden(cfg.faults, cfg.quarantine, cfg.quarantine_z)

    def round_codes(sel, t):
        if s.fault_table is not None:
            return s.fault_table[t][np.asarray(sel)]
        return np.zeros(len(sel), np.int32)

    engine = None
    codec_bytes = s.model_bytes
    if cfg.engine == "batched":
        engine = _make_round_engine(cfg, s, needs_sv, max_iters)
        codec_bytes = engine.upload_nbytes_per_client(params)

    all_losses_fn = jax.jit(jax.vmap(
        lambda p, x, y, n: local_loss(model, p, x, y, n),
        in_axes=(None, 0, 0, 0)))

    eval_acc = jax.jit(model.accuracy)

    fractions = jnp.asarray(s.fractions)
    zero_losses = jnp.zeros((cfg.n_clients,), jnp.float32)
    d_sched = poc_d_schedule(sel_spec, cfg.rounds)
    emask = eval_mask(cfg.rounds, cfg.eval_every)

    test_acc, val_loss_hist, selections = [], [], []
    total_evals = 0
    upload_bytes = download_bytes = 0
    quarantined_total = 0
    dispatches = 0
    sv_rounds = trunc_rounds = 0   # telemetry-only truncation counters
    vclock = VirtualClock() if s.clock is not None else None

    # jit compiles during the rounds (first dispatch of each cached
    # program) are attributed to compile_time_s by the active timer
    with ctimer:
        for t in range(cfg.rounds):
            key, sel_key, round_key = jax.random.split(key, 3)

            losses = zero_losses
            if sel_spec.uses_local_losses:
                losses = all_losses_fn(params, s.xs, s.ys, s.n_valid)
                dispatches += 1

            ctx = DeviceSelectionContext(data_fractions=fractions,
                                         local_losses=losses,
                                         poc_d=jnp.asarray(d_sched[t]))
            sel_dev, sstate = dev_select(sstate, sel_key, ctx)
            sel = np.asarray(sel_dev, np.int64)
            selections.append(sel)
            epochs_k = round_epochs(cfg, s, sel, t)

            sv_round = None
            evals_round = 0
            trunc_round = None         # device bool; read only with telemetry
            round_upload = 0
            q_round = 0
            if engine is not None:
                # ---- fused round: ONE dispatch for train+codec+SV+average ----
                codes = round_codes(sel, t) if hardened else None
                out = engine.step(params, sel, epochs_k, round_key,
                                  fault_codes=codes)
                params = out.params
                if needs_sv:
                    sv_round = out.sv
                    evals_round = int(out.utility_evals)
                    total_evals += evals_round
                    trunc_round = out.sv_truncated
                if hardened:
                    # charge only survivors: quarantined uploads never
                    # reach the PS (crash) or are discarded at ingest
                    q_round = int(out.quarantined)
                    quarantined_total += q_round
                    round_upload = codec_bytes * int(np.asarray(out.ok).sum())
                else:
                    round_upload = codec_bytes * len(sel)
                upload_bytes += round_upload
                dispatches += 1
            else:
                # ---- legacy loop: ClientUpdate at each selected client -------
                ckeys = jax.random.split(round_key, len(sel) + 1)
                updates, nbytes_list = [], []
                for i, k_id in enumerate(sel):
                    upd = client_update(
                        model, cfg.client, params, s.xs[k_id], s.ys[k_id],
                        s.n_valid[k_id], jnp.asarray(int(epochs_k[i])),
                        jnp.asarray(s.sigma_k_all[k_id]), ckeys[i])
                    if cfg.upload_codec != "identity":
                        upd, nbytes = compress_update(cfg.upload_codec, upd,
                                                      params)
                    else:
                        nbytes = s.model_bytes
                    nbytes_list.append(nbytes)
                    updates.append(upd)
                dispatches += len(sel)

                stacked = tree_stack(updates)
                n_k_sel = s.n_k_all[jnp.asarray(sel)]

                # ---- §19 hardening: inject + screen + mask -------------------
                h = None
                n_k_sv = n_k_sel
                if hardened:
                    codes = jnp.asarray(round_codes(sel, t), jnp.int32)
                    h = harden(stacked, params, n_k_sel, codes)
                    stacked, n_k_sv = h.stacked, h.n_k_sv
                    ok_np = np.asarray(h.ok)
                    q_round = int(h.quarantined)
                    quarantined_total += q_round
                    round_upload = int(sum(
                        nb for nb, good in zip(nbytes_list, ok_np) if good))
                    dispatches += 1
                else:
                    round_upload = int(sum(nbytes_list))

                # ---- GTG-Shapley at the PS (Alg. 2 / device variants) --------
                if needs_sv:
                    if cfg.shapley_impl == "streaming":
                        from repro.core.shapley_batched import (
                            gtg_shapley_streaming,
                        )
                        sv_round, stats = gtg_shapley_streaming(
                            stacked, n_k_sv, params, utility_fn,
                            batched_utility_fn, ckeys[-1], eps=cfg.shapley_eps,
                            n_perms=max_iters, sv_chunk=cfg.sv_chunk)
                    elif cfg.shapley_impl == "batched":
                        from repro.core.shapley_batched import gtg_shapley_batched
                        sv_round, stats = gtg_shapley_batched(
                            stacked, n_k_sv, params, utility_fn,
                            batched_utility_fn, ckeys[-1], eps=cfg.shapley_eps,
                            n_perms=max_iters)
                    else:
                        sv_round, stats = gtg_shapley(
                            stacked, n_k_sv, params, utility_fn, ckeys[-1],
                            eps=cfg.shapley_eps, max_iters=max_iters)
                    evals_round = int(stats.utility_evals)
                    total_evals += evals_round
                    trunc_round = stats.truncated_round
                    dispatches += 1
                    if h is not None:
                        sv_round = jnp.where(h.ok, sv_round,
                                             jnp.zeros((), sv_round.dtype))

                # ---- ModelAverage (Alg. 1 line 9) ----------------------------
                if h is not None:
                    from repro.faults import masked_average
                    params = masked_average(stacked, h.n_k_agg, h.ok, params)
                else:
                    params = weighted_average(stacked,
                                              normalized_weights(n_k_sel))
                dispatches += 1
                upload_bytes += round_upload

            download_bytes += s.model_bytes * len(sel)  # w^t broadcast
            if vclock is not None:
                vclock.advance(round_duration_s(s.clock, cfg.schedule, sel,
                                                epochs_k))

            sstate = dev_update(sstate, sel_dev, sv_round)

            do_eval = bool(emask[t])
            if do_eval:
                acc = float(eval_acc(params, s.x_test, s.y_test))
                vl = float(-utility_fn(params))
                test_acc.append((t + 1, acc))
                val_loss_hist.append((t + 1, vl))
                dispatches += 2

            if telemetry is not None:
                truncated = bool(np.asarray(trunc_round)) \
                    if trunc_round is not None else False
                if needs_sv:
                    sv_rounds += 1
                    trunc_rounds += truncated
                fields = dict(round=t, selections=sel, epochs=epochs_k,
                              utility_evals=evals_round, sv_truncated=truncated,
                              upload_bytes=round_upload,
                              download_bytes=s.model_bytes * len(sel))
                if hardened:
                    fields["quarantined"] = q_round
                if sv_round is not None:
                    fields["sv"] = np.asarray(sv_round)
                telemetry.emit("round_metrics", **fields)
                if do_eval:
                    telemetry.emit("eval", round=t, test_acc=acc, val_loss=vl)

    counts = np.asarray(sstate.valuation.counts)
    wall = time.perf_counter() - t_start
    compile_s = ctimer.seconds
    final_acc = test_acc[-1][1] if test_acc else float("nan")
    if telemetry is not None:
        from repro.telemetry.metrics import run_end_payload
        telemetry.emit("compile", seconds=compile_s,
                       program=f"{cfg.engine}_round_programs")
        telemetry.emit("run_end", **run_end_payload(
            rounds=cfg.rounds, wall_time_s=wall, compile_time_s=compile_s,
            final_acc=final_acc, utility_evals=total_evals,
            upload_bytes=upload_bytes, download_bytes=download_bytes,
            sv_rounds=sv_rounds, truncated_rounds=trunc_rounds,
            dispatches=dispatches))
    return FLResult(
        config=cfg,
        test_acc=test_acc,
        val_loss=val_loss_hist,
        final_acc=final_acc,
        sv_final=np.asarray(sstate.valuation.sv),
        selection_counts=counts,
        selections=selections,
        shapley_evals=total_evals,
        wall_time_s=wall,
        params=params,
        upload_bytes=upload_bytes,
        download_bytes=download_bytes,
        sim_time_s=vclock.now_s if vclock is not None else 0.0,
        dispatches=dispatches,
        compile_time_s=compile_s,
        execute_time_s=max(wall - compile_s, 0.0),
        quarantined_total=quarantined_total,
    )


def run_federated_replicated(cfg: FLConfig, seeds,
                             data: Optional[SynthDataset] = None,
                             model: Optional[ClassifierModel] = None,
                             selectors=None, **grid_kwargs) -> list[FLResult]:
    """Run a replica batch with ONE fused program (repro.engine.replicated).

    With ``cfg.engine != "scan"`` and no `selectors`, this is the PR-1
    per-round vmap: the fused round step advances all seeds per dispatch
    (DESIGN.md §6).  With ``cfg.engine == "scan"`` (or a `selectors` list
    of registry names) the whole strategies × seeds table — selection and
    valuation included — runs through `repro.grid.run_grid`
    (DESIGN.md §12): one whole-run `lax.scan` dispatch per capability
    partition, optionally segmented/checkpointed and replica-sharded via
    keyword passthrough; results come back selector-major, seed-minor.
    """
    if cfg.engine == "scan" or selectors is not None:
        from repro.engine.replicated import run_replicated_scan
        return run_replicated_scan(cfg, seeds, selectors=selectors,
                                   data=data, model=model, **grid_kwargs)
    if grid_kwargs:
        raise ValueError("grid options (rounds_per_segment, "
                         "checkpoint_dir, ...) require engine='scan'")
    from repro.engine.replicated import run_replicated
    return run_replicated(cfg, seeds, data=data, model=model)


def run_centralized(cfg: FLConfig, data: Optional[SynthDataset] = None,
                    model: Optional[ClassifierModel] = None) -> FLResult:
    """Upper bound: the server trains on the pooled data, same step budget."""
    if data is None:
        data = make_dataset(cfg.dataset, n_train=cfg.n_train, n_val=cfg.n_val,
                            n_test=cfg.n_test, seed=cfg.seed)
    if model is None:
        model = make_classifier(cfg.dataset)
    key = jax.random.key(cfg.seed)
    key, init_key = jax.random.split(key)
    params = model.init(init_key)

    x = jnp.asarray(data.x_train)
    y = jnp.asarray(data.y_train)
    n = jnp.asarray(x.shape[0])
    t_start = time.time()
    test_acc = []
    eval_acc = jax.jit(model.accuracy)
    x_test, y_test = jnp.asarray(data.x_test), jnp.asarray(data.y_test)
    emask = eval_mask(cfg.rounds, cfg.eval_every)
    for t in range(cfg.rounds):
        key, k = jax.random.split(key)
        params = client_update(model, cfg.client, params, x, y, n,
                               jnp.asarray(cfg.client.epochs),
                               jnp.asarray(0.0), k)
        if emask[t]:
            test_acc.append((t + 1, float(eval_acc(params, x_test, y_test))))
    return FLResult(cfg, test_acc, [], test_acc[-1][1], np.zeros(cfg.n_clients),
                    np.zeros(cfg.n_clients, np.int32), [], 0,
                    time.time() - t_start, params)
