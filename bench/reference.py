"""Plain reference of a federated run, in straightforward jax.numpy.

It imports nothing of the system under test and takes nothing it made:
from the cell's configuration, traffic, data and seed it partitions the
data, draws the initial weights, selects, trains, encodes, values and
aggregates on its own, following the protocol's published recipe
(GreedyFed, arXiv 2312.09108, Alg. 1 and 2) and the seed's key
discipline, so that the same seed gives the same cohorts, minibatches
and permutation walks as the system does:

- partition: client sizes q_k ~ 3x^2 normalised, labels ~ Dirichlet(alpha),
  drawn with `numpy.random.default_rng(seed)`;
- keys: `key(seed)` split once for the initial weights, then
  `split(key, 3)` per round into (next, selection, round) keys; the round
  key splits into one key per cohort slot and one for the Shapley walks;
- selection: GreedyFed's round-robin phase through
  `default_rng(seed).permutation(N)`, then the M largest cumulative
  values (the mean of a client's per-round values over the rounds it
  was in, ties to the lower index); FedAvg draws M of N without
  replacement;
- local training: E*B steps of SGD with momentum on minibatches drawn
  with replacement from the client's own rows;
- upload codec: per client and leaf, the delta to the broadcast model is
  kept whole (identity), or top-k by magnitude and int8-quantised against
  the leaf's largest magnitude (quant8_topk);
- GTG-Shapley: utility U(w) = -CE on the validation set; a round whose
  |U(w_new) - U(w_prev)| < eps is truncated (all values 0); otherwise
  every prefix of R balanced permutation walks is averaged by n_k and
  valued, and each client's value is its mean marginal;
- aggregation: the n_k-weighted mean of the cohort's models;
- eval: test accuracy and validation loss after rounds t with
  (t+1) % eval_every == 0, and after the last round.

`run` follows every round of the run.  Given `cohorts`, the run's
cohorts after GreedyFed's round-robin phase are those (the system's,
whose choice the comparison checks on its own values), and only the
rounds in `valued` are valued, truncation aside; without them it selects
greedily on its own values and values every round.  Each round it also
says whether the protocol truncates it.  Precision "highest" computes
in float32 with every matmul at full float32 precision; "bfloat16" is
the control: the same run with data, weights and arithmetic in
bfloat16.  `fault` plants one of the faults the check has to catch
(`FAULTS`), for reading their limits.
"""
from __future__ import annotations

import importlib.util
import json
import math
import os
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
FAULTS = ("frozen", "half_batch", "altered")


def load_model(config: dict):
    """The configuration's plain model (`configs/<name>.py`): init, apply,
    and its counts of operations and weights."""
    path = os.path.join(HERE, "configs", config["name"] + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_model_" + config["name"].replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Protocol(NamedTuple):
    n_clients: int
    m: int
    rounds: int
    epochs: int
    batches_per_epoch: int
    batch_size: int
    lr: float
    momentum: float
    dirichlet_alpha: float
    walks: int
    shapley_eps: float
    eval_every: int
    selector: str
    upload_codec: str
    topk_frac: float

    @staticmethod
    def of(config: dict, traffic: dict) -> "Protocol":
        fl = {**config["fl"], **traffic.get("fl", {})}
        return Protocol(
            n_clients=fl["n_clients"], m=fl["m"], rounds=fl["rounds"],
            epochs=fl["epochs"], batches_per_epoch=fl["batches_per_epoch"],
            batch_size=fl["batch_size"], lr=fl["lr"],
            momentum=fl["momentum"],
            dirichlet_alpha=fl["dirichlet_alpha"],
            walks=fl["walks_per_client"] * fl["m"],
            shapley_eps=fl["shapley_eps"], eval_every=fl["eval_every"],
            selector=fl["selector"], upload_codec=fl["upload_codec"],
            topk_frac=fl["topk_frac"])

    @property
    def rr_rounds(self) -> int:
        """GreedyFed's round-robin phase: every client once."""
        return -(-self.n_clients // self.m)


class RefOut(NamedTuple):
    selections: np.ndarray    # (T, M) cohorts
    sv: np.ndarray            # (T, M) per-round values (0 where unvalued)
    valued: np.ndarray        # (T,) rounds whose values were computed
    truncated: np.ndarray     # (T,) rounds the protocol truncates
    utility_evals: np.ndarray  # (T,) the protocol's count per round
    evals: dict               # {round (1-based): (test_acc, val_loss)}
    params0: dict             # initial weights
    params: dict              # weights after the last round
    upload_bytes: int         # whole-run ledger (depends on shapes only)
    download_bytes: int


# --------------------------------------------------------------------------
# data partition
# --------------------------------------------------------------------------

def power_law_fractions(n: int, rng) -> np.ndarray:
    q = np.maximum(rng.random(n) ** (1.0 / 3.0), 1e-4)
    return q / q.sum()


def dirichlet_partition(labels, n_clients, alpha, rng, fractions,
                        min_per_client=2):
    """Client index arrays: each client fills its size from classes drawn
    by its own Dirichlet(alpha) mix, out of per-class pools in a random
    order, falling back to classes that still have rows."""
    classes = np.unique(labels)
    sizes = np.maximum((fractions * labels.shape[0]).astype(int),
                       min_per_client)
    pools = [rng.permutation(np.where(labels == c)[0]) for c in classes]
    cursors = np.zeros(len(classes), np.int64)
    remaining = np.asarray([p.size for p in pools], np.int64)
    out = []
    for k in range(n_clients):
        p = rng.dirichlet(np.full(classes.shape[0], max(alpha, 1e-6)))
        parts, need = [], int(sizes[k])
        while need > 0:
            avail = np.where(remaining > 0)[0]
            if avail.size == 0:
                break
            pa = p[avail]
            s = pa.sum()
            pa = pa / s if s > 1e-12 else np.full(avail.size, 1 / avail.size)
            cnt = np.bincount(rng.choice(avail.size, size=need, p=pa),
                              minlength=avail.size)
            grant = np.minimum(cnt, remaining[avail])
            for ci, g in zip(avail, grant):
                if g:
                    parts.append(pools[ci][cursors[ci]:cursors[ci] + g])
            cursors[avail] += grant
            remaining[avail] -= grant
            need -= int(grant.sum())
        take = np.concatenate(parts) if parts else np.empty(0, np.int64)
        if take.size < min_per_client:
            for ci in range(len(classes)):
                g = min(min_per_client - take.size, int(remaining[ci]))
                if g > 0:
                    take = np.concatenate(
                        [take, pools[ci][cursors[ci]:cursors[ci] + g]])
                    cursors[ci] += g
                    remaining[ci] -= g
        out.append(take.astype(np.int64))
    return out


def client_stacks(x, y, parts):
    """(N, cap, ...) rows of every client, zero past its own count."""
    cap = max(p.size for p in parts)
    xs = np.zeros((len(parts), cap) + x.shape[1:], np.float32)
    ys = np.zeros((len(parts), cap), np.int32)
    nv = np.zeros((len(parts),), np.int32)
    for i, p in enumerate(parts):
        xs[i, :p.size], ys[i, :p.size], nv[i] = x[p], y[p], p.size
    return xs, ys, nv


# --------------------------------------------------------------------------
# the round's pieces
# --------------------------------------------------------------------------

def cross_entropy(logits, y):
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - gold)


def codec_roundtrip(delta, codec: str, frac: float):
    """Encode then decode one flattened delta row."""
    if codec == "identity":
        return delta
    absx = jnp.abs(delta)
    k = max(1, int(delta.size * frac))
    scale = jnp.maximum(jnp.max(absx), 1e-12) / 127.0
    quant = jnp.clip(jnp.round(delta / scale), -127.0, 127.0) * scale
    if codec == "quant8":
        return quant
    _, idx = jax.lax.top_k(absx, k)
    keep = jnp.zeros(delta.shape, bool).at[idx].set(True)
    return jnp.where(keep, quant if codec == "quant8_topk" else delta, 0.0)


def codec_nbytes(codec: str, sizes) -> int:
    """Wire bytes of one encoded upload of leaves with `sizes` elements."""
    ks = [max(1, int(n * 0.1)) for n in sizes]
    return {"identity": sum(4 * n for n in sizes),
            "quant8": sum(n + 4 for n in sizes),
            "topk": sum(8 * k for k in ks),
            "quant8_topk": sum(5 * k + 4 for k in ks)}[codec]


def permutation_batch(key, m):
    """(M, M): row k is a walk that starts at client k."""
    def one(k, subkey):
        others = jnp.delete(jnp.arange(m), k, assume_unique_indices=True)
        return jnp.concatenate([jnp.array([k]),
                                jax.random.permutation(subkey, others)])
    return jax.vmap(one)(jnp.arange(m), jax.random.split(key, m))


def draw_walks(key, m, n_walks):
    """(R, M) balanced walks: whole batches in which each client leads
    once, rows shuffled, the first R kept."""
    n_batches = -(-n_walks // m)
    bkey, skey = jax.random.split(key)
    perms = jax.vmap(lambda k: permutation_batch(k, m))(
        jax.random.split(bkey, n_batches)).reshape(n_batches * m, m)
    return jax.random.permutation(skey, perms, axis=0)[:n_walks]


class Reference:
    """The jitted run of one precision (and one planted fault)."""

    def __init__(self, model, config: dict, proto: Protocol, dtype,
                 fault: Optional[str] = None):
        if fault is not None and fault not in FAULTS:
            raise ValueError(f"unknown fault {fault!r}; known {FAULTS}")
        self.model, self.config, self.p = model, config, proto
        self.dtype, self.fault = dtype, fault
        self.greedy = proto.selector == "greedyfed"
        self._run = jax.jit(self._run_impl, static_argnums=(0,))

    # ---- pieces ----------------------------------------------------------
    def loss(self, params, x, y):
        return cross_entropy(self.model.apply(params, x, self.config), y)

    def select(self, t, sel_key, rr_order, cum, forced, use_forced):
        p = self.p
        if not self.greedy:
            return jax.random.choice(sel_key, p.n_clients, (p.m,),
                                     replace=False).astype(jnp.int32)
        rr = jnp.take(rr_order, (t * p.m + jnp.arange(p.m)) % p.n_clients)
        own = jnp.argsort(-cum, stable=True)[: p.m]
        greedy = jnp.where(use_forced, forced, own)
        return jnp.where(t < p.rr_rounds, rr, greedy).astype(jnp.int32)

    def client(self, params, x, y, nv, key):
        p = self.p
        n_steps = p.epochs * p.batches_per_epoch
        idx_key, _ = jax.random.split(key)
        idx = jax.random.randint(idx_key, (n_steps, p.batch_size), 0,
                                 jnp.maximum(nv, 1))
        if self.fault == "frozen":
            return params
        if self.fault == "half_batch":
            idx = idx[:, : p.batch_size // 2]

        def step(i, carry):
            w, mom = carry
            g = jax.grad(self.loss)(w, x[idx[i]], y[idx[i]])
            mom = jax.tree.map(lambda a, b: p.momentum * a + b, mom, g)
            w = jax.tree.map(lambda a, b: a - p.lr * b, w, mom)
            return w, mom

        zeros = jax.tree.map(jnp.zeros_like, params)
        return jax.lax.fori_loop(0, n_steps, step, (params, zeros))[0]

    def cohort(self, params, key, sel, xs, ys, nv):
        ckeys = jax.random.split(key, self.p.m + 1)
        x, y, n = xs[sel], ys[sel], nv[sel]
        models = jax.vmap(self.client, in_axes=(None, 0, 0, 0, 0))(
            params, x.astype(self.dtype), y, n, ckeys[: self.p.m])
        codec = self.p.upload_codec

        def encode(leaf, ref):
            flat = (leaf - ref[None]).reshape(leaf.shape[0], -1)
            rt = jax.vmap(lambda d: codec_roundtrip(d, codec,
                                                    self.p.topk_frac))(flat)
            return ref[None] + rt.reshape(leaf.shape)

        models = jax.tree.map(encode, models, params)
        return models, n.astype(jnp.float32), ckeys[self.p.m]

    @staticmethod
    def average(models, n_k):
        w = n_k / jnp.sum(n_k)
        return jax.tree.map(
            lambda l: jnp.tensordot(w.astype(l.dtype), l, 1), models)

    def utility(self, w, x_val, y_val):
        return -self.loss(w, x_val, y_val).astype(jnp.float32)

    def shapley(self, models, n_k, v0, key, x_val, y_val):
        """Each client's mean marginal over R walks (Alg. 2)."""
        p = self.p
        walks = draw_walks(key, p.m, p.walks)               # (R, M)

        def walk_values(walk):
            prefix = jnp.cumsum(jax.nn.one_hot(walk, p.m), axis=0)  # (M, M)
            weights = prefix * n_k[None, :]
            weights = weights / weights.sum(-1, keepdims=True)

            def value(wts):
                return self.utility(jax.tree.map(
                    lambda l: jnp.tensordot(wts.astype(l.dtype), l, 1),
                    models), x_val, y_val)
            return jax.lax.map(value, weights)              # (M,)

        vs = jax.lax.map(walk_values, walks)
        prev = jnp.concatenate(
            [jnp.full((p.walks, 1), v0, jnp.float32), vs[:, :-1]], 1)
        return jnp.zeros((p.m,), jnp.float32).at[walks.reshape(-1)].add(
            (vs - prev).reshape(-1)) / p.walks

    def evaluate(self, params, x_test, y_test, x_val, y_val):
        logits = self.model.apply(params, x_test, self.config)
        acc = jnp.mean((jnp.argmax(logits, -1) == y_test).astype(jnp.float32))
        return acc, self.loss(params, x_val, y_val).astype(jnp.float32)

    # ---- the jitted run --------------------------------------------------
    def _run_impl(self, own, params, key, rr_order, per_round, stacks, val,
                  test):
        """`own`: select greedily on the run's own values, valuing every
        round as the protocol does (truncated rounds value 0); else
        follow `per_round`'s cohorts and value its rounds whole."""
        p = self.p
        xs, ys, nv = stacks
        zeros_m = jnp.zeros((p.m,), jnp.float32)

        def body(carry, per):
            params, key, u_prev, cum, counts = carry
            t, forced, use_forced, value, do_eval = per
            key, sel_key, round_key = jax.random.split(key, 3)
            sel = self.select(t, sel_key, rr_order, cum, forced, use_forced)
            if self.fault == "altered":
                sel = sel.at[0].set(jnp.where(
                    t == 0, (sel[0] + 1) % p.n_clients, sel[0]))
            models, n_k, sv_key = self.cohort(params, round_key, sel,
                                              xs, ys, nv)
            new = self.average(models, n_k)
            u_new = self.utility(new, *val)
            truncated = jnp.abs(u_new - u_prev) < p.shapley_eps
            compute = (value & ~truncated) if own else value
            sv = jax.lax.cond(
                compute,
                lambda: self.shapley(models, n_k, u_prev, sv_key, *val),
                lambda: zeros_m)
            n_sel = counts[sel] + 1
            cum = cum.at[sel].set(
                ((n_sel - 1) * cum[sel] + sv) / n_sel.astype(jnp.float32))
            counts = counts.at[sel].set(n_sel)
            nan = jnp.float32(jnp.nan)
            acc, vloss = jax.lax.cond(
                do_eval, lambda w: self.evaluate(w, *test, *val),
                lambda w: (nan, nan), new)
            return ((new, key, u_new, cum, counts),
                    (sel, sv, truncated, acc, vloss))

        u0 = self.utility(params, *val)
        carry = (params, key, u0, jnp.zeros((p.n_clients,), jnp.float32),
                 jnp.zeros((p.n_clients,), jnp.int32))
        (params, _, _, _, _), ys_out = jax.lax.scan(body, carry, per_round)
        return params, ys_out


_PIECES = {}


def pieces(config: dict, proto: Protocol, dtype, fault) -> Reference:
    """One `Reference` (and so one compiled run) per setting, so that the
    runs of a cell share their programs."""
    key = (config["name"], json.dumps(config["model"], sort_keys=True),
           proto, jnp.dtype(dtype).name, fault)
    if key not in _PIECES:
        _PIECES[key] = Reference(load_model(config), config, proto, dtype,
                                 fault)
    return _PIECES[key]


def run(config: dict, traffic: dict, data, seed: int, *,
        valued=(), cohorts: Optional[np.ndarray] = None,
        precision: str = "highest", fault: Optional[str] = None) -> RefOut:
    """Follow every round of the run with `seed` (see the module
    docstring for `cohorts` and `valued`)."""
    proto = Protocol.of(config, traffic)
    if proto.selector not in ("greedyfed", "random"):
        raise ValueError(f"no reference for selector {proto.selector!r}")
    T, m = proto.rounds, proto.m
    own = proto.selector == "greedyfed" and cohorts is None
    dtype = {"highest": jnp.float32, "bfloat16": jnp.bfloat16}[precision]
    mm = "highest" if precision == "highest" else "default"

    rng = np.random.default_rng(seed)
    fractions = power_law_fractions(proto.n_clients, rng)
    parts = dirichlet_partition(np.asarray(data.y_train), proto.n_clients,
                                proto.dirichlet_alpha, rng, fractions)
    xs, ys, nv = client_stacks(np.asarray(data.x_train),
                               np.asarray(data.y_train), parts)
    rr_order = jnp.asarray(np.random.default_rng(seed).permutation(
        proto.n_clients), jnp.int32)

    forced = (np.zeros((T, m), np.int32) if cohorts is None
              else np.asarray(cohorts, np.int32).reshape(T, m))
    value = np.zeros(T, bool)
    value[list(valued)] = True
    if own:
        value[:] = True
    per_round = (jnp.arange(T, dtype=jnp.int32), jnp.asarray(forced),
                 jnp.full((T,), cohorts is not None),
                 jnp.asarray(value),
                 jnp.asarray([((t + 1) % proto.eval_every == 0)
                              or t == T - 1 for t in range(T)]))

    key = jax.random.key(seed)
    key, init_key = jax.random.split(key)
    with jax.default_matmul_precision(mm):
        ref = pieces(config, proto, dtype, fault)
        params0 = ref.model.init(init_key, config)
        params = jax.tree.map(lambda l: l.astype(dtype), params0)
        stacks = (jnp.asarray(xs).astype(dtype), jnp.asarray(ys),
                  jnp.asarray(nv))
        val = (jnp.asarray(data.x_val).astype(dtype), jnp.asarray(data.y_val))
        test = (jnp.asarray(data.x_test).astype(dtype),
                jnp.asarray(data.y_test))
        params, (sel, sv, trunc, acc, vloss) = ref._run(
            own, params, key, rr_order, per_round, stacks, val, test)
    trunc = np.asarray(trunc)
    do_eval = np.asarray(per_round[4])
    acc, vloss = np.asarray(acc), np.asarray(vloss)
    sizes = [math.prod(l.shape) for l in jax.tree.leaves(params0)]
    per_valued = proto.walks * m + 2 if proto.selector == "greedyfed" else 0
    return RefOut(
        selections=np.asarray(sel, np.int64),
        sv=np.asarray(sv, np.float64),
        valued=value,
        truncated=trunc,
        utility_evals=np.where(trunc & (per_valued > 0), 2,
                               per_valued).astype(np.int64),
        evals={t + 1: (float(acc[t]), float(vloss[t]))
               for t in range(T) if do_eval[t]},
        params0=jax.tree.map(np.asarray, params0),
        params=jax.tree.map(lambda l: np.asarray(l, np.float32), params),
        upload_bytes=codec_nbytes(proto.upload_codec, sizes) * m * T,
        download_bytes=codec_nbytes("identity", sizes) * m * T)
