"""The comparison that decides `correct`: the system's run against the
plain reference (`reference.py`), number by number.

Every number is a gap that is 0 when the two agree, and each has its own
limit in `checks/<cell>.json` (with the readings it was set from in
PERF.md).  The reference follows every round of each run:

- `sel_mismatch`: cohort slots that differ from the reference's (after
  GreedyFed's round-robin phase the reference follows the system's
  cohorts, which `greedy_gap` checks);
- `greedy_gap` (GreedyFed): per greedy round, how far the best client
  left out lies above the worst one taken, by the cumulative values the
  system's own per-round values make (the mean over the rounds a client
  was in), over the largest magnitude of those values; the worst round.
  0 where every cohort is the M largest;
- `trunc_gap` (GreedyFed): the share of rounds whose GTG truncation
  (|U(new) - U(old)| < eps) the system decided otherwise than the
  reference;
- `count_mismatch`: counters that differ: upload bytes, download bytes,
  and (GreedyFed) rounds whose utility evaluations are not what the
  protocol spends on a truncated or a valued round;
- `sv_gap` (GreedyFed): per valued round (a sample the harness draws
  from the seed), the widest gap of a client's Shapley value, over the
  reference's largest magnitude that round or `SV_FLOOR` x eps,
  whichever is larger; the worst round.  Values under 10 eps lie within
  the utility's rounding at the precision the configuration states, and
  the protocol itself takes utility changes under eps for none;
- `loss_gap`: relative gap of the validation loss at the eval rounds;
  the worst;
- `acc_gap`: absolute gap of the test accuracy there; the worst;
- `param_gap`: per leaf, the gap between the norms of the two runs'
  change from the initial weights after the last round, over the larger
  of the reference's change of that leaf and of the median leaf; the
  worst leaf.  Leaves whose reference change is under a thousandth of
  the median leaf's are left out;
- `window_mismatch` (made by the harness): outputs of the window's last
  pass that are not bitwise those of the warm-up calls the comparison
  read, which ran the same programs on the same inputs.

A gap that cannot be read (a missing round, a non-finite value) is
`UNREAD`, far above any limit and still a JSON number.
Numbers are taken per run; counts add, gaps take the worst run.
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional

import numpy as np

COUNTS = ("sel_mismatch", "count_mismatch", "window_mismatch")
UNREAD = 1e30
SV_FLOOR = 10.0   # x shapley_eps: the least magnitude a value is read at


class ProgOut(NamedTuple):
    """What the system's run produced, as the comparison reads it."""
    selections: np.ndarray     # (T, M)
    sv: Optional[np.ndarray]   # (T, M) per-round values, None if unvalued
    truncated: np.ndarray      # (T,) rounds whose GTG walk was truncated
    utility_evals: np.ndarray  # (T,)
    evals: dict                # {round (1-based): (test_acc, val_loss)}
    params: dict
    upload_bytes: int
    download_bytes: int


def prog_out(result, events: list, cell: Optional[int] = None) -> ProgOut:
    """Read one replica's run from its FLResult and the telemetry events
    its call emitted (`cell` picks the replica of a grid's events)."""
    rounds = [e for e in events if e["event"] == "round_metrics"
              and e.get("cell") == cell]
    rounds.sort(key=lambda e: e["round"])
    sv = (np.asarray([e["sv"] for e in rounds], np.float64)
          if rounds and "sv" in rounds[0] else None)
    acc = dict(result.test_acc)
    vloss = dict(result.val_loss)
    return ProgOut(
        selections=np.asarray(result.selections, np.int64),
        sv=sv,
        truncated=np.asarray([bool(e.get("sv_truncated", False))
                              for e in rounds], bool),
        utility_evals=np.asarray([e["utility_evals"] for e in rounds],
                                 np.int64),
        evals={r: (float(acc[r]), float(vloss[r])) for r in acc},
        params=_host(result.params),
        upload_bytes=int(result.upload_bytes),
        download_bytes=int(result.download_bytes))


def _host(tree) -> dict:
    if isinstance(tree, dict):
        return {k: _host(v) for k, v in tree.items()}
    return np.asarray(tree, np.float32)


def _leaves(tree, prefix=""):
    for k in sorted(tree):
        v = tree[k]
        if isinstance(v, dict):
            yield from _leaves(v, f"{prefix}{k}/")
        else:
            yield prefix + k, v


def _gap(value) -> float:
    value = float(value)
    return value if math.isfinite(value) else UNREAD


def param_gap(prog: dict, ref: dict, ref0: dict) -> float:
    """Worst leaf's gap between the norms of the change from `ref0`."""
    p, r, r0 = dict(_leaves(prog)), dict(_leaves(ref)), dict(_leaves(ref0))
    if set(p) != set(r) or any(p[k].shape != r[k].shape for k in r):
        return UNREAD
    n_ref = {k: float(np.linalg.norm((r[k] - r0[k]).astype(np.float64)))
             for k in r}
    n_prog = {k: float(np.linalg.norm((p[k] - r0[k]).astype(np.float64)))
              for k in r}
    med = float(np.median(list(n_ref.values())))
    worst = 0.0
    for k in r:
        if n_ref[k] < 1e-3 * med:
            continue
        worst = max(worst, abs(n_prog[k] - n_ref[k]) / max(n_ref[k], med))
    return _gap(worst)


def greedy_gap(selections: np.ndarray, sv: np.ndarray, n_clients: int,
               rr_rounds: int) -> float:
    """Worst greedy round's gap between the best client left out and the
    worst one taken, by the cumulative values of the rounds before."""
    cum = np.zeros(n_clients, np.float64)
    counts = np.zeros(n_clients, np.int64)
    worst = 0.0
    for t, sel in enumerate(selections):
        if t >= rr_rounds:
            out = np.ones(n_clients, bool)
            out[sel] = False
            scale = max(float(np.max(np.abs(cum))), 1e-12)
            worst = max(worst, (float(np.max(cum[out]))
                                - float(np.min(cum[sel]))) / scale)
        counts[sel] += 1
        cum[sel] = ((counts[sel] - 1) * cum[sel] + sv[t]) / counts[sel]
    return _gap(worst)


def numbers(prog: ProgOut, ref, proto) -> dict:
    """The gaps of one run (see the module docstring); `proto` is the
    reference's `Protocol` of the cell."""
    out = {}
    out["sel_mismatch"] = (int(np.sum(prog.selections != ref.selections))
                           if prog.selections.shape == ref.selections.shape
                           else int(ref.selections.size))
    counts = int(prog.upload_bytes != ref.upload_bytes)
    counts += int(prog.download_bytes != ref.download_bytes)
    if proto.selector == "greedyfed":
        T = proto.rounds
        whole = (prog.sv is not None and prog.sv.shape == ref.sv.shape
                 and prog.truncated.shape == (T,)
                 and prog.utility_evals.shape == (T,))
        if not whole:
            out["greedy_gap"] = out["trunc_gap"] = out["sv_gap"] = UNREAD
            counts += T
        else:
            out["greedy_gap"] = greedy_gap(prog.selections, prog.sv,
                                           proto.n_clients, proto.rr_rounds)
            out["trunc_gap"] = float(np.mean(prog.truncated
                                             != ref.truncated))
            spent = np.where(prog.truncated, 2, proto.walks * proto.m + 2)
            counts += int(np.sum(prog.utility_evals != spent))
            floor = max(SV_FLOOR * proto.shapley_eps, 1e-12)
            gaps = [np.max(np.abs(prog.sv[t] - ref.sv[t]))
                    / max(float(np.max(np.abs(ref.sv[t]))), floor)
                    for t in np.flatnonzero(ref.valued)]
            out["sv_gap"] = _gap(max(gaps)) if gaps else UNREAD
    out["count_mismatch"] = counts
    loss, acc = 0.0, 0.0
    for r, (ref_acc, ref_loss) in ref.evals.items():
        if r not in prog.evals:
            loss = acc = UNREAD
            break
        p_acc, p_loss = prog.evals[r]
        loss = max(loss, _gap(abs(p_loss - ref_loss) / abs(ref_loss)))
        acc = max(acc, _gap(abs(p_acc - ref_acc)))
    out["loss_gap"], out["acc_gap"] = loss, acc
    out["param_gap"] = param_gap(prog.params, ref.params, ref.params0)
    return out


def valued_rounds(prog: ProgOut, proto, n: int, seed: int,
                  run_seed: int) -> list:
    """`n` rounds to value, drawn from the seed among the rounds the
    system valued: half from GreedyFed's round-robin phase, half from its
    greedy phase, where each has enough."""
    if proto.selector != "greedyfed" or n <= 0:
        return []
    rng = np.random.default_rng([seed, run_seed, 0x5A])
    walked = ~prog.truncated if prog.truncated.shape == (proto.rounds,) \
        else np.ones(proto.rounds, bool)
    t = np.arange(proto.rounds)
    rr = t[walked & (t < proto.rr_rounds)]
    greedy = t[walked & (t >= proto.rr_rounds)]
    n_greedy = min(len(greedy), n - min(len(rr), n // 2))
    n_rr = min(len(rr), n - n_greedy)
    pick = list(rng.choice(rr, n_rr, replace=False)) + list(
        rng.choice(greedy, n_greedy, replace=False))
    return sorted(int(x) for x in pick)


def combine(per_replica: list) -> dict:
    """Counts add over runs; gaps take the worst run."""
    out = {}
    for nums in per_replica:
        for k, v in nums.items():
            if k in COUNTS:
                out[k] = out.get(k, 0) + v
            else:
                out[k] = max(out.get(k, 0.0), v)
    return out


def outputs_differ(a, b) -> int:
    """Outputs of two FLResults that are not bitwise equal."""
    diff = 0
    pa, pb = dict(_leaves(_host(a.params))), dict(_leaves(_host(b.params)))
    diff += sum(not np.array_equal(pa[k], pb.get(k)) for k in pa)
    diff += int(not np.array_equal(np.asarray(a.selections),
                                   np.asarray(b.selections)))
    diff += int(not np.array_equal(np.asarray(a.sv_final),
                                   np.asarray(b.sv_final)))
    diff += int(a.test_acc != b.test_acc) + int(a.val_loss != b.val_loss)
    diff += int(a.upload_bytes != b.upload_bytes)
    return diff


def verdict(nums: dict, limits: dict) -> bool:
    """True when every limit holds; a number without a limit, or a limit
    without its number, is a fault of the check itself."""
    if set(nums) != set(limits):
        raise KeyError(f"numbers {sorted(nums)} against limits "
                       f"{sorted(limits)}")
    return all(nums[k] <= limits[k] for k in limits)
