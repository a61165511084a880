"""Host spans at the layer boundaries of a run, and the gather scope.

A solo scan run writes `repro.setup`, `repro.operands`, `repro.scan` and
`repro.results` once each; a grid writes `repro.setup` once per cell and
`repro.operands`, `repro.segment` and `repro.results` once per dispatch.
They land in the profiler's own trace, on the calling thread's line and
inside the caller's enclosing annotation, one after another.  The cohort
gather carries its own `repro.gather` scope inside `repro.train` in the
compiled round step's HLO.
"""
import glob
import os
import re
from collections import Counter

import jax
import jax.numpy as jnp
from jax.profiler import ProfileData

from repro.engine.round_engine import RoundSpec, jitted_round_step
from repro.federated.client import ClientConfig
from repro.federated.server import FLConfig, run_federated
from repro.grid import GridSpec, run_grid
from repro.models.mlp_cnn import make_classifier

TINY = dict(n_clients=6, m=2, rounds=4, n_train=240, n_val=40, n_test=40,
            eval_every=2,
            client=ClientConfig(epochs=1, batches_per_epoch=1, batch_size=8))
CALLER = "test.caller"
TOP = ("repro.setup", "repro.operands", "repro.scan", "repro.segment",
       "repro.results")


def _traced(tmp_path, fn):
    """Run `fn` under the profiler inside the caller's annotation; return
    (the caller's span, the `repro.*` spans on its line, in order)."""
    tdir = str(tmp_path / "trace")
    with jax.profiler.trace(tdir):
        with jax.profiler.TraceAnnotation(CALLER):
            fn()
    [path] = glob.glob(os.path.join(tdir, "plugins", "profile", "*",
                                    "*.xplane.pb"))
    lines = [[(e.name, e.start_ns, e.start_ns + e.duration_ns)
              for e in line.events]
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:") for line in plane.lines]
    mine = [i for i, ev in enumerate(lines)
            if any(e[0] == CALLER for e in ev)]
    assert len(mine) == 1, "the caller's annotation lies on one line"
    events = sorted(lines[mine[0]], key=lambda e: e[1])
    [caller] = [e for e in events if e[0] == CALLER]
    spans = [e for e in events if e[0].startswith("repro.")]
    # no repro.* span of the run lies on another line
    assert [e[0] for i, ev in enumerate(lines) if i != mine[0]
            for e in ev if e[0] in TOP] == []
    return caller, spans


def _check_nesting(caller, spans):
    for name, s, e in spans:
        assert caller[1] <= s and e <= caller[2], name
    top = [sp for sp in spans if sp[0] in TOP]
    for (_, _, end), (name, start, _) in zip(top, top[1:]):
        assert end <= start, f"{name} overlaps the span before it"


def test_solo_scan_run_spans_each_host_layer_once(tmp_path):
    cfg = FLConfig(engine="scan", selector="fedavg", **TINY)
    caller, spans = _traced(tmp_path, lambda: run_federated(cfg))
    assert Counter(n for n, _, _ in spans) == {
        "repro.setup": 1, "repro.operands": 1, "repro.scan": 1,
        "repro.results": 1}
    assert [n for n, _, _ in spans] == ["repro.setup", "repro.operands",
                                        "repro.scan", "repro.results"]
    _check_nesting(caller, spans)


def test_grid_spans_setup_per_cell_and_layers_per_dispatch(tmp_path):
    base = FLConfig(engine="scan", selector="fedavg", **TINY)
    gspec = GridSpec.product(base, selectors=["fedavg"], seeds=[0, 1])
    caller, spans = _traced(tmp_path, lambda: run_grid(gspec))
    assert [n for n, _, _ in spans] == [
        "repro.setup", "repro.setup", "repro.operands", "repro.segment",
        "repro.results"]
    _check_nesting(caller, spans)


def test_gather_scope_inside_train_in_compiled_round_step():
    model = make_classifier("mnist")
    ccfg = ClientConfig(epochs=1, batches_per_epoch=1, batch_size=4)
    params = model.init(jax.random.key(0))
    n, m, cap = 6, 2, 8
    step = jitted_round_step(model, ccfg, RoundSpec())
    text = step.lower(
        params, jnp.zeros((n, cap, 784)), jnp.zeros((n, cap), jnp.int32),
        jnp.full((n,), cap, jnp.int32), jnp.zeros((n,)),
        jnp.zeros((10, 784)), jnp.zeros((10,), jnp.int32),
        jnp.arange(m, dtype=jnp.int32), jnp.ones((m,), jnp.int32),
        jax.random.key(1), jnp.zeros((m,), jnp.int32)).compile().as_text()
    gathers = [line for line in text.splitlines()
               if re.search(r"=\s*\S+\s+gather\(", line)]
    scoped = [line for line in gathers
              if "repro.train/repro.gather/" in line]
    # the four cohort_take calls (xs, ys, n_valid, sigma) gather by `sel`
    assert len(scoped) >= 4, gathers
