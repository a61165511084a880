"""The one generator of runs: a configuration, a traffic mix and a seed
make the calls a cell's window drives.

A configuration (`configs/<name>.json`) holds the protocol's sizes under
`fl` and the data's under `data`; a traffic mix (`traffic/<name>.json`)
holds the runner (`solo`: one `run_federated` call a run; `grid`: one
`run_grid` call of every run), the program seeds of the runs that make
one pass of the window, optionally a fixed `data_seed`, and `fl` keys
that override the configuration's (the selector, the upload codec, any
other `FLConfig` field).  Keys the system's `FLConfig` does not have
(`walks_per_client`, `topk_frac`, and the model's and optimizer's sizes,
which the system fixes per dataset) are checked against what the
system runs, never passed.

The runs of a pass are the traffic's own list, as a user's seed sweep
names its seeds: each with the partition, client sizes and padded
client stacks that its seed draws, so every run of a cell does the same
work.  `--seed` orders them, and makes the data where the traffic fixes
no `data_seed`.
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple

import numpy as np

# configuration keys that are facts about the protocol, checked against
# the system instead of passed to FLConfig
PROTOCOL_ONLY = ("walks_per_client", "topk_frac", "epochs",
                 "batches_per_epoch", "batch_size", "lr", "momentum")


class Plan(NamedTuple):
    runner: str
    data: Any                 # SynthDataset shared by every run
    cfgs: list                # FLConfig per run of one pass, in call order
    rounds: int               # rounds of one run

    @property
    def runs(self) -> int:
        return len(self.cfgs)

    def groups(self) -> list:
        """The runs of each call of one pass."""
        if self.runner == "solo":
            return [[c] for c in self.cfgs]
        if self.runner == "grid":
            return [list(self.cfgs)]
        raise ValueError(f"unknown runner {self.runner!r}")

    def call(self, group: list, telemetry=None) -> list:
        """One whole call of the cell's entry over the runs of `group`;
        FLResult per run, with every output on the device finished."""
        import jax

        if self.runner == "solo":
            from repro.federated.server import run_federated
            results = [run_federated(group[0], data=self.data,
                                     telemetry=telemetry)]
        else:
            from repro.grid import run_grid
            from repro.grid.spec import GridCell, GridSpec
            spec = GridSpec(group[0], tuple(
                GridCell(c.selector, c.seed) for c in group))
            results = list(run_grid(spec, data=self.data,
                                    isolate_cells=False, retries=0,
                                    telemetry=telemetry).results)
        jax.block_until_ready([r.params for r in results])
        return results


def merged_fl(config: dict, traffic: dict) -> dict:
    return {**config["fl"], **traffic.get("fl", {})}


def make_plan(config: dict, traffic: dict, seed: int) -> Plan:
    from repro.data.synth import make_dataset
    from repro.federated.client import ClientConfig
    from repro.federated.server import FLConfig

    fl = merged_fl(config, traffic)
    client = ClientConfig(epochs=fl["epochs"],
                          batches_per_epoch=fl["batches_per_epoch"],
                          batch_size=fl["batch_size"], lr=fl["lr"],
                          momentum=fl["momentum"])
    names = {f.name for f in dataclasses.fields(FLConfig)}
    passed = {k: v for k, v in fl.items() if k not in PROTOCOL_ONLY}
    unknown = sorted(set(passed) - names)
    if unknown:
        raise ValueError(f"FLConfig has no field(s) {unknown}")
    passed["shapley_max_iters"] = fl["walks_per_client"] * fl["m"]
    data = make_dataset(config["model"]["dataset"], n_train=fl["n_train"],
                        n_val=fl["n_val"], n_test=fl["n_test"],
                        difficulty=config["data"]["difficulty"],
                        seed=traffic.get("data_seed", seed))
    order = np.random.default_rng([seed, 0x0DE4]).permutation(
        len(traffic["program_seeds"]))
    cfgs = [FLConfig(dataset=config["model"]["dataset"], engine="scan",
                     client=client, seed=int(traffic["program_seeds"][i]),
                     **passed) for i in order]
    return Plan(traffic["runner"], data, cfgs, fl["rounds"])
