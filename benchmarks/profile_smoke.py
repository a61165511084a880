"""Profile smoke: cost cards + capture window on real engine runs (§17).

    PYTHONPATH=src python -m benchmarks.profile_smoke  (or `make profile-smoke`)

Drives one tiny telemetry-on scan run and one tiny segmented grid with a
profiler capture window open, then asserts the §17 observability contract
end-to-end:

  * every `compile` event in both streams carries a populated cost card
    (flops, bytes accessed, per-device peak bytes, roofline terms);
  * the `profile` event reports a real capture (`captured=True`) with
    per-stage wall seconds recovered from the capture's own trace, the
    set-up span among them;
  * both streams schema-validate.

Exit nonzero on any violation — `CHECK_PROFILE=1 scripts/check.sh` turns
this into a gate.  No BENCH artifact: this is a contract smoke, not a
timing bench (BENCH_telemetry.json owns the overhead numbers).
"""
from __future__ import annotations

import os
import sys
import tempfile

import jax

from repro.federated.client import ClientConfig
from repro.federated.server import FLConfig, run_federated
from repro.grid import GridSpec, run_grid
from repro.telemetry import Telemetry, validate_events

TINY = dict(n_clients=8, m=3, rounds=4, n_train=400, n_val=80, n_test=80,
            eval_every=2,
            client=ClientConfig(epochs=1, batches_per_epoch=2,
                                batch_size=16))

CARD_KEYS = ("flops", "bytes_accessed", "peak_bytes",
             "intensity_flops_per_byte")


def _check_cards(events, who: str) -> list[str]:
    errors = []
    compiles = [e for e in events if e["event"] == "compile"]
    if not compiles:
        errors.append(f"{who}: no compile events in stream")
    for ev in compiles:
        card = ev.get("cost_card")
        if not card:
            errors.append(f"{who}: compile event {ev.get('program')!r} "
                          "has no cost card")
            continue
        # roofline terms exist only against a chip's published peaks
        keys = CARD_KEYS + (("roofline",) if jax.devices()[0].platform
                            != "cpu" else ())
        missing = [k for k in keys if card.get(k) is None]
        if missing:
            errors.append(f"{who}: {ev.get('program')!r} card missing "
                          f"{missing}")
    profiles = [e for e in events if e["event"] == "profile"]
    if not profiles:
        errors.append(f"{who}: no profile event (capture window absent)")
    for ev in profiles:
        if not ev.get("captured"):
            errors.append(f"{who}: the profiler did not capture")
        if not ev.get("stage_wall_s", {}).get("setup"):
            errors.append(f"{who}: profile event has no set-up wall")
    return errors


def main() -> int:
    errors: list[str] = []
    with tempfile.TemporaryDirectory() as td:
        print("== scan run (telemetry + capture window) ==")
        cfg = FLConfig(engine="scan", selector="greedyfed", **TINY)
        tel = Telemetry(trace_dir=os.path.join(td, "scan"),
                        heartbeat_every_s=1e9)
        run_federated(cfg, telemetry=tel)
        validate_events(tel.events)
        errors += _check_cards(tel.events, "scan")

        print("== segmented grid (telemetry + capture window) ==")
        base = FLConfig(engine="scan", selector="greedyfed", **TINY)
        gspec = GridSpec.product(base, selectors=["greedyfed", "fedavg"],
                                 seeds=[0])
        gtel = Telemetry(trace_dir=os.path.join(td, "grid"),
                        heartbeat_every_s=1e9)
        run_grid(gspec, rounds_per_segment=2, telemetry=gtel)
        validate_events(gtel.events)
        errors += _check_cards(gtel.events, "grid")

        for tel_, who in ((tel, "scan"), (gtel, "grid")):
            for ev in tel_.events:
                if ev["event"] == "compile" and ev.get("cost_card"):
                    c = ev["cost_card"]
                    print(f"  {who}:{ev['program']}: "
                          f"{c['flops']:.3g} flops, "
                          f"{c['bytes_accessed']:.3g} B accessed, "
                          f"peak {c['peak_bytes'] / 1e6:.1f} MB/dev, "
                          f"{c['intensity_flops_per_byte']:.2f} flops/B"
                          + (f" ({c['roofline']['dominant']}-bound)"
                             if "roofline" in c else ""))
                elif ev["event"] == "profile":
                    walls = ", ".join(f"{k}={v:.2f}s" for k, v in
                                      sorted(ev["stage_wall_s"].items()))
                    print(f"  {who}:profile captured={ev['captured']} "
                          f"[{walls}]")

    if errors:
        print("\nPROFILE SMOKE FAILED:", file=sys.stderr)
        for e in errors:
            print(f"  - {e}", file=sys.stderr)
        return 1
    print("profile smoke OK: every compile event carries a cost card; "
          "capture window recovered stage walls")
    return 0


if __name__ == "__main__":
    sys.exit(main())
