"""Roofline analysis from compiled dry-run artifacts.

Per-chip peaks come from `PEAKS`, keyed by `device.device_kind`; a kind
that is not in the table is an error, never a default.  The dry-run
roofline projects onto the production target, a TPU v5e:

    compute    = FLOPs_per_device / peak bf16 FLOP/s
    memory     = bytes_per_device / HBM bytes/s
    collective = collective_traffic_per_device / ICI bytes/s per link

Methodology notes:
  * ``compiled.cost_analysis()`` runs on the post-SPMD per-device module, so
    its flops/bytes are already per-device.
  * XLA's HloCostAnalysis counts a while-loop body ONCE, ignoring the trip
    count — a scanned L-layer model would under-report by ~L.  We therefore
    ASSEMBLE the roofline from two python-unrolled compiles with 1 and 2
    layers (scan_layers=False):
        layer_cost    = cost(L=2) - cost(L=1)
        embed_head    = cost(L=1) - layer_cost
        total         = embed_head + n_layers * layer_cost
    (whisper's encoder scales with the same trick: both 1/2-layer models
    carry one/two encoder layers, and encoder_layers == n_layers.)
  * Collective traffic: parse the per-device HLO text, sum result-shape
    bytes of all-reduce/all-gather/reduce-scatter/all-to-all/
    collective-permute ops (all-reduce weighted 2x for the ring's
    reduce-scatter + all-gather phases).
"""
from __future__ import annotations

import dataclasses
import re
from typing import NamedTuple

import jax
import numpy as np


class Peaks(NamedTuple):
    flops: float        # peak bf16 FLOP/s per chip
    hbm_bw: float       # HBM bytes/s per chip
    ici_bw: float       # inter-chip bytes/s per link


# Source: Google Cloud documentation, "TPU v5e" (system architecture):
# 197 TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s of ICI per chip over four
# links (50 GB/s per link).  JAX reports a v5e as "TPU v5 lite".
V5E = "TPU v5 lite"
PEAKS = {V5E: Peaks(flops=197e12, hbm_bw=819e9, ici_bw=50e9)}


def peaks_for(device_kind: str) -> Peaks:
    """Published peaks of one chip of `device_kind`; KeyError if unknown."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _line_result_bytes(lhs: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(lhs):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def collective_bytes_from_text(hlo: str) -> dict:
    """Per-collective-kind result bytes summed over the per-device module.

    NOTE: ops inside while bodies are counted once (see module docstring) —
    use the assembled numbers for scanned models.
    """
    out = {k: 0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    for line in hlo.splitlines():
        if "=" not in line:
            continue
        _, _, rhs = line.partition("=")
        rhs = rhs.strip()
        # op token appears right before '(' e.g. "bf16[128]{0} all-reduce(..."
        m = re.search(r"([\w-]+)\(", rhs)
        if not m:
            continue
        op = m.group(1)
        if op.endswith("-done"):
            continue  # async pair: bytes already counted at the -start op
        base = op[:-6] if op.endswith("-start") else op
        if base in _COLLECTIVES:
            out[base] += _line_result_bytes(rhs[: m.start()])
            counts[base] += 1
    total = sum(out.values()) + out["all-reduce"]  # all-reduce counts 2x
    return {"by_kind": out, "counts": counts, "weighted_total": total}


def _cost_of(fn, args, in_s, out_s) -> dict:
    lowered = jax.jit(fn, in_shardings=in_s, out_shardings=out_s).lower(*args)
    compiled = lowered.compile()
    cost = compiled.cost_analysis()
    coll = collective_bytes_from_text(compiled.as_text())
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "collective_bytes": float(coll["weighted_total"]),
        "collective_by_kind": coll["by_kind"],
    }


def assembled_roofline(cfg, shape, mesh) -> dict:
    """Per-device FLOPs/bytes/collective totals via 1/2-layer differencing."""
    from repro.launch.dryrun import build_step  # circular-safe at call time

    def cost_with_layers(n: int) -> dict:
        enc = min(cfg.encoder_layers, n) if cfg.encoder_layers else 0
        c = dataclasses.replace(cfg, n_layers=n, encoder_layers=enc,
                                scan_layers=False, remat=False)
        fn, args, in_s, out_s = build_step(c, shape, mesh)
        return _cost_of(fn, args, in_s, out_s)

    c1 = cost_with_layers(1)
    c2 = cost_with_layers(2)
    L = cfg.n_layers

    def assemble(key):
        layer = max(c2[key] - c1[key], 0.0)
        stem = max(c1[key] - layer, 0.0)
        return stem + L * layer, layer, stem

    flops, flops_layer, flops_stem = assemble("flops")
    bytes_, bytes_layer, bytes_stem = assemble("bytes")
    coll, coll_layer, coll_stem = assemble("collective_bytes")
    return {
        "per_device_flops": flops,
        "per_device_bytes": bytes_,
        "per_device_collective_bytes": coll,
        "per_layer": {"flops": flops_layer, "bytes": bytes_layer,
                      "collective_bytes": coll_layer},
        "stem": {"flops": flops_stem, "bytes": bytes_stem,
                 "collective_bytes": coll_stem},
        "note": "remat disabled in assembly; training remat adds ~1 fwd of "
                "recompute per layer (see EXPERIMENTS.md)",
    }


def model_flops(cfg, shape) -> float:
    """6*N*D (train) / 2*N*D (inference) with N = active non-embedding params.

    Enc-dec (whisper): the encoder's params only see n_frontend_tokens
    frames, not the decoder's seq_len tokens — counted separately so the
    useful-FLOP ratio stays meaningful.
    """
    from repro.models.lm.config import (
        _attn_params, _ffn_params, active_param_count,
    )
    n = active_param_count(cfg) - cfg.vocab * cfg.d_model  # drop embed gather
    mult = {"train": 6.0, "prefill": 2.0, "decode": 2.0}[shape.kind]
    dec_tokens = shape.global_batch * (
        shape.seq_len if shape.kind != "decode" else 1)

    if not cfg.encoder_layers:
        return mult * n * dec_tokens

    enc_layer = 2 * cfg.d_model + _attn_params(cfg) + _ffn_params(cfg)
    n_enc = cfg.encoder_layers * enc_layer + cfg.d_model
    n_dec = n - n_enc
    enc_tokens = shape.global_batch * cfg.n_frontend_tokens
    # decode reuses the prefilled encoder output: encoder cost amortised away
    enc_mult = 0.0 if shape.kind == "decode" else mult
    return mult * n_dec * dec_tokens + enc_mult * n_enc * enc_tokens


def roofline_report(cfg, shape, rec: dict, *, n_devices: int) -> dict:
    asm = rec["assembled"]
    peak = PEAKS[V5E]          # the dry run's production target
    compute_t = asm["per_device_flops"] / peak.flops
    memory_t = asm["per_device_bytes"] / peak.hbm_bw
    coll_t = asm["per_device_collective_bytes"] / peak.ici_bw
    terms = {"compute_s": compute_t, "memory_s": memory_t,
             "collective_s": coll_t}
    dominant = max(terms, key=terms.get)
    mf = model_flops(cfg, shape)
    hlo_global = asm["per_device_flops"] * n_devices
    report = {
        **terms,
        "dominant": dominant,
        "model_flops_global": mf,
        "hlo_flops_global": hlo_global,
        "useful_flops_ratio": mf / hlo_global if hlo_global else 0.0,
        "step_time_lower_bound_s": max(terms.values()),
        "flops_util_at_bound": (
            asm["per_device_flops"] / peak.flops
            / max(max(terms.values()), 1e-12)),
    }
    return report
