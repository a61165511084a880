"""AOT cost probes and sharding helpers for the installed jax (0.9).

Everything that reads an AOT-compiled executable's cost or memory
analysis goes through here, and `named_shardings` turns a pytree of
PartitionSpecs into `NamedSharding`s for `jax.jit`.  Mesh contexts use
`jax.set_mesh` directly.
"""
from __future__ import annotations

from typing import Any

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

PyTree = Any


def cost_analysis_of(compiled) -> dict:
    """`cost_analysis()` of an AOT-compiled executable as a dict with
    whatever of `flops` / `bytes_accessed` the backend reports (keys
    absent when unavailable or NaN)."""
    cost = compiled.cost_analysis() or {}
    out: dict = {}
    for key, name in (("flops", "flops"),
                      ("bytes accessed", "bytes_accessed")):
        v = cost.get(key)
        if v is not None and v == v:
            out[name] = float(v)
    return out


def memory_stats_of(compiled):
    """`memory_analysis()` of an AOT-compiled executable: byte counts
    plus a derived `peak_bytes` = temp + argument + output − aliased, or
    None when the backend exposes no analysis."""
    mem = compiled.memory_analysis()
    if mem is None:
        return None
    sizes = {f"{name}_bytes": int(getattr(mem, f"{name}_size_in_bytes"))
             for name in ("temp", "argument", "output", "alias",
                          "generated_code")}
    peak = (sizes["temp_bytes"] + sizes["argument_bytes"]
            + sizes["output_bytes"] - sizes["alias_bytes"])
    sizes["peak_bytes"] = max(peak, 0)
    return sizes


def aot_compile(jitted, *args, **kwargs):
    """`jitted.lower(*args).compile()`, None on failure.  Array arguments
    are reduced to their avals first, so the probe works on donated/
    deleted buffers and never touches data."""
    def aval(a):
        if hasattr(a, "shape") and hasattr(a, "dtype"):
            return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                        sharding=getattr(a, "sharding",
                                                         None))
        return a

    try:
        args = jax.tree.map(aval, args)
        kwargs = jax.tree.map(aval, kwargs)
        return jitted.lower(*args, **kwargs).compile()
    except Exception:
        return None


def compiled_flops(jitted, *args, **kwargs) -> float:
    """Best-effort compiled-cost probe: the flops `jitted` would execute
    for these args, NaN when unavailable.  Costs a fresh lower+compile —
    callers gate it behind an explicit stats flag (or use the cached
    cost cards in repro.telemetry.profile)."""
    compiled = aot_compile(jitted, *args, **kwargs)
    if compiled is None:
        return float("nan")
    return cost_analysis_of(compiled).get("flops", float("nan"))


def compiled_memory_stats(jitted, *args, **kwargs):
    """Best-effort compiled peak-memory probe, mirroring `compiled_flops`:
    the XLA `memory_analysis()` byte counts (with derived `peak_bytes`),
    or None when unavailable.  Fresh lower+compile, like the flops probe."""
    compiled = aot_compile(jitted, *args, **kwargs)
    if compiled is None:
        return None
    return memory_stats_of(compiled)


def named_shardings(mesh, specs: PyTree) -> PyTree:
    """Normalise a pytree of PartitionSpec / None / Sharding leaves into
    `NamedSharding`s on `mesh` (None -> fully replicated), so `jax.jit`
    gets concrete shardings with or without an ambient mesh.
    """
    def conv(s):
        if s is None:
            s = P()
        if isinstance(s, jax.sharding.Sharding):
            return s
        return NamedSharding(mesh, s)

    return jax.tree.map(conv, specs,
                        is_leaf=lambda s: s is None or isinstance(s, P))
