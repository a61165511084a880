# OPTIONAL layer. Add <name>.py (or .cu) + ops.py + ref.py ONLY
# for compute hot-spots the paper itself optimizes with a custom
# kernel. Leave this package empty if the paper has none.
import jax
import jax.numpy as jnp


def pad_to(x, mult: int):
    """Zero-pad the last axis of a (M, D) matrix view up to a multiple of
    the kernel tile (shared by the ops wrappers; padding is sliced off
    after the kernel runs)."""
    pad = (-x.shape[-1]) % mult
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)))
    return x


def default_interpret() -> bool:
    """Backend-derived default for the kernels' `interpret` knob.

    On TPU every kernel is compiled natively by Mosaic — never
    interpreted, and never swapped for its reference after a failure: a
    kernel the chip's compiler refuses fails the trace
    (tests/test_tpu_compile.py compiles each one for a v5e at the MNIST
    MLP's widths).  Everywhere else the interpreter IS the only way to
    run them.  ops wrappers resolve `interpret=None` through this at
    trace time.
    """
    return jax.default_backend() != "tpu"
