"""Sparse cohort gather: selected-client rows out of (sharded) stacks.

The selection engines produce a global (M,) id vector each round; this
package turns it into the cohort's rows without materialising anything
O(N) beyond the (sharded) client stacks themselves.  `ops.py` holds the
dense path, `jnp.take` on the stack's stored (N, cap, F) shape (XLA
gathers the M rows; nothing touches the whole table), and the
cross-shard masked-gather + psum path for client-axis-sharded stacks
(DESIGN.md §16).  Both copy bits, so both are bitwise `jnp.take`.
"""
from repro.kernels.cohort_gather.ops import cohort_gather, cohort_take

__all__ = ["cohort_gather", "cohort_take"]
