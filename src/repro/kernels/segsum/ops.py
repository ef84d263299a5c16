"""jit dispatch for masked segment reductions.

``segment_reduce`` is the shared server for both PRB scheduler
normalizers (``sim.sched``) and the per-cell load aggregation
(``sim.cells``): it accepts 1-D or batched 2-D inputs, folds an optional
activity mask into the out-of-range-id redirect (the same dummy-segment
idiom ``scheduler_step`` uses), and dispatches kernel vs jnp oracle.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.segsum.kernel import segment_reduce_batched
from repro.kernels.segsum.ref import segment_reduce_ref


@partial(jax.jit,
         static_argnames=("n_segments", "op", "use_kernel", "interpret"))
def segment_reduce(values, seg_ids, n_segments: int, *, op: str = "sum",
                   mask=None, use_kernel: bool = True,
                   interpret: bool | None = None):
    """Reduce ``values`` into ``n_segments`` buckets keyed by ``seg_ids``.

    Accepts (N,) or (T, N) inputs (``seg_ids`` broadcasts against
    ``values``). ``mask=False`` rows are redirected to segment id
    ``n_segments`` and so contribute nothing. Empty segments reduce to
    the op identity (0 for sum, -inf for max), matching
    ``jax.ops.segment_{sum,max}``."""
    squeeze = values.ndim == 1
    v = values[None] if squeeze else values
    g = jnp.broadcast_to(jnp.asarray(seg_ids, jnp.int32), v.shape)
    if mask is not None:
        m = jnp.broadcast_to(jnp.asarray(mask, bool), v.shape)
        g = jnp.where(m, g, n_segments)
    if use_kernel:
        out = segment_reduce_batched(v, g, n_segments, op=op,
                                     interpret=interpret)
    else:
        # one spill bucket so dummy-redirected ids (== n_segments) stay
        # in range for the jnp scatter path, then drop it
        out = segment_reduce_ref(v, g, n_segments + 1,
                                 op=op)[:, :n_segments]
    return out[0] if squeeze else out
