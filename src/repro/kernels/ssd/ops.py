"""jit wrappers for the SSD kernel and its single-step recurrence."""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels.ssd.kernel import ssd
from repro.kernels.ssd.ref import ssd_ref, ssd_step_ref


@partial(jax.jit, static_argnames=("chunk", "use_kernel", "interpret"))
def ssd_mixer(x, dt, A, Bm, Cm, *, chunk=256, use_kernel=True,
              interpret=None):
    if use_kernel:
        return ssd(x, dt, A, Bm, Cm, chunk=chunk, interpret=interpret)
    return ssd_ref(x, dt, A, Bm, Cm, chunk)


@jax.jit
def ssd_step(x_t, dt_t, A, B_t, C_t, state):
    """One O(1) SSD recurrence step (see ``ssd_step_ref``).

    Pure jnp — a single step has no tile structure worth a Pallas kernel,
    and keeping it GSPMD-partitionable is what lets the recurrent
    estimator's per-report ingest shard over a serving mesh
    (``pallas_call`` cannot be partitioned; the chunked ``ssd`` kernel is
    for offline/sequence passes)."""
    return ssd_step_ref(x_t, dt_t, A, B_t, C_t, state)
