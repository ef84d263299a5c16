"""jit wrapper: dCor with the Pallas pairwise-distance kernel."""
from __future__ import annotations

from functools import partial

import jax

from repro.core.privacy import dcor as _dcor
from repro.kernels.dcor.kernel import pairwise_dists


@partial(jax.jit, static_argnames=("interpret",))
def dcor_kernel(x, y, *, interpret: bool | None = None):
    fn = partial(pairwise_dists, interpret=interpret)
    return _dcor(x, y, dist_fn=fn)
