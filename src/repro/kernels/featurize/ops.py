"""jit dispatch for the fused KPM-window featurize stage."""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels.featurize.kernel import featurize
from repro.kernels.featurize.ref import featurize_ref


@partial(jax.jit, static_argnames=("window", "use_kernel", "interpret"))
def kpm_feature_windows(kpms, center, scale, window: int, *,
                        use_kernel: bool = True,
                        interpret: bool | None = None):
    """(N, L, K) raw KPM slab -> (N, L - window + 1, window, K) normalized
    rolling windows, entirely on device.

    Drop-in for the ``EpisodeBatch.kpm_windows(normalize=True)`` host path
    over any trace slab: the engine's chunked ``estimate_fleet`` feeds the
    slab covering one chunk of report periods and reshapes the result into
    estimator rows. ``use_kernel=False`` runs the jnp oracle (one fused
    gather + affine — also what GSPMD shards under a mesh)."""
    if use_kernel:
        return featurize(kpms, center, scale, window, interpret=interpret)
    return featurize_ref(kpms, center, scale, window)
