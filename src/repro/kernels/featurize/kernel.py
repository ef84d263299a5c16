"""Fused KPM-window featurize + normalize as a Pallas kernel.

The fleet estimator consumes, per report period, each UE's rolling
(WINDOW, 15) KPM window. The host path materializes every window up
front (``EpisodeBatch.kpm_windows``: a numpy stride-trick view whose
``astype(float32)`` copy expands the (N, T + W, 15) trace ~WINDOWx), then
ships the copies to the device chunk by chunk. This kernel fuses the
whole featurize stage on device: one pass over a raw KPM slab normalizes
(the fixed affine of ``channel.kpm``) and scatters the overlapping
windows straight into VMEM-tiled output blocks — the trace crosses the
host->device boundary once, at 1/WINDOW the bytes.

Grid: (row blocks, window blocks). Every grid step sees the full trace
axis (the overlapping windows make block-aligned input tiling impossible)
and loads each of its windows straight from the input ref at a dynamic
start (``pl.ds``): Mosaic lowers a ref slice, not a ``dynamic_slice`` of a
loaded value. The window axis is a static unroll of ``block_windows``
(window, K) copies, each stored whole into the output block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

F32 = jnp.float32


def _featurize_kernel(x_ref, c_ref, s_ref, o_ref, *, block_b, window):
    j = pl.program_id(1)
    for b in range(block_b):  # static unroll: one window per copy
        x = x_ref[:, pl.ds(j * block_b + b, window), :].astype(F32)
        # the division (not a reciprocal multiply) mirrors
        # channel.kpm.normalize_kpms so kernel, oracle and host path agree
        # to f32 rounding
        o_ref[:, b, :, :] = (x - c_ref[...]) / s_ref[...]


def featurize(kpms, center, scale, window: int, *, block_rows: int = 16,
              block_windows: int = 8, interpret: bool | None = None):
    """kpms (N, L, K) raw -> (N, B, window, K) normalized windows, where
    ``B = L - window + 1`` and window ``b`` covers trace steps
    ``[b, b + window)`` — the ``EpisodeBatch.kpm_windows`` convention.

    The default blocks keep the output tile — (window, K) pads to
    (32, 128) f32 in VMEM — at 2 MiB, well inside scoped VMEM."""
    n, length, k = kpms.shape
    b = length - window + 1
    if b < 1:
        raise ValueError(f"trace of {length} steps holds no {window}-window")
    bn = min(block_rows, n)
    bb = min(block_windows, b)
    pad_n, pad_b = (-n) % bn, (-b) % bb
    if pad_n or pad_b:  # pad rows + trace tail; padded windows are sliced off
        kpms = jnp.pad(kpms, ((0, pad_n), (0, pad_b), (0, 0)))
    npad, bpad = n + pad_n, b + pad_b
    kernel = functools.partial(_featurize_kernel, block_b=bb, window=window)
    out = pl.pallas_call(
        kernel,
        grid=(npad // bn, bpad // bb),
        in_specs=[
            # full trace axis per step: the windows overlap, so their
            # source range is not block-alignable — each step slices its
            # own span out of the shared slab
            pl.BlockSpec((bn, length + pad_b, k), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, k), lambda i, j: (0, 0)),
            pl.BlockSpec((1, k), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bn, bb, window, k),
                               lambda i, j: (i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((npad, bpad, window, k), F32),
        interpret=resolve_interpret(interpret),
    )(kpms, jnp.asarray(center, F32).reshape(1, k),
      jnp.asarray(scale, F32).reshape(1, k))
    return out[:n, :b]
