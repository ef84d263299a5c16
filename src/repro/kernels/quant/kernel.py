"""Rowwise symmetric int8 quantisation as a Pallas kernel.

Used at the split boundary (core/boundary codec), for compressed gradient
all-reduce (optim/compress), the int8 KV-cache option, and the int8
estimator weights (``estimator.serve``), whose CNN fc rows are 78,624 wide.

Rows are tiled over columns too, so a row of any width fits scoped VMEM.
Grid: (row blocks, pass, column blocks). Pass 0 folds each column tile's
absmax into a (block_rows, 1) VMEM scratch; pass 1 re-reads the tiles and
writes the quantized values. The q output's block index stays at column 0
through pass 0, so nothing is written back before pass 1 fills it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

F32 = jnp.float32


def _quant_kernel(x_ref, q_ref, s_ref, amax_ref, *, qmax):
    p, c = pl.program_id(1), pl.program_id(2)
    x = x_ref[...].astype(F32)

    @pl.when((p == 0) & (c == 0))
    def _init():
        amax_ref[...] = jnp.zeros_like(amax_ref)

    @pl.when(p == 0)
    def _absmax():
        amax_ref[...] = jnp.maximum(
            amax_ref[...], jnp.max(jnp.abs(x), axis=-1, keepdims=True))

    @pl.when(p == 1)
    def _quantize():
        # explicit reciprocal multiply: XLA rewrites `amax / const` that way
        # in some fusion contexts but not others; writing it out keeps the
        # kernel and the jnp oracle bit-identical (a 1-ULP scale skew flips
        # round())
        scale = jnp.maximum(amax_ref[...], 1e-8) * jnp.float32(1.0 / qmax)
        q = jnp.clip(jnp.round(x / scale), -qmax - 1, qmax)
        q_ref[...] = q.astype(jnp.int8)
        s_ref[...] = scale


def quantize(x, *, qmax: int = 127, block_rows: int = 256,
             block_cols: int = 2048, interpret: bool | None = None):
    """x: (n, d) -> (q int8 (n, d), scale f32 (n, 1)).

    Zero padding is exact: a zero column never raises a row's absmax, and
    padded rows and columns are sliced off."""
    n, d = x.shape
    bm, bk = min(block_rows, n), min(block_cols, d)
    pm, pk = (-n) % bm, (-d) % bk
    if pm or pk:
        x = jnp.pad(x, ((0, pm), (0, pk)))
    npad, dpad = x.shape
    kernel = functools.partial(_quant_kernel, qmax=qmax)
    q, s = pl.pallas_call(
        kernel,
        grid=(npad // bm, 2, dpad // bk),
        in_specs=[pl.BlockSpec((bm, bk), lambda i, p, c: (i, c))],
        out_specs=[
            pl.BlockSpec((bm, bk), lambda i, p, c: (i, c * p)),
            pl.BlockSpec((bm, 1), lambda i, p, c: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((npad, dpad), jnp.int8),
            jax.ShapeDtypeStruct((npad, 1), F32),
        ],
        scratch_shapes=[pltpu.VMEM((bm, 1), F32)],
        interpret=resolve_interpret(interpret),
    )(x)
    return q[:n, :d], s[:n]
