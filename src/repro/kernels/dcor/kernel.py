"""Pairwise Euclidean distances as a Pallas kernel — the O(n^2 d) inner loop
of distance correlation (privacy metric, Sec. V).

Grid (ni, nj, nd): (block_n, block_d) tiles of rows i and j are streamed
through VMEM; squared distances accumulate in an fp32 scratch across the
feature-chunk axis (innermost, sequential), and the sqrt happens on the
final chunk. Feature dim never materialises in full — this is what lets
dCor run over multi-megabyte activations on TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

F32 = jnp.float32


def _dist_kernel(xi_ref, xj_ref, o_ref, acc_ref, *, n_d):
    kd = pl.program_id(2)
    bi, bj = pl.program_id(0), pl.program_id(1)

    @pl.when(kd == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    xi = xi_ref[...].astype(F32)  # (bn, bd)
    xj = xj_ref[...].astype(F32)
    si = jnp.sum(xi * xi, axis=1)
    sj = jnp.sum(xj * xj, axis=1)
    cross = jax.lax.dot_general(xi, xj, (((1,), (1,)), ((), ())),
                                preferred_element_type=F32)
    acc_ref[...] += si[:, None] + sj[None, :] - 2.0 * cross

    @pl.when(kd == n_d - 1)
    def _finish():
        # pin self-distances to exact 0: the squared-norm expansion cancels
        # catastrophically on the diagonal and sqrt amplifies the residue
        bn = acc_ref.shape[0]
        rows = bi * bn + jax.lax.broadcasted_iota(jnp.int32, (bn, bn), 0)
        cols = bj * bn + jax.lax.broadcasted_iota(jnp.int32, (bn, bn), 1)
        d2 = jnp.where(rows == cols, 0.0, jnp.maximum(acc_ref[...], 0.0))
        o_ref[...] = jnp.sqrt(d2).astype(o_ref.dtype)


def pairwise_dists(x, *, block_n: int = 128, block_d: int = 512,
                   interpret: bool | None = None):
    n, d = x.shape
    block_n = min(block_n, n)
    block_d = min(block_d, d)
    pad_n = (-n) % block_n
    pad_d = (-d) % block_d
    if pad_n or pad_d:
        x = jnp.pad(x, ((0, pad_n), (0, pad_d)))
    np_, dp = x.shape
    grid = (np_ // block_n, np_ // block_n, dp // block_d)
    kernel = functools.partial(_dist_kernel, n_d=grid[2])
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, block_d), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_n, block_d), lambda i, j, k: (j, k)),
        ],
        out_specs=pl.BlockSpec((block_n, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((np_, np_), F32),
        scratch_shapes=[pltpu.VMEM((block_n, block_n), F32)],
        interpret=resolve_interpret(interpret),
    )(x, x)
    return out[:n, :n]
