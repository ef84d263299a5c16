"""Masked batched segment reduction (sum / max) as a Pallas kernel.

The multi-cell fleet leans on tiny-segment reductions in two hot places:
the gNB PRB scheduler normalizers (``sim.sched.cell_shares`` and the
max-C/I winner pick, every report period inside the engine's scan) and
the (C, T) per-cell offered-load aggregation behind the inter-cell
interference coupling (``sim.cells.cell_load``). XLA lowers
``segment_sum`` to scatter-adds; here the reduction runs as a one-hot
compare-and-reduce over VMEM tiles — no scatter, and the C axis (cells,
typically < 64) stays resident.

Out-of-range segment ids contribute nothing, which is the whole masking
story: masked rows (empty pool slots) are redirected to segment id
``n_segments`` by the ops wrapper and fall out of the one-hot compare.

Grid: (batch tiles, element tiles); the element axis is innermost and
accumulates into a (block_t, C) scratch across its tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

F32 = jnp.float32
I32 = jnp.int32

NEG_INF = float("-inf")


def _segreduce_kernel(v_ref, g_ref, o_ref, acc_ref, *, n_segments, nk, op):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.full_like(
            acc_ref, 0.0 if op == "sum" else NEG_INF)

    v = v_ref[...].astype(F32)[:, :, None]  # (bt, bn, 1)
    # broadcasted_iota: a 1-D iota does not lower on TPU
    seg = jax.lax.broadcasted_iota(I32, (1, 1, n_segments), 2)
    hit = g_ref[...][:, :, None] == seg  # (bt, bn, C) one-hot
    if op == "sum":
        acc_ref[...] += jnp.sum(jnp.where(hit, v, 0.0), axis=1)
    else:
        acc_ref[...] = jnp.maximum(
            acc_ref[...], jnp.max(jnp.where(hit, v, NEG_INF), axis=1))

    @pl.when(j == nk - 1)
    def _finish():
        o_ref[...] = acc_ref[...]


def segment_reduce_batched(values, seg_ids, n_segments: int, *,
                           op: str = "sum", block_t: int = 8,
                           block_n: int = 512, interpret: bool | None = None):
    """values (T, N) f32 + seg_ids (T, N) i32 -> (T, C) reductions.

    ``op``: "sum" or "max". Segment ids outside ``[0, n_segments)`` are
    ignored; an empty segment reduces to the identity (0 for sum, -inf
    for max — matching ``jax.ops.segment_{sum,max}``)."""
    if op not in ("sum", "max"):
        raise ValueError(f"op must be 'sum' or 'max': {op!r}")
    t, n = values.shape
    bt, bn = min(block_t, t), min(block_n, n)
    pt, pn = (-t) % bt, (-n) % bn
    if pt or pn:
        values = jnp.pad(values, ((0, pt), (0, pn)))
        # padded ids hit no segment of [0, C)
        seg_ids = jnp.pad(seg_ids, ((0, pt), (0, pn)),
                          constant_values=n_segments)
    tp, npad = t + pt, n + pn
    nk = npad // bn
    out = pl.pallas_call(
        functools.partial(_segreduce_kernel, n_segments=n_segments, nk=nk,
                          op=op),
        grid=(tp // bt, nk),
        in_specs=[
            pl.BlockSpec((bt, bn), lambda i, j: (i, j)),
            pl.BlockSpec((bt, bn), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((bt, n_segments), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((tp, n_segments), F32),
        scratch_shapes=[pltpu.VMEM((bt, n_segments), F32)],
        interpret=resolve_interpret(interpret),
    )(values.astype(F32), seg_ids.astype(I32))
    return out[:t]
