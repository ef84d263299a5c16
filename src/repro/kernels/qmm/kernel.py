"""int8 x int8 -> int32 rowwise-scaled matmul as a Pallas kernel.

The int8 serving path's FC layers (estimator LSTM projection, CNN fc,
regression head): activations quantized rowwise per sample, weights
pre-quantized rowwise per output channel (both via the ``kernels/quant``
formula), the product accumulated on the MXU in int32 and scaled back to
f32 on the final K tile. Integer accumulation is associative, so the
tiled kernel and the one-shot jnp oracle agree *exactly* — the kernel-
vs-ref pin is ``assert_array_equal``, not allclose.

Grid: (M tiles, N tiles, K tiles); K innermost (sequential on TPU) with
the int32 accumulator living in VMEM scratch across K steps.
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

_CONTRACT_LAST = (((1,), (1,)), ((), ()))


def _qmm_kernel(xq_ref, sx_ref, wq_ref, sw_ref, o_ref, acc_ref, *, nk):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        xq_ref[...], wq_ref[...], _CONTRACT_LAST,
        preferred_element_type=I32)

    @pl.when(k == nk - 1)
    def _finish():
        o_ref[...] = acc_ref[...].astype(F32) * sx_ref[...] * sw_ref[...].T


def qmm(xq, sx, wq, sw, *, block_m: int = 128, block_n: int = 128,
        block_k: int = 512, interpret: bool | None = None):
    """(M, K) int8 @ (N, K) int8 -> (M, N) f32.

    ``xq``/``sx``: rowwise-quantized activations + (M, 1) scales;
    ``wq``/``sw``: per-output-channel quantized weights + (N, 1) scales
    (the ``quantize_rows(w.T)`` layout). int8 zero-padding is exact, so
    arbitrary shapes cost nothing but the pad copy."""
    m, kdim = xq.shape
    n = wq.shape[0]
    # at least 8 output channels per tile: Mosaic lowers a 1-row weight
    # tile as a matrix-vector product, which has no int8 -> int32 form
    # (the estimator head's last layer has one output)
    bm, bn, bk = min(block_m, m), min(block_n, max(n, 8)), min(block_k, kdim)
    pm, pn, pk = (-m) % bm, (-n) % bn, (-kdim) % bk
    if pm or pk:
        xq = jnp.pad(xq, ((0, pm), (0, pk)))
        sx = jnp.pad(sx, ((0, pm), (0, 0)))
    if pn or pk:
        wq = jnp.pad(wq, ((0, pn), (0, pk)))
        sw = jnp.pad(sw, ((0, pn), (0, 0)))
    mp, npad, kp = m + pm, n + pn, kdim + pk
    nk = kp // bk
    out = pl.pallas_call(
        functools.partial(_qmm_kernel, nk=nk),
        grid=(mp // bm, npad // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((bn, bk), lambda i, j, k: (j, k)),
            pl.BlockSpec((bn, 1), lambda i, j, k: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, npad), F32),
        scratch_shapes=[pltpu.VMEM((bm, bn), I32)],
        interpret=resolve_interpret(interpret),
    )(xq, jnp.asarray(sx, F32), wq, jnp.asarray(sw, F32))
    return out[:m, :n]
