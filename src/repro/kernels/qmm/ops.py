"""jit dispatch for int8 serving matmuls.

``quantize_weight`` is the offline half (done once per deployment);
``int8_matmul`` the serving half — dynamic rowwise activation
quantization (via the ``kernels/quant`` oracle formula, inside the same
jit) followed by the int8 x int8 -> int32 product.
"""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels.qmm.kernel import qmm
from repro.kernels.qmm.ref import qmm_ref
from repro.kernels.quant.ops import quantize_rows
from repro.kernels.quant.ref import quantize_ref


def quantize_weight(w, *, use_kernel: bool = True,
                    interpret: bool | None = None):
    """(K, N) f32 weight -> ((N, K) int8, (N, 1) f32 scales).

    Rowwise quantization of ``w.T`` — one int8 row (and one scale) per
    *output* channel, the layout ``int8_matmul`` and the int8 LSTM kernel
    consume. Runs the existing ``kernels/quant`` quantizer."""
    return quantize_rows(w.T, use_kernel=use_kernel, interpret=interpret)


@partial(jax.jit, static_argnames=("use_kernel", "interpret"))
def int8_matmul(x, wq, sw, *, use_kernel: bool = True,
                interpret: bool | None = None):
    """x (M, K) f32 @ quantized weight -> (M, N) f32.

    Activations are quantized rowwise on the fly (exactly the
    ``kernels/quant`` formula, so the quant kernel and this path agree
    bit-for-bit); the product runs as int8 x int8 -> int32 and is scaled
    back to f32. ``use_kernel=False`` takes the jnp oracle — identical
    numerics (integer accumulation is exact), and the form GSPMD can
    shard under a serving mesh."""
    xq, sx = quantize_ref(x)
    if use_kernel:
        return qmm(xq, sx, wq, sw, interpret=interpret)
    return qmm_ref(xq, sx, wq, sw)
