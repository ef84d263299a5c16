"""jit wrappers for quantise/dequantise."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.quant.kernel import quantize
from repro.kernels.quant.ref import dequantize_ref, quantize_ref


@partial(jax.jit, static_argnames=("use_kernel", "interpret"))
def quantize_rows(x, *, use_kernel=True, interpret=None):
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    if use_kernel:
        q, s = quantize(x2, interpret=interpret)
    else:
        q, s = quantize_ref(x2)
    return q.reshape(shape), s.reshape(shape[:-1] + (1,))


@partial(jax.jit, static_argnames=("dtype",))
def dequantize_rows(q, s, dtype=jnp.bfloat16):
    return dequantize_ref(q, s, dtype)
