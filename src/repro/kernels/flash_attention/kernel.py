"""Flash attention as a Pallas TPU kernel.

Tiling: grid (BH, num_q_blocks, num_kv_blocks); the kv-block axis is the
innermost (sequential on TPU), so fp32 scratch accumulators (acc, m, l) in
VMEM persist across kv steps — the classical online-softmax recurrence.
BlockSpecs keep one (block_q, dh) Q tile and one (block_k, dh) K/V tile in
VMEM; dh and block sizes should be multiples of 128 on real hardware (MXU
alignment) — asserted softly so reduced test shapes still run in interpret
mode on CPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

F32 = jnp.float32
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale, causal, window, block_q, block_k, n_kv):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[...].astype(F32)  # (bq, dh)
    k = k_ref[...].astype(F32)  # (bk, dh)
    v = v_ref[...].astype(F32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=F32) * scale
    q_pos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 0)
    k_pos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 1)
    valid = jnp.ones((block_q, block_k), bool)
    if causal:
        valid &= q_pos >= k_pos
    if window:
        valid &= q_pos - k_pos < window
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]
    l_prev = l_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + p.sum(axis=1)
    acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=F32)
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(ik == n_kv - 1)
    def _finish():
        o_ref[...] = (acc_ref[...] /
                      jnp.maximum(l_ref[...], 1e-30)[:, None]
                      ).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    scale: float | None = None, block_q: int = 128,
                    block_k: int = 128, interpret: bool | None = None):
    """q,k,v: (BH, S, dh) with kv heads pre-broadcast to q heads."""
    BH, Sq, dh = q.shape
    Sk = k.shape[1]
    scale = scale if scale is not None else dh**-0.5
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    assert Sq % block_q == 0 and Sk % block_k == 0, (Sq, Sk, block_q, block_k)
    n_q = Sq // block_q
    n_kv = Sk // block_k
    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, n_kv=n_kv)
    return pl.pallas_call(
        kernel,
        grid=(BH, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((None, block_q, dh), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, block_k, dh), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((None, block_k, dh), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, dh), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, dh), q.dtype),
        scratch_shapes=[
            # fp32 accumulators surviving the (sequential) kv-block loop
            pltpu.VMEM((block_q, dh), F32),
            pltpu.VMEM((block_q,), F32),
            pltpu.VMEM((block_q,), F32),
        ],
        interpret=resolve_interpret(interpret),
    )(q, k, v)
