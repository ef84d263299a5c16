"""jit'd public wrapper: GQA-aware flash attention entry point.

Differentiable: forward runs the Pallas kernel; backward differentiates
through the jnp oracle (mathematically identical) via custom_vjp — the
standard bring-up pattern until the dedicated backward kernel lands.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.flash_attention.ref import attention_ref

_VJP_CACHE: dict = {}


def _kernel_attn(causal, window, block_q, block_k, interpret):
    key = (causal, window, block_q, block_k, interpret)
    if key in _VJP_CACHE:
        return _VJP_CACHE[key]

    @jax.custom_vjp
    def f(q, k, v):
        return flash_attention(q, k, v, causal=causal, window=window,
                               block_q=block_q, block_k=block_k,
                               interpret=interpret)

    def fwd(q, k, v):
        return f(q, k, v), (q, k, v)

    def bwd(res, g):
        q, k, v = res
        _, vjp = jax.vjp(
            lambda a, b, c: attention_ref(a, b, c, causal=causal,
                                          window=window), q, k, v)
        return vjp(g)

    f.defvjp(fwd, bwd)
    _VJP_CACHE[key] = f
    return f


def _flatten_gqa(q, k, v):
    """(B,S,H,dh) + (B,S,KV,dh) -> (B*H, S, dh) with kv broadcast."""
    B, Sq, H, dh = q.shape
    KV = k.shape[2]
    G = H // KV
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, dh)
    kf = jnp.repeat(k.transpose(0, 2, 1, 3), G, axis=1).reshape(
        B * H, k.shape[1], dh)
    vf = jnp.repeat(v.transpose(0, 2, 1, 3), G, axis=1).reshape(
        B * H, v.shape[1], dh)
    return qf, kf, vf


@partial(jax.jit, static_argnames=("causal", "window", "use_kernel",
                                   "block_q", "block_k", "interpret"))
def mha(q, k, v, *, causal=True, window=0, use_kernel=True, block_q=128,
        block_k=128, interpret=None):
    """Multi-head attention. q: (B,S,H,dh); k,v: (B,S,KV,dh) (GQA)."""
    B, Sq, H, dh = q.shape
    qf, kf, vf = _flatten_gqa(q, k, v)
    if use_kernel:
        of = _kernel_attn(causal, window, block_q, block_k, interpret)(
            qf, kf, vf)
    else:
        of = attention_ref(qf, kf, vf, causal=causal, window=window)
    return of.reshape(B, H, Sq, dh).transpose(0, 2, 1, 3)
