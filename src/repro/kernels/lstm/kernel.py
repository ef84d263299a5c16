"""Fused LSTM-cell scan as a Pallas kernel (fp32 + int8 serving variants).

The throughput estimator's temporal branch is a 30-step LSTM over each
UE's KPM window (``estimator.model.lstm_branch``): per step a
(B, K) @ (K, 4H) input projection, a (B, H) @ (H, 4H) recurrence, and the
gate chain. As XLA ops that is a ``lax.scan`` of ~10 small kernels per
step; here the whole scan runs inside one grid step per batch tile —
weights and the (h, c) carry stay resident in VMEM across all 30 steps,
and the matmul + gates + elementwise chain fuses into one kernel.

The int8 variant is the serving path's quantized LSTM: weights arrive
pre-quantized rowwise per *output* channel (the ``kernels/quant``
formula, applied to ``w.T``), activations are dynamically quantized
per row each step inside the kernel, and both projections run as
int8 x int8 -> int32 MXU dots scaled back to f32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

F32 = jnp.float32
I32 = jnp.int32

# contract the LAST axis of both operands: (B, K) x (OUT, K) -> (B, OUT),
# the layout int8 weights are stored in (rowwise quantization of w.T)
_CONTRACT_LAST = (((1,), (1,)), ((), ()))


def _gates(z, c):
    i, f, g, o = jnp.split(z, 4, axis=-1)
    c = jax.nn.sigmoid(f + 1.0) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
    h = jax.nn.sigmoid(o) * jnp.tanh(c)
    return h, c


def _rowq(x, qmax):
    # the kernels/quant rowwise symmetric formula, inlined (a kernel body
    # cannot nest a pallas_call); reciprocal multiply keeps it
    # bit-identical with quantize_ref / the quant kernel
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) * jnp.float32(1.0 / qmax)
    q = jnp.clip(jnp.round(x / scale), -qmax - 1, qmax).astype(jnp.int8)
    return q, scale


def _step_input(x_ref, t):
    """Step ``t`` of the (bn, T, K) window block as (bn, K) f32: a ref
    slice, which Mosaic lowers (a ``dynamic_slice`` of a loaded value it
    does not)."""
    return x_ref[:, pl.ds(t, 1), :][:, 0].astype(F32)


def _lstm_kernel(x_ref, wx_ref, wh_ref, b_ref, o_ref, *, t_steps, hidden):
    wx, wh, bias = wx_ref[...], wh_ref[...], b_ref[...]
    bn = x_ref.shape[0]
    h0 = jnp.zeros((bn, hidden), F32)

    def step(t, carry):
        h, c = carry
        x_t = _step_input(x_ref, t)
        z = (jnp.dot(x_t, wx, preferred_element_type=F32)
             + jnp.dot(h, wh, preferred_element_type=F32) + bias)
        return _gates(z, c)

    h, _ = jax.lax.fori_loop(0, t_steps, step, (h0, jnp.zeros_like(h0)))
    o_ref[...] = h


def _lstm_q_kernel(x_ref, wxq_ref, wxs_ref, whq_ref, whs_ref, b_ref, o_ref,
                   *, t_steps, hidden, qmax):
    wxq, whq = wxq_ref[...], whq_ref[...]  # (4H, K) / (4H, H) int8
    wxs, whs = wxs_ref[...].T, whs_ref[...].T  # (1, 4H) per-column scales
    bias = b_ref[...]
    bn = x_ref.shape[0]
    h0 = jnp.zeros((bn, hidden), F32)

    def qdot(a, wq, ws):
        qa, sa = _rowq(a, qmax)
        acc = jax.lax.dot_general(qa, wq, _CONTRACT_LAST,
                                  preferred_element_type=I32)
        return acc.astype(F32) * sa * ws

    def step(t, carry):
        h, c = carry
        x_t = _step_input(x_ref, t)
        z = qdot(x_t, wxq, wxs) + qdot(h, whq, whs) + bias
        return _gates(z, c)

    h, _ = jax.lax.fori_loop(0, t_steps, step, (h0, jnp.zeros_like(h0)))
    o_ref[...] = h


def lstm_scan(kpms, wx, wh, b, *, block_rows: int = 128,
              interpret: bool | None = None):
    """kpms (B, T, K), wx (K, 4H), wh (H, 4H), b (4H,) -> final h (B, H)."""
    n, t_steps, k = kpms.shape
    hidden = wh.shape[0]
    bn = min(block_rows, n)
    pad = (-n) % bn
    if pad:
        kpms = jnp.pad(kpms, ((0, pad), (0, 0), (0, 0)))
    npad = n + pad
    kernel = functools.partial(_lstm_kernel, t_steps=t_steps, hidden=hidden)
    out = pl.pallas_call(
        kernel,
        grid=(npad // bn,),
        in_specs=[
            pl.BlockSpec((bn, t_steps, k), lambda i: (i, 0, 0)),
            pl.BlockSpec((k, 4 * hidden), lambda i: (0, 0)),
            pl.BlockSpec((hidden, 4 * hidden), lambda i: (0, 0)),
            pl.BlockSpec((1, 4 * hidden), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bn, hidden), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((npad, hidden), F32),
        interpret=resolve_interpret(interpret),
    )(kpms.astype(F32), jnp.asarray(wx, F32), jnp.asarray(wh, F32),
      jnp.asarray(b, F32).reshape(1, -1))
    return out[:n]


def lstm_scan_q(kpms, wxq, wxs, whq, whs, b, *, qmax: int = 127,
                block_rows: int = 128, interpret: bool | None = None):
    """int8-serving LSTM scan -> final h (B, H) in f32.

    ``wxq`` (4H, K) / ``whq`` (4H, H): int8 weights quantized rowwise per
    output channel (``quantize_rows(w.T)``); ``wxs`` / ``whs`` (4H, 1):
    their f32 scales. Activations are quantized per row, per step, inside
    the kernel."""
    n, t_steps, k = kpms.shape
    hidden = whq.shape[1]
    bn = min(block_rows, n)
    pad = (-n) % bn
    if pad:
        kpms = jnp.pad(kpms, ((0, pad), (0, 0), (0, 0)))
    npad = n + pad
    kernel = functools.partial(_lstm_q_kernel, t_steps=t_steps,
                               hidden=hidden, qmax=qmax)
    out = pl.pallas_call(
        kernel,
        grid=(npad // bn,),
        in_specs=[
            pl.BlockSpec((bn, t_steps, k), lambda i: (i, 0, 0)),
            pl.BlockSpec((4 * hidden, k), lambda i: (0, 0)),
            pl.BlockSpec((4 * hidden, 1), lambda i: (0, 0)),
            pl.BlockSpec((4 * hidden, hidden), lambda i: (0, 0)),
            pl.BlockSpec((4 * hidden, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, 4 * hidden), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bn, hidden), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((npad, hidden), F32),
        interpret=resolve_interpret(interpret),
    )(kpms.astype(F32), wxq, jnp.asarray(wxs, F32), whq,
      jnp.asarray(whs, F32), jnp.asarray(b, F32).reshape(1, -1))
    return out[:n]
