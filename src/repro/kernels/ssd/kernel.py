"""Mamba2 SSD as a Pallas TPU kernel.

Grid (B*G, nc): the chunk axis is innermost/sequential, so the running
inter-chunk state (hpg, hd, N) lives in VMEM scratch across chunk steps —
the XLA fallback materialises every chunk's (L, L) decay matrices in HBM
(the 23GB temp observed on mamba2 train_4k); here one (L, L) tile exists
per head at a time, in VMEM.

Blocks put the head axis first, so the body is a static loop over the
group's heads of plain 2-D tiles: ``x_ref[h]`` is (L, hd), ``dt_ref[h]``
(L, 1). The within-chunk cumulative decay is a lower-triangular matmul
(Mosaic has no cumsum), and every dot runs at fp32 contract precision.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

F32 = jnp.float32


_HI = jax.lax.Precision.HIGHEST


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())), precision=_HI,
                               preferred_element_type=F32)


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, sout_ref, s_ref,
                *, n_chunks, hpg, L):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    Bv = b_ref[...].astype(F32)  # (L, N)
    Cv = c_ref[...].astype(F32)  # (L, N)
    CB = _dot(Cv, Bv, ((1,), (1,)))  # (L, L) [t, s]
    rows = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    tri = rows >= jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    ones_tri = tri.astype(F32)
    for h in range(hpg):  # static: hpg is a handful of heads
        x = x_ref[h].astype(F32)  # (L, hd)
        dt = dt_ref[h].astype(F32)  # (L, 1)
        dA = dt * a_ref[h].astype(F32)  # (L, 1)
        lcum = _dot(ones_tri, dA, ((1,), (0,)))  # (L, 1) inclusive cumsum
        lrow = _dot(dA, ones_tri, ((0,), (1,)))  # (1, L) the same, as a row
        # mask before exp (matches ref.py): keeps the masked upper triangle
        # from overflowing exp and poisoning gradients through the where().
        M = jnp.exp(jnp.where(tri, lcum - lrow, -jnp.inf)) * CB  # (L, L)
        du = dt * x  # (L, hd)
        y_intra = _dot(M, du, ((1,), (0,)))  # (L, hd)
        # inter-chunk: contribution of the carried state
        s_prev = s_ref[h]  # (hd, N)
        y_inter = _dot(Cv, s_prev, ((1,), (1,))) * jnp.exp(lcum)
        y_ref[h] = (y_intra + y_inter).astype(y_ref.dtype)
        # state update
        lend = lcum[L - 1:L, :]  # (1, 1)
        S_c = _dot(du * jnp.exp(lend - lcum), Bv, ((0,), (0,)))  # (hd, N)
        # the chunk's total decay again, as a (1, N) row: Mosaic cannot
        # broadcast the (1, 1) ``lend`` over both sublanes and lanes
        lend_n = _dot(dA, jnp.ones((L, s_prev.shape[1]), F32), ((0,), (0,)))
        s_ref[h] = s_prev * jnp.exp(lend_n) + S_c

    @pl.when(ic == n_chunks - 1)
    def _emit_state():
        sout_ref[...] = s_ref[...]


def ssd(x, dt, A, Bm, Cm, *, chunk: int = 256, interpret: bool | None = None):
    """x: (B,S,nh,hd); dt: (B,S,nh); A: (nh,); Bm/Cm: (B,S,G,N).
    Returns (y (B,S,nh,hd), state (B,G,hpg,hd,N))."""
    B, S, nh, hd = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    hpg = nh // G
    L = min(chunk, S)
    assert S % L == 0
    nc = S // L
    # regroup to (B*G, hpg, S, ...) so one grid cell owns one B/C group
    # and each head's chunk is a 2-D tile
    xg = x.reshape(B, S, G, hpg, hd).transpose(0, 2, 3, 1, 4).reshape(
        B * G, hpg, S, hd)
    dtg = dt.reshape(B, S, G, hpg).transpose(0, 2, 3, 1).reshape(
        B * G, hpg, S, 1)
    Ag = jnp.broadcast_to(A.reshape(1, G, hpg), (B, G, hpg)).reshape(
        B * G, hpg, 1, 1)
    Bg = Bm.transpose(0, 2, 1, 3).reshape(B * G, S, N)
    Cg = Cm.transpose(0, 2, 1, 3).reshape(B * G, S, N)
    kernel = functools.partial(_ssd_kernel, n_chunks=nc, hpg=hpg, L=L)
    y, state = pl.pallas_call(
        kernel,
        grid=(B * G, nc),
        in_specs=[
            pl.BlockSpec((None, hpg, L, hd), lambda b, c: (b, 0, c, 0)),
            pl.BlockSpec((None, hpg, L, 1), lambda b, c: (b, 0, c, 0)),
            pl.BlockSpec((None, hpg, 1, 1), lambda b, c: (b, 0, 0, 0)),
            pl.BlockSpec((None, L, N), lambda b, c: (b, c, 0)),
            pl.BlockSpec((None, L, N), lambda b, c: (b, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, hpg, L, hd), lambda b, c: (b, 0, c, 0)),
            pl.BlockSpec((None, hpg, hd, N), lambda b, c: (b, 0, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * G, hpg, S, hd), x.dtype),
            jax.ShapeDtypeStruct((B * G, hpg, hd, N), F32),
        ],
        scratch_shapes=[pltpu.VMEM((hpg, hd, N), F32)],
        interpret=resolve_interpret(interpret),
    )(xg, dtg, Ag, Bg, Cg)
    y = y.reshape(B, G, hpg, S, hd).transpose(0, 3, 1, 2, 4).reshape(
        B, S, nh, hd)
    state = state.reshape(B, G, hpg, hd, N)
    return y, state
