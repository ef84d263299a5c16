"""Transformer / SSM / recurrent building blocks (pure JAX, template-driven).

Every block has a ``*_template(cfg)`` returning a ParamSpec tree and a
forward taking ``(cfg, params, x, ...)``. Compute runs in bf16 with fp32
accumulation; params are fp32.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from repro.dist.sharding import axis_size, constrain, kv_repeat
from repro.models.template import ParamSpec

F32 = jnp.float32
DEFAULT_COMPUTE = jnp.bfloat16

ATTN_CHUNK = 1024  # kv-chunk for flash-style attention
MOE_GROUP = 1024  # tokens per MoE dispatch group

# When True, inner lax.scans (attention kv-chunks, SSD chunk recurrence) are
# unrolled into python loops so XLA cost_analysis counts every iteration.
# Used ONLY by the roofline calibration compiles (see launch/roofline.py).
INNER_UNROLL = False

# Route full-sequence self-attention through the Pallas flash-attention
# kernel (repro/kernels/flash_attention). The kernel interprets on the cpu
# backend and compiles on tpu (``kernels.resolve_interpret``); on the chip
# this is the path that keeps score tiles in VMEM.
USE_PALLAS_ATTENTION = False


def _maybe_unrolled_scan(step, init, xs, length):
    if not INNER_UNROLL:
        return lax.scan(step, init, xs)
    carry = init
    ys = []
    for i in range(length):
        x_i = jax.tree.map(lambda t: t[i], xs)
        carry, y = step(carry, x_i)
        ys.append(y)
    if ys and ys[0] is not None:
        ys = jax.tree.map(lambda *t: jnp.stack(t), *ys)
    else:
        ys = None
    return carry, ys


def cast(x, dtype):
    return x.astype(dtype) if x.dtype != dtype else x


# ------------------------------------------------------------------ norms
def rms_norm(x, scale, eps):
    xf = cast(x, F32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * lax.rsqrt(var + eps) * (1.0 + cast(scale, F32))
    return cast(out, x.dtype)


def _act(name):
    return {"silu": jax.nn.silu, "gelu": partial(jax.nn.gelu, approximate=True)}[name]


# ------------------------------------------------------------------ rope
def rope(x, positions, theta):
    """x: (..., S, H, dh), positions: (..., S) int32."""
    dh = x.shape[-1]
    half = dh // 2
    freqs = jnp.exp(
        -jnp.log(jnp.float32(theta)) * jnp.arange(half, dtype=F32) / half
    )
    ang = positions[..., None].astype(F32) * freqs  # (..., S, half)
    cos = jnp.cos(ang)[..., None, :]  # (..., S, 1, half)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(cast(x, F32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return cast(out, x.dtype)


# ------------------------------------------------------------------ attention cores
# GQA grouping is KV-MAJOR everywhere: q head h uses kv head h // (H/KV), so
# consecutive q heads share a kv head. With q heads sharded over 'model',
# each shard's q group aligns exactly with its local kv shard — G-major
# grouping forced XLA to all-gather the whole KV cache per layer.
def _gqa_scores(q, k, scale):
    """q: (B,Sq,KV,G,dh) k: (B,Sk,KV,dh) -> (B,KV,G,Sq,Sk) fp32."""
    return jnp.einsum(
        "bqkgd,bskd->bkgqs", q, k, preferred_element_type=F32
    ) * scale


def direct_attention(q, k, v, mask, scale):
    """Reference full-materialisation attention.

    q: (B,Sq,H,dh); k,v: (B,Sk,KV,dh); mask broadcastable to (B,1,1,Sq,Sk)."""
    B, Sq, H, dh = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, Sq, KV, G, dh)
    s = _gqa_scores(qg, k, scale)
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", cast(p, v.dtype), v, preferred_element_type=F32)
    return cast(o.reshape(B, Sq, H, dh), q.dtype)


def chunked_attention(q, k, v, *, causal, window, scale, chunk=ATTN_CHUNK):
    """Flash-style online-softmax attention, scanned over KV chunks.

    O(Sq * chunk) live memory; exact. q:(B,Sq,H,dh) k,v:(B,Sk,KV,dh).
    ``window``>0 restricts to a trailing sliding window (causal only).
    """
    B, Sq, H, dh = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    G = H // KV
    if Sk % chunk:
        chunk = Sk  # fallback: single chunk
    nck = Sk // chunk
    qg = q.reshape(B, Sq, KV, G, dh)
    kc = k.reshape(B, nck, chunk, KV, dh).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, nck, chunk, KV, dh).transpose(1, 0, 2, 3, 4)
    q_pos = jnp.arange(Sq, dtype=jnp.int32)

    def step(carry, xs):
        acc, m, l = carry
        kb, vb, ci = xs
        s = _gqa_scores(qg, kb, scale)  # (B,KV,G,Sq,chunk)
        k_pos = ci * chunk + jnp.arange(chunk, dtype=jnp.int32)
        valid = jnp.ones((Sq, chunk), bool)
        if causal:
            valid &= q_pos[:, None] >= k_pos[None, :]
        if window:
            valid &= q_pos[:, None] - k_pos[None, :] < window
        s = jnp.where(valid[None, None, None], s, -1e30)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bkgqs,bskd->bkgqd", cast(p, vb.dtype), vb, preferred_element_type=F32
        )
        return (acc, m_new, l), None

    acc0 = jnp.zeros((B, KV, G, Sq, dh), F32)
    m0 = jnp.full((B, KV, G, Sq), -jnp.inf, F32)
    l0 = jnp.zeros((B, KV, G, Sq), F32)
    (acc, m, l), _ = _maybe_unrolled_scan(
        step, (acc0, m0, l0), (kc, vc, jnp.arange(nck, dtype=jnp.int32)), nck
    )
    o = acc / jnp.maximum(l[..., None], 1e-30)
    o = o.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, dh)  # (B,Sq,KV,G,dh)->flat
    return cast(o, q.dtype)


def local_attention(q, k, v, *, window, scale):
    """Exact sliding-window attention via (self + previous) blocks.

    FLOPs O(S * 2*window) instead of O(S^2). Block rows are processed through
    a scan so only one row's (w, 2w) score tile is live at a time."""
    B, S, H, dh = q.shape
    KV = k.shape[2]
    G = H // KV
    w = window
    pad = (-S) % w
    if pad:
        padfn = lambda t: jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        q, k, v = padfn(q), padfn(k), padfn(v)
    Sp = S + pad
    nb = Sp // w
    qb = q.reshape(B, nb, w, KV, G, dh)
    kb = k.reshape(B, nb, w, KV, dh)
    vb = v.reshape(B, nb, w, KV, dh)
    shift = lambda t: jnp.pad(t, ((0, 0), (1, 0)) + ((0, 0),) * (t.ndim - 2))[:, :-1]
    kctx = jnp.concatenate([shift(kb), kb], axis=2)  # (B,nb,2w,KV,dh)
    vctx = jnp.concatenate([shift(vb), vb], axis=2)
    q_pos = jnp.arange(w)[:, None]  # within-block
    k_pos = jnp.arange(2 * w)[None, :] - w
    rel = q_pos - k_pos  # absolute distance q-k
    valid = (rel >= 0) & (rel < w)

    def row(_, xs):
        qi, ki, vi, is_first = xs
        s = jnp.einsum("bqkgd,bskd->bkgqs", qi, ki,
                       preferred_element_type=F32) * scale
        v_ok = valid & ~(is_first & (k_pos < 0))
        s = jnp.where(v_ok[None, None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bkgqs,bskd->bqkgd", cast(p, vi.dtype), vi,
                       preferred_element_type=F32)
        return None, cast(o, qi.dtype)

    first = jnp.zeros((nb,), bool).at[0].set(True)
    xs = (qb.transpose(1, 0, 2, 3, 4, 5), kctx.transpose(1, 0, 2, 3, 4),
          vctx.transpose(1, 0, 2, 3, 4), first)
    _, ob = _maybe_unrolled_scan(row, None, xs, nb)
    o = ob.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sp, H, dh)
    return o[:, :S]


def decode_attention(q, ck, cv, cpos, pos, *, window, scale):
    """Single-token attention over a (ring-buffer) cache.

    q: (B,1,H,dh); ck/cv: (B,W,KV,dh); cpos: (W,) int32 absolute positions
    written (-1 = empty); pos: scalar current position."""
    B, _, H, dh = q.shape
    KV = ck.shape[2]
    G = H // KV
    qg = q.reshape(B, 1, KV, G, dh)
    s = _gqa_scores(qg, ck, scale)[..., 0, :]  # (B,KV,G,W)
    valid = (cpos >= 0) & (cpos <= pos)
    if window:
        valid &= pos - cpos < window
    s = jnp.where(valid[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgw,bwkd->bkgd", cast(p, cv.dtype), cv,
                   preferred_element_type=F32)
    return cast(o.reshape(B, 1, H, dh), q.dtype)


# ------------------------------------------------------------------ attention block
def attn_template(cfg, kind: str):
    D, H, KV, dh = cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim
    kv_in = cfg.vision_dim if kind == "cross" else D
    t = {
        "ln": ParamSpec((D,), ("embed",), init="zeros"),
        "wq": ParamSpec((D, H, dh), ("fsdp", "heads", None)),
        "wk": ParamSpec((kv_in, KV, dh), ("fsdp", "kv", None)),
        "wv": ParamSpec((kv_in, KV, dh), ("fsdp", "kv", None)),
        "wo": ParamSpec((H, dh, D), ("heads", None, "fsdp")),
    }
    if cfg.qkv_bias:
        t["bq"] = ParamSpec((H, dh), ("heads", None), init="zeros")
        t["bk"] = ParamSpec((KV, dh), ("kv", None), init="zeros")
        t["bv"] = ParamSpec((KV, dh), ("kv", None), init="zeros")
    if kind == "cross":
        t["gate"] = ParamSpec((), (), init="zeros")
    return t


def qkv_proj(cfg, p, x, cross_kv=None, dtype=DEFAULT_COMPUTE):
    """Returns q (B,S,H,dh), k, v (B,S,KVeff,dh) with kv repeated for TP."""
    q = jnp.einsum("bsd,dhk->bshk", x, cast(p["wq"], dtype))
    kv_src = cast(cross_kv, dtype) if cross_kv is not None else x
    k = jnp.einsum("bsd,dhk->bshk", kv_src, cast(p["wk"], dtype))
    v = jnp.einsum("bsd,dhk->bshk", kv_src, cast(p["wv"], dtype))
    if cfg.qkv_bias:
        q = q + cast(p["bq"], dtype)
        k = k + cast(p["bk"], dtype)
        v = v + cast(p["bv"], dtype)
    r = kv_repeat(cfg.kv_heads, cfg.n_heads)
    if r > 1:
        k = jnp.repeat(k, r, axis=2)
        v = jnp.repeat(v, r, axis=2)
    q = constrain(q, ("batch", _q_seq_axis(cfg), "heads", None))
    k = constrain(k, ("batch", "seq", "kv", None))
    v = constrain(v, ("batch", "seq", "kv", None))
    return q, k, v


def _q_seq_axis(cfg) -> str:
    """Context-parallel attention fallback: if the head count can't shard
    over 'model', shard queries/outputs on their sequence dim instead."""
    m = axis_size("model")
    return "ctx_attn" if (m > 1 and cfg.n_heads % m) else "seq"


def attention_block(cfg, p, x, *, kind, window, positions, cross_kv=None,
                    dtype=DEFAULT_COMPUTE, return_cache=False, max_seq=None):
    """Full-sequence (train / prefill) attention sublayer. Returns residual
    delta (and, if return_cache, the decode cache this prefill produces)."""
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    q, k, v = qkv_proj(cfg, p, h, cross_kv if kind == "cross" else None, dtype)
    scale = cfg.head_dim ** -0.5
    if kind != "cross":
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    S = x.shape[1]
    if kind == "cross":
        o = chunked_attention(q, k, v, causal=False, window=0, scale=scale)
    elif USE_PALLAS_ATTENTION and kind != "cross":
        from repro.kernels.flash_attention import mha  # lazy: optional path
        o = mha(q, k, v, causal=cfg.causal, window=window,
                block_q=min(128, S), block_k=min(128, S))
    elif window and S > window:
        o = local_attention(q, k, v, window=window, scale=scale)
    elif S > ATTN_CHUNK:
        o = chunked_attention(q, k, v, causal=cfg.causal, window=window, scale=scale)
    else:
        q_pos = jnp.arange(S)
        mask = jnp.ones((S, S), bool)
        if cfg.causal:
            mask &= q_pos[:, None] >= q_pos[None, :]
        if window:
            mask &= q_pos[:, None] - q_pos[None, :] < window
        o = direct_attention(q, k, v, mask[None, None, None], scale)
    o = constrain(o, ("batch", _q_seq_axis(cfg), "heads", None))
    out = jnp.einsum("bshk,hkd->bsd", o, cast(p["wo"], dtype))
    if kind == "cross":
        out = jnp.tanh(cast(p["gate"], F32)).astype(dtype) * out
    out = constrain(out, ("batch", "seq", "embed"))
    if not return_cache:
        return out
    if kind == "cross":
        cache = {"k": cast(k, jnp.bfloat16), "v": cast(v, jnp.bfloat16)}
    else:
        ms = max_seq or S
        W = min(window, ms) if window else ms
        keep = min(W, S)  # most recent tokens that fit the ring
        pos = jnp.arange(S - keep, S, dtype=jnp.int32)
        slots = pos % W
        int8 = cfg.kv_dtype == "int8"
        kv_dt = jnp.int8 if int8 else jnp.bfloat16
        if int8:
            kq, ksc = _kv_quant(k[:, S - keep:])
            vq, vsc = _kv_quant(v[:, S - keep:])
        else:
            kq, vq = cast(k[:, S - keep:], kv_dt), cast(v[:, S - keep:], kv_dt)
        ck = jnp.zeros((k.shape[0], W) + k.shape[2:], kv_dt)
        cv = jnp.zeros_like(ck)
        ck = ck.at[:, slots].set(kq)
        cv = cv.at[:, slots].set(vq)
        cpos = jnp.full((W,), -1, jnp.int32).at[slots].set(pos)
        cache = {"k": ck, "v": cv, "pos": cpos}
        if int8:
            zs = jnp.zeros((k.shape[0], W, k.shape[2], 1), F32)
            cache["k_scale"] = zs.at[:, slots].set(ksc)
            cache["v_scale"] = zs.at[:, slots].set(vsc)
    return out, cache


def _kv_quant(x):
    """Per-(token, head) symmetric int8 over head_dim. x: (..., dh)."""
    from repro.core.boundary import rowwise_quant  # lazy: avoid import cycle
    return rowwise_quant(x, 127)


def _kv_deq(q, s, dtype):
    return (q.astype(F32) * s).astype(dtype)


def attention_decode(cfg, p, x, cache, pos, *, kind, window, cross_kv=None,
                     dtype=DEFAULT_COMPUTE):
    """One-token attention with cache update. x: (B,1,D). Returns (delta,
    new_cache)."""
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    scale = cfg.head_dim ** -0.5
    if kind == "cross":
        # static cross-kv: cache holds projected vision k/v, no update
        q = jnp.einsum("bsd,dhk->bshk", h, cast(p["wq"], dtype))
        r = kv_repeat(cfg.kv_heads, cfg.n_heads)
        ck, cv = cache["k"], cache["v"]
        W = ck.shape[1]
        o = decode_attention(q, ck, cv, jnp.zeros((W,), jnp.int32), pos,
                             window=0, scale=scale)
        out = jnp.einsum("bshk,hkd->bsd", o, cast(p["wo"], dtype))
        out = jnp.tanh(cast(p["gate"], F32)).astype(dtype) * out
        return out, cache
    q, k, v = qkv_proj(cfg, p, h, None, dtype)
    posv = jnp.full((x.shape[0], 1), pos, jnp.int32)
    q = rope(q, posv, cfg.rope_theta)
    k = rope(k, posv, cfg.rope_theta)
    W = cache["k"].shape[1]
    slot = pos % W
    int8 = cfg.kv_dtype == "int8"
    if int8:
        kq, ks = _kv_quant(k)
        vq, vs = _kv_quant(v)
    else:
        kq, vq = cast(k, cache["k"].dtype), cast(v, cache["v"].dtype)
    ck = lax.dynamic_update_slice(cache["k"], kq, (0, slot, 0, 0))
    cv = lax.dynamic_update_slice(cache["v"], vq, (0, slot, 0, 0))
    cpos = lax.dynamic_update_slice(cache["pos"], pos[None].astype(jnp.int32),
                                    (slot,))
    ck = constrain(ck, ("batch", "cache_seq", "kv", None))
    cv = constrain(cv, ("batch", "cache_seq", "kv", None))
    new_cache = {"k": ck, "v": cv, "pos": cpos}
    if int8:
        cks = lax.dynamic_update_slice(cache["k_scale"], ks, (0, slot, 0, 0))
        cvs = lax.dynamic_update_slice(cache["v_scale"], vs, (0, slot, 0, 0))
        new_cache["k_scale"], new_cache["v_scale"] = cks, cvs
        ck = _kv_deq(ck, cks, dtype)
        cv = _kv_deq(cv, cvs, dtype)
    o = decode_attention(q, ck, cv, cpos, pos, window=window, scale=scale)
    out = jnp.einsum("bshk,hkd->bsd", o, cast(p["wo"], dtype))
    return constrain(out, ("batch", "seq", "embed")), new_cache


def attn_cache_template(cfg, batch, max_seq, window, kind):
    r = kv_repeat(cfg.kv_heads, cfg.n_heads)
    kveff = cfg.kv_heads * r
    if kind == "cross":
        W = cfg.vision_tokens
        return {
            "k": ParamSpec((batch, W, kveff, cfg.head_dim),
                           ("batch", None, "kv", None), jnp.bfloat16, "zeros"),
            "v": ParamSpec((batch, W, kveff, cfg.head_dim),
                           ("batch", None, "kv", None), jnp.bfloat16, "zeros"),
        }
    W = min(window, max_seq) if window else max_seq
    int8 = cfg.kv_dtype == "int8"
    kv_dt = jnp.int8 if int8 else jnp.bfloat16
    t = {
        "k": ParamSpec((batch, W, kveff, cfg.head_dim),
                       ("batch", "cache_seq", "kv", None), kv_dt, "zeros"),
        "v": ParamSpec((batch, W, kveff, cfg.head_dim),
                       ("batch", "cache_seq", "kv", None), kv_dt, "zeros"),
        "pos": ParamSpec((W,), ("cache_seq",), jnp.int32, "neg_ones"),
    }
    if int8:
        t["k_scale"] = ParamSpec((batch, W, kveff, 1),
                                 ("batch", "cache_seq", "kv", None),
                                 F32, "zeros")
        t["v_scale"] = ParamSpec((batch, W, kveff, 1),
                                 ("batch", "cache_seq", "kv", None),
                                 F32, "zeros")
    return t


# ------------------------------------------------------------------ MLP
def mlp_template(cfg):
    D, Fd = cfg.d_model, cfg.d_ff
    return {
        "ln": ParamSpec((D,), ("embed",), init="zeros"),
        "wg": ParamSpec((D, Fd), ("fsdp", "ff")),
        "wu": ParamSpec((D, Fd), ("fsdp", "ff")),
        "wd": ParamSpec((Fd, D), ("ff", "fsdp")),
    }


def mlp_block(cfg, p, x, dtype=DEFAULT_COMPUTE):
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    g = _act(cfg.act)(h @ cast(p["wg"], dtype))
    u = h @ cast(p["wu"], dtype)
    hid = constrain(g * u, ("batch", "seq", "ff"))
    return constrain(hid @ cast(p["wd"], dtype), ("batch", "seq", "embed"))


# ------------------------------------------------------------------ MoE
def moe_template(cfg):
    D, Fd, E = cfg.d_model, cfg.d_ff, cfg.num_experts
    return {
        "ln": ParamSpec((D,), ("embed",), init="zeros"),
        "router": ParamSpec((D, E), (None, None), init="fan_in"),
        "wg": ParamSpec((E, D, Fd), ("experts", "fsdp", "ff")),
        "wu": ParamSpec((E, D, Fd), ("experts", "fsdp", "ff")),
        "wd": ParamSpec((E, Fd, D), ("experts", "ff", "fsdp")),
    }


def moe_block(cfg, p, x, dtype=DEFAULT_COMPUTE):
    """Group-wise top-k dispatch/combine (Switch-style with capacity).

    Returns (delta, aux_loss)."""
    B, S, D = x.shape
    E, K = cfg.num_experts, cfg.top_k
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    T = B * S
    g = min(MOE_GROUP, T)
    if T % g:
        g = T
    nG = T // g
    xt = constrain(h.reshape(nG, g, D), ("batch", None, "embed"))
    logits = jnp.einsum("gtd,de->gte", cast(xt, F32), cast(p["router"], F32))
    probs = jax.nn.softmax(logits, axis=-1)  # (nG,g,E)
    top_p, top_i = lax.top_k(probs, K)  # (nG,g,K)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    cap = max(1, int(cfg.capacity_factor * g * K / E))
    onehot = jax.nn.one_hot(top_i, E, dtype=F32)  # (nG,g,K,E)
    # position of each (token,k) within its expert queue, priority by k then t
    flat = onehot.transpose(0, 2, 1, 3).reshape(nG, K * g, E)
    pos = jnp.cumsum(flat, axis=1) - flat  # (nG,K*g,E)
    pos = pos.reshape(nG, K, g, E).transpose(0, 2, 1, 3)  # (nG,g,K,E)
    keep = (pos < cap) * onehot
    slot_idx = jnp.sum(pos * onehot, -1).astype(jnp.int32)
    slot = jax.nn.one_hot(slot_idx, cap, dtype=F32)  # (nG,g,K,cap)
    dispatch = jnp.einsum("gtke,gtkc->gtec", keep, slot)  # (nG,g,E,cap)
    combine = jnp.einsum("gtke,gtkc,gtk->gtec", keep, slot, top_p)
    xe = jnp.einsum("gtec,gtd->gecd", cast(dispatch, dtype), cast(xt, dtype))
    xe = constrain(xe, ("batch", "experts", None, "embed"))
    gg = _act(cfg.act)(jnp.einsum("gecd,edf->gecf", xe, cast(p["wg"], dtype)))
    uu = jnp.einsum("gecd,edf->gecf", xe, cast(p["wu"], dtype))
    hid = constrain(gg * uu, ("batch", "experts", None, "ff"))
    ye = jnp.einsum("gecf,efd->gecd", hid, cast(p["wd"], dtype))
    # reduce-scatter the ff-contraction onto the capacity dim instead of
    # all-reducing the full (groups,E,cap,D) buffer
    ye = constrain(ye, ("batch", "experts", "cap", "embed"))
    y = jnp.einsum("gecd,gtec->gtd", ye, cast(combine, dtype))
    y = constrain(y, ("batch", None, "embed"))
    # load-balancing aux loss (Switch): E * sum_e f_e * p_e
    me = probs.mean(axis=(0, 1))  # (E,)
    fe = onehot.sum(axis=2).mean(axis=(0, 1))  # fraction routed per expert
    aux = E * jnp.sum(me * fe) / K
    return constrain(y.reshape(B, S, D), ("batch", "seq", "embed")), aux


# ------------------------------------------------------------------ RG-LRU (Griffin)
def rec_template(cfg):
    D, W = cfg.d_model, cfg.lru_width
    cw = cfg.conv1d_width
    return {
        "ln": ParamSpec((D,), ("embed",), init="zeros"),
        "wx": ParamSpec((D, W), ("fsdp", "ff")),
        "wy": ParamSpec((D, W), ("fsdp", "ff")),
        "conv_w": ParamSpec((cw, W), (None, "ff"), init="fan_in"),
        "conv_b": ParamSpec((W,), ("ff",), init="zeros"),
        "wi": ParamSpec((W, W), ("fsdp", "ff")),
        "wa": ParamSpec((W, W), ("fsdp", "ff")),
        "lam": ParamSpec((W,), ("ff",), init="normal", scale=0.5),
        "wo": ParamSpec((W, D), ("ff", "fsdp")),
    }


def _causal_conv(x, w, b, state=None):
    """Depthwise causal conv. x:(B,S,W) w:(cw,W). state: (B,cw-1,W) or None.
    Returns (y, new_state)."""
    cw = w.shape[0]
    if state is None:
        xp = jnp.pad(x, ((0, 0), (cw - 1, 0), (0, 0)))
    else:
        xp = jnp.concatenate([cast(state, x.dtype), x], axis=1)
    y = sum(xp[:, i : i + x.shape[1]] * w[i] for i in range(cw))
    new_state = xp[:, -(cw - 1) :] if cw > 1 else None
    return y + b, new_state


_LRU_C = 8.0


def _rglru_gates(p, xc, dtype):
    i = jax.nn.sigmoid(xc @ cast(p["wi"], dtype))
    r = jax.nn.sigmoid(xc @ cast(p["wa"], dtype))
    log_a = -_LRU_C * jax.nn.softplus(cast(p["lam"], F32)) * cast(r, F32)
    a = jnp.exp(log_a)
    gated = jnp.sqrt(jnp.maximum(1.0 - a * a, 1e-9)) * cast(i, F32) * cast(xc, F32)
    return a, gated  # fp32


def rec_block(cfg, p, x, dtype=DEFAULT_COMPUTE):
    """RG-LRU temporal-mixing sublayer (train/prefill, associative scan).
    Returns (residual delta, decode state)."""
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    xb = h @ cast(p["wx"], dtype)
    yb = _act(cfg.act)(h @ cast(p["wy"], dtype))
    xc, conv_state = _causal_conv(
        xb, cast(p["conv_w"], dtype), cast(p["conv_b"], dtype)
    )
    xc = constrain(xc, ("batch", "seq", "ff"))
    a, gated = _rglru_gates(p, xc, dtype)

    def comb(c1, c2):
        a1, b1 = c1
        a2, b2 = c2
        return a1 * a2, b1 * a2 + b2

    _, hseq = lax.associative_scan(comb, (a, gated), axis=1)
    out = (cast(hseq, dtype) * yb) @ cast(p["wo"], dtype)
    state = {"h": hseq[:, -1], "conv": cast(conv_state, jnp.bfloat16)}
    return constrain(out, ("batch", "seq", "embed")), state


def rec_decode(cfg, p, x, cache, dtype=DEFAULT_COMPUTE):
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    xb = h @ cast(p["wx"], dtype)
    yb = _act(cfg.act)(h @ cast(p["wy"], dtype))
    xc, conv_state = _causal_conv(xb, cast(p["conv_w"], dtype),
                                  cast(p["conv_b"], dtype), cache["conv"])
    a, gated = _rglru_gates(p, xc, dtype)
    hnew = a * cache["h"][:, None] + gated  # (B,1,W)
    out = (cast(hnew, dtype) * yb) @ cast(p["wo"], dtype)
    return out, {"h": hnew[:, 0], "conv": cast(conv_state, cache["conv"].dtype)}


def rec_cache_template(cfg, batch):
    W, cw = cfg.lru_width, cfg.conv1d_width
    return {
        "h": ParamSpec((batch, W), ("batch", "ff"), F32, "zeros"),
        "conv": ParamSpec((batch, cw - 1, W), ("batch", None, "ff"),
                          jnp.bfloat16, "zeros"),
    }


# ------------------------------------------------------------------ Mamba2 SSD
def ssd_template(cfg):
    D, Din, N, nh = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.d_inner // cfg.ssm_headdim
    G = cfg.ssm_ngroups
    cw = cfg.conv1d_width
    return {
        "ln": ParamSpec((D,), ("embed",), init="zeros"),
        "wz": ParamSpec((D, Din), ("fsdp", "ff")),
        "wx": ParamSpec((D, Din), ("fsdp", "ff")),
        "wB": ParamSpec((D, G * N), ("fsdp", None)),
        "wC": ParamSpec((D, G * N), ("fsdp", None)),
        "wdt": ParamSpec((D, nh), ("fsdp", "heads")),
        "dt_bias": ParamSpec((nh,), ("heads",), init="zeros"),
        "A_log": ParamSpec((nh,), ("heads",), init="normal", scale=0.5),
        "Dskip": ParamSpec((nh,), ("heads",), init="ones"),
        "conv_x": ParamSpec((cw, Din), (None, "ff"), init="fan_in"),
        "conv_B": ParamSpec((cw, G * N), (None, None), init="fan_in"),
        "conv_C": ParamSpec((cw, G * N), (None, None), init="fan_in"),
        "norm": ParamSpec((Din,), ("ff",), init="zeros"),
        "wo": ParamSpec((Din, D), ("ff", "fsdp")),
    }


def _ssd_inputs(cfg, p, x, dtype, conv_state=None):
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    z = h @ cast(p["wz"], dtype)
    xs = h @ cast(p["wx"], dtype)
    Bm = h @ cast(p["wB"], dtype)
    Cm = h @ cast(p["wC"], dtype)
    dt = jax.nn.softplus(
        cast(h @ cast(p["wdt"], dtype), F32) + cast(p["dt_bias"], F32)
    )  # (B,S,nh) fp32
    states = {}
    for name in ("x", "B", "C"):
        t = {"x": xs, "B": Bm, "C": Cm}[name]
        st_in = None if conv_state is None else conv_state[name]
        t, st = _causal_conv(t, cast(p["conv_" + name], dtype), 0.0, st_in)
        t = jax.nn.silu(t)
        if name == "x":
            xs = t
        elif name == "B":
            Bm = t
        else:
            Cm = t
        states[name] = st
    nh = cfg.d_inner // cfg.ssm_headdim
    Bsz, S = x.shape[0], x.shape[1]
    xh = xs.reshape(Bsz, S, nh, cfg.ssm_headdim)
    G, N = cfg.ssm_ngroups, cfg.ssm_state
    Bg = Bm.reshape(Bsz, S, G, N)
    Cg = Cm.reshape(Bsz, S, G, N)
    A = -jnp.exp(cast(p["A_log"], F32))  # (nh,)
    return z, xh, Bg, Cg, dt, A, states


def ssd_block(cfg, p, x, dtype=DEFAULT_COMPUTE):
    """Chunked state-space-dual (Mamba2) mixer: quadratic within chunks,
    linear state recurrence across chunks. Returns (delta, decode cache)."""
    z, xh, Bg, Cg, dt, A, conv_states = _ssd_inputs(cfg, p, x, dtype)
    if USE_PALLAS_ATTENTION:  # kernelised mixer core (VMEM-resident state)
        from repro.kernels.ssd import ssd as ssd_kernel
        B_, S_ = x.shape[0], x.shape[1]
        yk, s_last = ssd_kernel(xh, dt, A, Bg, Cg,
                                chunk=min(cfg.ssm_chunk, S_))
        nh = cfg.d_inner // cfg.ssm_headdim
        y = cast(yk, F32) + cast(p["Dskip"], F32)[:, None] * cast(xh, F32)
        y = y.reshape(B_, S_, cfg.d_inner)
        y = rms_norm(cast(y, dtype) * jax.nn.silu(z), p["norm"], cfg.norm_eps)
        out = y @ cast(p["wo"], dtype)
        G = cfg.ssm_ngroups
        cache = {"s": s_last.reshape(B_, G, nh // G, cfg.ssm_headdim,
                                     cfg.ssm_state),
                 "conv": {k: cast(v, jnp.bfloat16)
                          for k, v in conv_states.items()}}
        return constrain(out, ("batch", "seq", "embed")), cache
    B, S, nh, hd = xh.shape
    G, N = Bg.shape[2], Bg.shape[3]
    L = min(cfg.ssm_chunk, S)
    if S % L:
        L = S
    nc = S // L
    hpg = nh // G  # heads per B/C group
    xc = xh.reshape(B, nc, L, nh, hd)
    Bc = Bg.reshape(B, nc, L, G, N)
    Cc = Cg.reshape(B, nc, L, G, N)
    dtc = dt.reshape(B, nc, L, nh)
    dA = dtc * A  # (B,nc,L,nh) log-decay per step
    lcum = jnp.cumsum(dA, axis=2)  # inclusive cumsum of log decay
    # --- within chunk (quadratic, attention-like) ---
    CB = jnp.einsum("bclgn,bcmgn->bcglm", Cc, Bc, preferred_element_type=F32)
    CB = CB.reshape(B, nc, G, 1, L, L)
    decay = lcum[:, :, :, None, :] - lcum[:, :, None, :, :]  # l_t - l_s (t q, s k)
    decay = jnp.transpose(decay, (0, 1, 4, 2, 3))  # (B,nc,nh,L,L) [t,s]
    tri = jnp.tril(jnp.ones((L, L), bool))
    M = jnp.where(tri, jnp.exp(decay), 0.0)
    M = M.reshape(B, nc, G, hpg, L, L) * CB  # (B,nc,G,hpg,L,L)
    du = dtc[..., None] * cast(xc, F32)  # (B,nc,L,nh,hd)
    duh = du.reshape(B, nc, L, G, hpg, hd)
    y_intra = jnp.einsum("bcghts,bcsghd->bctghd", M, duh)
    # --- chunk states ---
    lend = lcum[:, :, -1:, :]  # (B,nc,1,nh)
    sdecay = jnp.exp(lend - lcum)  # decay from s to chunk end
    S_c = jnp.einsum("bcsgn,bcsghd->bcghdn", Bc,
                     duh * sdecay.reshape(B, nc, L, G, hpg)[..., None])
    # --- recurrence across chunks ---
    chunk_decay = jnp.exp(lend[:, :, 0])  # (B,nc,nh)

    def step(s_prev, xs_):
        sc, cd = xs_
        s_new = s_prev * cd.reshape(B, G, hpg)[..., None, None] + sc
        return s_new, s_prev

    s0 = jnp.zeros((B, G, hpg, hd, N), F32)
    s_last, s_prevs = _maybe_unrolled_scan(
        step, s0,
        (S_c.transpose(1, 0, 2, 3, 4, 5), chunk_decay.transpose(1, 0, 2)), nc
    )
    s_prevs = s_prevs.transpose(1, 0, 2, 3, 4, 5)  # (B,nc,G,hpg,hd,N)
    qdecay = jnp.exp(lcum).reshape(B, nc, L, G, hpg)  # decay chunk-start -> t
    y_inter = jnp.einsum("bctgn,bcghdn->bctghd", Cc, s_prevs) * qdecay[..., None]
    y = (y_intra + y_inter).reshape(B, nc, L, nh, hd)
    y = y + cast(p["Dskip"], F32)[:, None] * cast(xc, F32)
    y = y.reshape(B, S, nh * hd)
    y = rms_norm(cast(y, dtype) * jax.nn.silu(z), p["norm"], cfg.norm_eps)
    out = y @ cast(p["wo"], dtype)
    cache = {"s": s_last,
             "conv": {k: cast(v, jnp.bfloat16) for k, v in conv_states.items()}}
    return constrain(out, ("batch", "seq", "embed")), cache


def ssd_decode(cfg, p, x, cache, dtype=DEFAULT_COMPUTE):
    """Single-step SSD recurrence. cache: {'s': (B,G,hpg,hd,N), 'conv':...}"""
    conv_state = cache["conv"]
    z, xh, Bg, Cg, dt, A, new_conv = _ssd_inputs(cfg, p, x, dtype, conv_state)
    B = x.shape[0]
    nh, hd = xh.shape[2], xh.shape[3]
    G, N = Bg.shape[2], Bg.shape[3]
    hpg = nh // G
    dA = jnp.exp(dt[:, 0] * A)  # (B,nh)
    du = dt[:, 0, :, None] * cast(xh[:, 0], F32)  # (B,nh,hd)
    duh = du.reshape(B, G, hpg, hd)
    s = cache["s"] * dA.reshape(B, G, hpg)[..., None, None] + jnp.einsum(
        "bgn,bghd->bghdn", Bg[:, 0], duh
    )
    y = jnp.einsum("bgn,bghdn->bghd", Cg[:, 0], s)
    y = y + cast(p["Dskip"], F32).reshape(G, hpg)[None, ..., None] * cast(
        xh[:, 0].reshape(B, G, hpg, hd), F32
    )
    y = y.reshape(B, 1, nh * hd)
    y = rms_norm(cast(y, dtype) * jax.nn.silu(z), p["norm"], cfg.norm_eps)
    out = y @ cast(p["wo"], dtype)
    return out, {"s": s, "conv": new_conv}


def ssd_cache_template(cfg, batch):
    nh = cfg.d_inner // cfg.ssm_headdim
    G, N, hd = cfg.ssm_ngroups, cfg.ssm_state, cfg.ssm_headdim
    cw = cfg.conv1d_width
    return {
        "s": ParamSpec((batch, G, nh // G, hd, N),
                       ("batch", None, "heads", None, None), F32, "zeros"),
        "conv": {
            "x": ParamSpec((batch, cw - 1, cfg.d_inner), ("batch", None, "ff"),
                           jnp.bfloat16, "zeros"),
            "B": ParamSpec((batch, cw - 1, G * N), ("batch", None, None),
                           jnp.bfloat16, "zeros"),
            "C": ParamSpec((batch, cw - 1, G * N), ("batch", None, None),
                           jnp.bfloat16, "zeros"),
        },
    }
