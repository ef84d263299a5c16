"""Plain reference of the recurrent SSD throughput estimator, its weights,
and its cost.

Per report, the 16 inputs (the 15 KPMs under the fixed affine
normalisation and the clipped PRB allocation ratio) are projected to a
per-head value u (heads x head_dim), a step dt = softplus(.), and the
input and output vectors B, C (groups x state_dim). Each head's state
follows the Mamba-2 recurrence S_t = exp(dt_t A) S_{t-1} + dt_t B_t (x) u_t
with A = -exp(a_log), and reads out y_t = C_t . S_t. The readout
RMS-normalises y, scales it by a gain, appends the inputs, and applies a
two-layer head. Period t of a session is read at report WINDOW - 1 + t.

Written as a sequential scan over reports in ``jax.numpy``; it imports
nothing of the program. ``precision="highest"`` is the reference;
``"high"`` is the control: every contraction's operands are split into
two bfloat16 parts and the three leading products summed in float32,
which is what ``high`` (bf16_3x) does on a TPU, done explicitly so that it
is the same on every backend.
"""
from __future__ import annotations

import numpy as np

KPM_CENTER = np.array([-85, -10.5, 22, -3, 2, 13, 1, 15, 7, 14, 0.5, 400, 40,
                       8, 2], np.float32)
KPM_SCALE = np.array([5, 2, 5, 1, 1, 3, 1, 15, 7, 14, 0.5, 400, 60, 15, 6],
                     np.float32)
WINDOW = 30
BLOCK_ROWS = 4096


def n_feats(c):
    return c["n_kpms"] + 1


def shapes(c):
    f, nh, hd = n_feats(c), c["n_heads"], c["head_dim"]
    gn = c["n_groups"] * c["state_dim"]
    return {"in": {"wu": (f, nh * hd), "wdt": (f, nh), "bdt": (nh,),
                   "wb": (f, gn), "wc": (f, gn), "a_log": (nh,)},
            "norm": {"g": (nh * hd,)},
            "head": {"w1": (nh * hd + f, c["hidden"]), "b1": (c["hidden"],),
                     "w2": (c["hidden"], 1), "b2": (1,)}}


def init_params(c, key):
    """Random weights, normal / sqrt(fan-in); biases and a_log zero, the
    norm gain one (jit this)."""
    import jax
    import jax.numpy as jnp
    out = {}
    for g, leaves in shapes(c).items():
        out[g] = {}
        for name, shape in leaves.items():
            key, sub = jax.random.split(key)
            if name == "g":
                out[g][name] = jnp.ones(shape, jnp.float32)
            elif len(shape) == 1:
                out[g][name] = jnp.zeros(shape, jnp.float32)
            else:
                out[g][name] = (jax.random.normal(sub, shape, jnp.float32)
                                / np.float32(np.sqrt(shape[0])))
    return out


def calibrate(params, raw):
    """Map the readout so that ``raw`` has its 1st and 99th percentiles at
    5 and 125 Mbps (see the LSTM configuration's ``calibrate``)."""
    lo, hi = np.percentile(np.asarray(raw, np.float64), [1, 99])
    a = np.float32(120.0 / (hi - lo))
    head = params["head"]
    return dict(params, head=dict(head, w2=head["w2"] * a,
                                  b2=(head["b2"] - np.float32(lo)) * a
                                  + np.float32(5.0)))


def _contract(fn, a, b, precision):
    import jax.numpy as jnp
    if precision == "highest":
        return fn(a, b)
    if precision != "high":
        raise ValueError(f"precision {precision!r}")

    def split(x):
        hi = x.astype(jnp.bfloat16).astype(jnp.float32)
        return hi, (x - hi).astype(jnp.bfloat16).astype(jnp.float32)

    (ah, al), (bh, bl) = split(a), split(b)
    return fn(ah, bh) + fn(ah, bl) + fn(al, bh)


def forward_seq(c, p, feats, precision):
    """(B, S, F) report features -> (B, S) readouts, unclipped."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def dot(a, b):
        return _contract(lambda x, y: jnp.dot(x, y, precision="highest"), a,
                         b, precision)

    bsz, s, _ = feats.shape
    nh, hd, g, n = c["n_heads"], c["head_dim"], c["n_groups"], c["state_dim"]
    pi = p["in"]
    u = dot(feats, pi["wu"]).reshape(bsz, s, nh, hd)
    dt = jax.nn.softplus(dot(feats, pi["wdt"]) + pi["bdt"])
    bm = jnp.repeat(dot(feats, pi["wb"]).reshape(bsz, s, g, n), nh // g, 2)
    cm = jnp.repeat(dot(feats, pi["wc"]).reshape(bsz, s, g, n), nh // g, 2)
    a = -jnp.exp(pi["a_log"])

    def step(state, xs):  # state (B, nh, hd, N)
        u_t, dt_t, b_t, c_t = xs
        state = (state * jnp.exp(dt_t * a)[:, :, None, None]
                 + (dt_t[:, :, None] * u_t)[..., None] * b_t[:, :, None, :])
        y = _contract(lambda x, z: jnp.sum(x * z[:, :, None, :], -1), state,
                      c_t, precision)
        return state, y

    xs = tuple(jnp.swapaxes(v, 0, 1) for v in (u, dt, bm, cm))
    _, y = lax.scan(step, jnp.zeros((bsz, nh, hd, n), jnp.float32), xs)
    yf = jnp.swapaxes(y, 0, 1).reshape(bsz, s, nh * hd)
    yf = yf * lax.rsqrt(jnp.mean(yf * yf, -1, keepdims=True) + 1e-6)
    hp = p["head"]
    h = jnp.concatenate([yf * p["norm"]["g"], feats], -1)
    h = jnp.maximum(dot(h, hp["w1"]) + hp["b1"], 0.0)
    return (dot(h, hp["w2"]) + hp["b2"])[..., 0]


def features(episode):
    """(M, S, 16) float32 report features of every session's trace."""
    k = ((np.asarray(episode.kpms, np.float32) - KPM_CENTER) / KPM_SCALE
         ).astype(np.float32)
    a = np.clip(np.asarray(episode.alloc_ratio, np.float32), 0.0, 1.0)
    return np.concatenate(
        [k, np.broadcast_to(a[:, None, None], k.shape[:2] + (1,))], -1)


_JIT = {}


def raw_estimates(c, params, episode, precision):
    """(M, T) unclipped reference estimates of every session at every
    period of its trace."""
    return _forward_sessions(c, params, features(episode), precision,
                             episode.tp_mbps.shape[1])


def calibration_raw(c, params, episode):
    """Unclipped reference estimates of one block of sessions spread over
    the horizon (what ``calibrate`` maps into the table's range)."""
    m, t_steps = episode.tp_mbps.shape
    pick = np.linspace(0, m - 1, min(m, BLOCK_ROWS)).astype(int)
    return _forward_sessions(c, params, features(episode)[pick], "highest",
                             t_steps).ravel()


def _forward_sessions(c, params, feats, precision, t_steps):
    import jax
    import jax.numpy as jnp
    key = (repr(sorted((k, str(v)) for k, v in c.items())), precision)
    if key not in _JIT:
        _JIT[key] = jax.jit(lambda p, f: forward_seq(c, p, f, precision))
    fwd = _JIT[key]
    m = len(feats)
    out = np.empty((m, t_steps), np.float32)
    for s0 in range(0, m, BLOCK_ROWS):
        blk = feats[s0:s0 + BLOCK_ROWS]
        k = len(blk)
        if k < BLOCK_ROWS:
            blk = np.concatenate([blk, np.repeat(blk[:1], BLOCK_ROWS - k,
                                                 0)])
        y = np.asarray(fwd(params, jnp.asarray(blk)))
        out[s0:s0 + k] = y[:k, WINDOW - 1:WINDOW - 1 + t_steps]
    return out


def step_flops(c):
    """FLOPs of one report through the estimator: the four projections, the
    state update and read-out, the norm and the head."""
    f, nh, hd = n_feats(c), c["n_heads"], c["head_dim"]
    gn = c["n_groups"] * c["state_dim"]
    proj = 2 * f * (nh * hd + nh + 2 * gn)
    rec = 4 * nh * hd * c["state_dim"]
    head = 2 * (nh * hd + f) * c["hidden"] + 2 * c["hidden"]
    return float(proj + rec + 3 * nh * hd + head)


def weight_bytes(c):
    return 4.0 * sum(int(np.prod(s)) for g in shapes(c).values()
                     for s in g.values())


def decision_flops(c):
    """Forward FLOPs the model needs for one UE-period's decision: one
    recurrent step."""
    return step_flops(c)


def estimator_cost(c, horizon, calls):
    """(FLOPs, bytes) of the estimator's sequence passes over one horizon:
    every session's whole trace of ``horizon['steps']`` reports, read as 16
    float32 inputs and written as one float32 estimate, plus the weights
    once per call."""
    steps = horizon["sessions"] * horizon["steps"]
    return (step_flops(c) * steps,
            4.0 * (n_feats(c) + 1) * steps + weight_bytes(c) * calls)
