"""Plain reference of the windowed LSTM+CNN throughput estimator (paper
arXiv:2509.01906, Fig. 3 and Table I), its weights, and its cost.

Branch 1: an LSTM (forget-gate bias +1) over the WINDOW normalised KPM
reports before the period, projected to ``hidden``. Branch 2: a CNN over
the period's (2, n_sc, 14) IQ grid, conv3x3(16)-relu-maxpool2-conv3x3(32)-
relu-maxpool2-flatten-linear-relu. The two are mixed by the UE's PRB
allocation ratio and read out by a two-layer head. Straight ``jax.numpy``
at float32; it imports nothing of the program.

Two precisions: ``highest``, every contraction in float32; and ``bf16``,
the precision the configuration states: every matmul and convolution
operand rounded to bfloat16, products and sums in float32 (what XLA's
default precision does with float32 operands on a TPU), done explicitly
so that it is the same on every backend.
"""
from __future__ import annotations

import numpy as np

# the fixed affine KPM normalisation the estimator is defined on
KPM_CENTER = np.array([-85, -10.5, 22, -3, 2, 13, 1, 15, 7, 14, 0.5, 400, 40,
                       8, 2], np.float32)
KPM_SCALE = np.array([5, 2, 5, 1, 1, 3, 1, 15, 7, 14, 0.5, 400, 60, 15, 6],
                     np.float32)
BLOCK_ROWS = 64  # rows per reference forward: far below the program's peak


def cnn_flat(c):
    return c["cnn_ch"][1] * (c["n_sc"] // 4) * (c["n_sym"] // 4)


def shapes(c):
    h, k, c1, c2 = c["lstm_hidden"], c["n_kpms"], *c["cnn_ch"]
    return {
        "lstm": {"wx": (k, 4 * h), "wh": (h, 4 * h), "b": (4 * h,),
                 "proj": (h, c["hidden"])},
        "cnn": {"conv1": (3, 3, 2, c1), "b1": (c1,), "conv2": (3, 3, c1, c2),
                "b2": (c2,), "fc": (cnn_flat(c), c["hidden"]),
                "fcb": (c["hidden"],)},
        "head": {"w1": (c["hidden"], c["hidden"]), "b1": (c["hidden"],),
                 "w2": (c["hidden"], 1), "b2": (1,)},
    }


def init_params(c, key):
    """Random weights, normal / sqrt(fan-in), biases zero (jit this)."""
    import jax
    import jax.numpy as jnp
    out = {}
    for g, leaves in shapes(c).items():
        out[g] = {}
        for name, shape in leaves.items():
            key, sub = jax.random.split(key)
            if len(shape) == 1:
                out[g][name] = jnp.zeros(shape, jnp.float32)
            else:
                fan = int(np.prod(shape[:-1]))
                out[g][name] = (jax.random.normal(sub, shape, jnp.float32)
                                / np.float32(np.sqrt(fan)))
    return out


def calibrate(params, raw):
    """Map the readout so that ``raw`` (its output on this run's rows) has
    its 1st and 99th percentiles at 5 and 125 Mbps: the estimates then
    spread over the lookup table's 1..130 Mbps instead of sitting at the
    clip, where they would hide faults."""
    lo, hi = np.percentile(np.asarray(raw, np.float64), [1, 99])
    a = np.float32(120.0 / (hi - lo))
    head = params["head"]
    return dict(params, head=dict(head, w2=head["w2"] * a,
                                  b2=(head["b2"] - np.float32(lo)) * a
                                  + np.float32(5.0)))


def forward(c, p, kpms, iq, alloc, precision):
    """(B, WINDOW, 15) normalised KPMs, (B, 2, n_sc, 14) IQ, (B,) PRB
    ratios -> (B,) Mbps, unclipped."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    if precision not in ("highest", "bf16"):
        raise ValueError(f"precision {precision!r}")

    def rnd(x):
        if precision == "bf16":
            return x.astype(jnp.bfloat16).astype(jnp.float32)
        return x

    def dot(a, b):
        return jnp.dot(rnd(a), rnd(b), precision="highest")

    lp = p["lstm"]
    h = jnp.zeros((kpms.shape[0], c["lstm_hidden"]), jnp.float32)
    cell = jnp.zeros_like(h)
    for t in range(c["window"]):
        z = dot(kpms[:, t], lp["wx"]) + dot(h, lp["wh"]) + lp["b"]
        i, f, g, o = jnp.split(z, 4, axis=-1)
        cell = jax.nn.sigmoid(f + 1.0) * cell + jax.nn.sigmoid(i) * jnp.tanh(g)
        h = jax.nn.sigmoid(o) * jnp.tanh(cell)
    v_t = dot(h, lp["proj"])
    cp = p["cnn"]
    x = iq.transpose(0, 2, 3, 1)
    for w, b in ((cp["conv1"], cp["b1"]), (cp["conv2"], cp["b2"])):
        x = lax.conv_general_dilated(rnd(x), rnd(w), (1, 1), "SAME",
                                     dimension_numbers=("NHWC", "HWIO",
                                                        "NHWC"),
                                     precision="highest")
        x = jnp.maximum(x + b, 0.0)
        x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 2, 2, 1),
                              (1, 2, 2, 1), "VALID")
    v_s = jnp.maximum(dot(x.reshape(x.shape[0], -1), cp["fc"]) + cp["fcb"],
                      0.0)
    w = jnp.clip(alloc, 0.0, 1.0)[:, None]
    hp = p["head"]
    hh = jnp.maximum(dot(w * v_t + (1.0 - w) * v_s, hp["w1"]) + hp["b1"], 0.0)
    return (dot(hh, hp["w2"]) + hp["b2"])[:, 0]


def rows(c, episode, ue=None, per=None):
    """Yield (n_rows, kpms, iq, alloc) blocks of BLOCK_ROWS rows: every
    (UE, period) of ``episode`` in (UE, period) order, or the rows
    ``(ue[i], per[i])``. The last block is padded by repeating its first
    row."""
    k = ((np.asarray(episode.kpms, np.float32) - KPM_CENTER) / KPM_SCALE
         ).astype(np.float32)
    n, t_steps = episode.tp_mbps.shape
    win = c["window"]
    if ue is None:
        ue = np.repeat(np.arange(n), t_steps)
        per = np.tile(np.arange(t_steps), n)
    for s in range(0, len(ue), BLOCK_ROWS):
        u = ue[s:s + BLOCK_ROWS]
        t = per[s:s + BLOCK_ROWS]
        m = len(u)
        if m < BLOCK_ROWS:
            u = np.concatenate([u, np.full(BLOCK_ROWS - m, u[0])])
            t = np.concatenate([t, np.full(BLOCK_ROWS - m, t[0])])
        kp = k[u[:, None], t[:, None] + np.arange(win)[None]]
        yield (m, kp, np.asarray(episode.iq[u, t], np.float32),
               np.asarray(episode.alloc_ratio[u], np.float32))


def raw_estimates(c, params, episode, precision):
    """(N, T) unclipped reference estimates of every UE-period."""
    return _forward_rows(c, params, episode, precision).reshape(
        episode.tp_mbps.shape)


def calibration_raw(c, params, episode):
    """Unclipped reference estimates of one block of rows spread over the
    UEs and periods (what ``calibrate`` maps into the table's range)."""
    n, t_steps = episode.tp_mbps.shape
    ue = np.linspace(0, n - 1, BLOCK_ROWS).astype(int)
    return _forward_rows(c, params, episode, "highest", ue,
                         np.arange(BLOCK_ROWS) % t_steps)


def _forward_rows(c, params, episode, precision, ue=None, per=None):
    import jax.numpy as jnp
    fwd = _jitted(c, precision)
    outs = []
    for m, kp, iq, al in rows(c, episode, ue, per):
        out = np.asarray(fwd(params, jnp.asarray(kp), jnp.asarray(iq),
                             jnp.asarray(al)))
        outs.append(out[:m])
    return np.concatenate(outs)


_JIT = {}


def _jitted(c, precision):
    import jax
    key = (repr(sorted((k, str(v)) for k, v in c.items())), precision)
    if key not in _JIT:
        _JIT[key] = jax.jit(lambda p, k, i, a: forward(c, p, k, i, a,
                                                        precision))
    return _JIT[key]


def flops_per_row(c):
    """Multiply-adds x 2 of one forward row: the LSTM over the window, the
    two convolutions, the CNN's linear layer and the head."""
    h, k, c1, c2 = c["lstm_hidden"], c["n_kpms"], *c["cnn_ch"]
    px = c["n_sc"] * c["n_sym"]
    lstm = c["window"] * 2 * (k + h) * 4 * h + 2 * h * c["hidden"]
    conv = 2 * 9 * 2 * c1 * px + 2 * 9 * c1 * c2 * (px // 4)
    fc = 2 * cnn_flat(c) * c["hidden"]
    head = 2 * c["hidden"] * c["hidden"] + 2 * c["hidden"]
    return float(lstm + conv + fc + head)


def weight_bytes(c):
    return 4.0 * sum(int(np.prod(s)) for g in shapes(c).values()
                     for s in g.values())


def row_bytes(c):
    """f32 bytes one row brings in and takes out: the KPM window, the IQ
    grid, the PRB ratio and the estimate."""
    return 4.0 * (c["window"] * c["n_kpms"] + 2 * c["n_sc"] * c["n_sym"]
                  + 2)


def decision_flops(c):
    """Forward FLOPs the model needs for one UE-period's decision."""
    return flops_per_row(c)


def estimator_cost(c, horizon, calls):
    """(FLOPs, bytes) of the estimator forward over one horizon of
    ``horizon['ues']`` x ``horizon['periods']`` rows, in ``calls``
    forward executions that each read the weights once."""
    n_rows = horizon["ues"] * horizon["periods"]
    return (flops_per_row(c) * n_rows,
            row_bytes(c) * n_rows + weight_bytes(c) * calls)
