"""AI-based throughput estimator (paper Fig. 3 + Table I).

Branch 1: LSTM (hidden 124, window 30) over the 15 numerical KPMs.
Branch 2: CNN over the (2, 273*12, 14) IQ spectrogram:
    conv3x3(16) - relu - maxpool2 - conv3x3(32) - relu - maxpool2 -
    flatten - linear(hidden) - relu - dropout
Fusion: weighted sum with w = allocated-PRB ratio (KPMs are trustworthy
exactly when the UE's grant covers the band), then an FC regression head.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import lax

from repro.dist.sharding import constrain
from repro.models.template import ParamSpec, init_from_template

F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class EstimatorConfig:
    n_kpms: int = 15
    window: int = 30
    lstm_hidden: int = 124
    hidden: int = 124
    n_sc: int = 3276
    n_sym: int = 14
    cnn_ch: tuple = (16, 32)
    dropout: float = 0.1

    @property
    def cnn_flat(self) -> int:
        return self.cnn_ch[1] * (self.n_sc // 4) * (self.n_sym // 4)

    @property
    def row_bytes(self) -> int:
        """f32 bytes one input row holds at the forward's peak: the
        (2, n_sc, n_sym) IQ input, conv1's full-resolution activations,
        conv2's (after one 2x2 pool) and the (window, n_kpms) KPM window
        — what ``cnn_branch`` and ``lstm_branch`` below allocate."""
        px = self.n_sc * self.n_sym
        c1, c2 = self.cnn_ch
        return 4 * (2 * px + px * c1 + (px // 4) * c2
                    + self.window * self.n_kpms)


def estimator_template(e: EstimatorConfig):
    c1, c2 = e.cnn_ch
    h = e.lstm_hidden
    return {
        "lstm": {
            "wx": ParamSpec((e.n_kpms, 4 * h), (None, None)),
            "wh": ParamSpec((h, 4 * h), (None, None)),
            "b": ParamSpec((4 * h,), (None,), init="zeros"),
            "proj": ParamSpec((h, e.hidden), (None, None)),
        },
        "cnn": {
            "conv1": ParamSpec((3, 3, 2, c1), (None,) * 4),
            "b1": ParamSpec((c1,), (None,), init="zeros"),
            "conv2": ParamSpec((3, 3, c1, c2), (None,) * 4),
            "b2": ParamSpec((c2,), (None,), init="zeros"),
            "fc": ParamSpec((e.cnn_flat, e.hidden), (None, None)),
            "fcb": ParamSpec((e.hidden,), (None,), init="zeros"),
        },
        "head": {
            "w1": ParamSpec((e.hidden, e.hidden), (None, None)),
            "b1": ParamSpec((e.hidden,), (None,), init="zeros"),
            "w2": ParamSpec((e.hidden, 1), (None, None)),
            "b2": ParamSpec((1,), (None,), init="zeros"),
        },
    }


def init_estimator(e: EstimatorConfig, key):
    return init_from_template(estimator_template(e), key)


def lstm_branch(p, kpms):
    """kpms: (B, T, K) -> (B, hidden)."""
    B = kpms.shape[0]
    h0 = jnp.zeros((B, p["wh"].shape[0]), F32)
    c0 = jnp.zeros_like(h0)

    def cell(carry, x_t):
        h, c = carry
        z = x_t @ p["wx"] + h @ p["wh"] + p["b"]
        i, f, g, o = jnp.split(z, 4, axis=-1)
        c = jax.nn.sigmoid(f + 1.0) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
        h = jax.nn.sigmoid(o) * jnp.tanh(c)
        return (h, c), None

    (h, _), _ = lax.scan(cell, (h0, c0), kpms.transpose(1, 0, 2))
    return h @ p["proj"]


def cnn_branch(p, iq, *, dropout_rate=0.0, key=None):
    """iq: (B, 2, S, 14) -> (B, hidden)."""
    x = iq.transpose(0, 2, 3, 1)  # NHWC
    for w, b in ((p["conv1"], p["b1"]), (p["conv2"], p["b2"])):
        x = lax.conv_general_dilated(
            x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))
        x = jax.nn.relu(x + b)
        x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 2, 2, 1),
                              (1, 2, 2, 1), "VALID")
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(x @ p["fc"] + p["fcb"])
    if dropout_rate and key is not None:
        keep = jax.random.bernoulli(key, 1 - dropout_rate, x.shape)
        x = x * keep / (1 - dropout_rate)
    return x


def estimator_forward(e: EstimatorConfig, params, kpms, iq, alloc, *,
                      train: bool = False, key=None):
    """Returns predicted max throughput in Mbps, shape (B,).

    The B dim carries the logical ``batch`` axis: under an active
    ``dist.sharding`` ruleset (the fleet serving path, see
    ``repro.sim.serving``) the UE batch shards over the mesh's data axis
    while the weights — whose template axes are all ``None`` — stay
    replicated. Outside a ruleset every ``constrain`` is the identity, so
    training and CPU tests run this code unchanged.
    """
    kpms = constrain(kpms.astype(F32), ("batch", None, None))
    iq = constrain(iq.astype(F32), ("batch", None, None, None))
    alloc = constrain(alloc.astype(F32), ("batch",))
    v_t = lstm_branch(params["lstm"], kpms)
    v_s = cnn_branch(params["cnn"], iq,
                     dropout_rate=e.dropout if train else 0.0, key=key)
    w = jnp.clip(alloc, 0.0, 1.0)[:, None]
    fused = constrain(w * v_t + (1.0 - w) * v_s, ("batch", "embed"))
    h = jax.nn.relu(fused @ params["head"]["w1"] + params["head"]["b1"])
    out = h @ params["head"]["w2"] + params["head"]["b2"]
    return constrain(out[:, 0], ("batch",))
