"""Recurrent SSM throughput estimator with K-period forecasts.

The windowed estimator (``estimator.model``) re-reads a (WINDOW, 15) KPM
window plus an IQ spectrogram every report period — O(WINDOW) featurize
work and ~2 KB of window buffer per UE per period. This module is the
O(1)-per-report alternative: each UE carries a constant-size SSD
recurrent state (the Mamba-2 recurrence of ``repro.kernels.ssd``), one
report updates it in place, and the readout emits the *current*
throughput estimate plus K-period-ahead forecasts by rolling the
recurrence forward in closed form — so the split controller can act on
where the channel is going, not only where it is.

Two execution modes share one parameter set:

  * **sequence mode** (``ssm_forward_seq``) — a whole (B, S) trace
    through ``ssd_mixer`` (chunked kernel or jnp oracle, pinned equal by
    ``tests/test_kernels.py``): offline training, the frozen fleet path,
    and state warmup;
  * **step mode** (``ssm_step``) — one report through ``ssd_step``: the
    online serving loop and the slot pool. A scan of steps reproduces
    the sequence pass (allclose; different accumulation order), pinned
    by ``tests/test_estimator_ssm.py``.

Inputs are the 15 normalized KPMs plus the PRB allocation ratio, and —
with ``SSMConfig(include_iq=True)`` — ``N_IQ_FEATS`` summary channels
of the period's IQ spectrogram snapshot (``iq_features``). The snapshot
is an instantaneous input, not carried history, so the O(1)-per-report
cost and the constant state are untouched; without it the estimator is
blind exactly where KPMs are blind (low-load + zero-overlap
interference, the paper's Fig. 2b regime). The trade-off is documented
in docs/estimator.md.

Forecast rollout, in closed form: holding the last input u, per head
``y_{t+j} = d^j y_t + (sum_{i<j} d^i) * dt * (C.B) * u`` with
``d = exp(dt*A)`` — K extra readouts, no extra state. ``forecast_policy``
collapses the (K+1) forecasts to the one effective throughput the
(unchanged) controller consumes; ``forecast_horizon=0`` is pinned
bit-identical to the plain current estimate.

Precision: both modes trace every contraction at fp32 (``_fp32``) on
every backend, as the ssd kernel does inside. XLA's TPU default rounds
f32 matmul operands to bf16, and the readout's RMS norm magnifies that
rounding on near-zero mixer outputs: at random weights the bf16 jnp path
and the fp32 kernel path forecast up to 12.3 Mbps apart on a TPU v5e.
"""
from __future__ import annotations

import dataclasses
from functools import partial, wraps

import jax
import jax.numpy as jnp
import numpy as np

from repro.channel import kpm as kpmmod
from repro.channel.scenarios import WINDOW
from repro.dist.sharding import constrain
from repro.kernels.ssd import ssd_mixer, ssd_step
from repro.models.template import ParamSpec, init_from_template

F32 = jnp.float32

FORECAST_POLICIES = ("last", "min", "discount")
N_IQ_FEATS = 6  # summary channels ``iq_features`` derives per snapshot


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    """Hashable config of the recurrent estimator (a jit static arg).

    ``forecast_horizon`` K adds K rolled-forward readouts per estimate;
    ``forecast_policy`` reduces them to one effective Mbps ("last" = the
    current estimate, "min" = plan for the worst forecast period,
    "discount" = gamma-weighted mean). ``use_kernel`` routes sequence
    passes through the Pallas SSD kernel instead of the jnp oracle —
    keep False on CPU hosts (interpret-mode Pallas is for parity tests);
    the O(1) step path is jnp either way, so it shards under a mesh.
    """

    n_kpms: int = 15
    n_heads: int = 4
    head_dim: int = 8
    n_groups: int = 1
    state_dim: int = 8  # N — SSD state columns per group
    hidden: int = 32  # readout MLP width
    forecast_horizon: int = 0  # K periods rolled forward
    forecast_policy: str = "last"
    forecast_discount: float = 0.8
    chunk: int = 64  # ssd_mixer chunk length for sequence passes
    use_kernel: bool = False
    # append per-period IQ summary channels (``iq_features``) to the
    # report row: the spectrogram snapshot is an *instantaneous* input —
    # no carried history — so the O(1)-per-report serving cost and the
    # constant state are unchanged; this is what lets the recurrent
    # estimator see interference where KPMs are blind (low-load jamming,
    # the paper's Fig. 2b regime).
    include_iq: bool = False

    def __post_init__(self):
        if self.n_heads % self.n_groups:
            raise ValueError(f"n_heads ({self.n_heads}) must divide into "
                             f"n_groups ({self.n_groups})")
        if self.forecast_policy not in FORECAST_POLICIES:
            raise ValueError(f"forecast_policy must be one of "
                             f"{FORECAST_POLICIES}: {self.forecast_policy!r}")
        if self.forecast_horizon < 0:
            raise ValueError(
                f"forecast_horizon must be >= 0: {self.forecast_horizon}")

    @property
    def n_feats(self) -> int:
        # 15 KPMs + the PRB allocation ratio (+ IQ summary channels)
        return self.n_kpms + 1 + (N_IQ_FEATS if self.include_iq else 0)

    @property
    def heads_per_group(self) -> int:
        return self.n_heads // self.n_groups

    def state_shape(self) -> tuple:
        """Per-UE recurrent state (the ssd carried-state layout)."""
        return (self.n_groups, self.heads_per_group, self.head_dim,
                self.state_dim)

    def state_bytes(self) -> int:
        """f32 bytes of recurrent state one UE costs the serving fleet."""
        return int(np.prod(self.state_shape())) * 4


def ssm_template(c: SSMConfig):
    f, nh, hd = c.n_feats, c.n_heads, c.head_dim
    gn = c.n_groups * c.state_dim
    return {
        "in": {
            "wu": ParamSpec((f, nh * hd), (None, None)),
            "wdt": ParamSpec((f, nh), (None, None)),
            # softplus(0) ~ 0.69 -> per-period decay ~ exp(-0.69) at A=-1:
            # a half-life of one report period before training moves it
            "bdt": ParamSpec((nh,), (None,), init="zeros"),
            "wb": ParamSpec((f, gn), (None, None)),
            "wc": ParamSpec((f, gn), (None, None)),
            "a_log": ParamSpec((nh,), (None,), init="zeros"),  # A = -e^a
        },
        # RMSNorm gain on the mixer output: the SSD state's steady-state
        # magnitude is input- and decay-dependent (decay -> 1 grows it
        # without bound), so the readout sees a normalized y no matter
        # where the dynamics settle — same role as Mamba-2's post-mixer
        # norm, and what keeps length extrapolation + online adaptation
        # stable.
        "norm": {"g": ParamSpec((nh * hd,), (None,), init="ones")},
        "head": {
            "w1": ParamSpec((nh * hd + f, c.hidden), (None, None)),
            "b1": ParamSpec((c.hidden,), (None,), init="zeros"),
            "w2": ParamSpec((c.hidden, 1), (None, None)),
            "b2": ParamSpec((1,), (None,), init="zeros"),
        },
    }


def init_ssm(c: SSMConfig, key):
    return init_from_template(ssm_template(c), key)


def ssm_state_init(c: SSMConfig, batch_shape: tuple = ()) -> jax.Array:
    return jnp.zeros(tuple(batch_shape) + c.state_shape(), F32)


def iq_features(iq: np.ndarray) -> np.ndarray:
    """(..., 2, n_sc, 14) IQ spectrogram snapshots -> (..., N_IQ_FEATS)
    summary channels, O(n_sc) per snapshot (no history, no learned
    weights): total log-power, narrowband peak, symbol burstiness (tdd),
    high/low subband contrast (cci), tail power, and occupancy — the
    interference signatures the windowed estimator's CNN learns from the
    same snapshot."""
    x = np.asarray(iq, np.float32)
    p = x[..., 0, :, :] ** 2 + x[..., 1, :, :] ** 2  # (..., n_sc, 14)
    n_sc = p.shape[-2]
    psc = p.mean(-1)  # (..., n_sc) per-subcarrier power
    psym = p.mean(-2)  # (..., 14)  per-symbol power
    lo = psc[..., :n_sc // 2].mean(-1)
    hi = psc[..., n_sc // 2:].mean(-1)
    med = np.median(p, axis=(-2, -1))
    feats = np.stack([
        np.log1p(p.mean((-2, -1))),
        np.log1p(psc.max(-1)),
        np.log1p(psym).std(-1),
        np.log1p(hi) - np.log1p(lo),
        np.log1p(np.quantile(p, 0.95, axis=(-2, -1))),
        (p > 2.0 * med[..., None, None] + 1e-6).mean((-2, -1)),
    ], axis=-1)
    return feats.astype(np.float32)


def iq_columns(iq: np.ndarray, s: int) -> np.ndarray:
    """(N, S, N_IQ_FEATS) f32 host columns of an ``include_iq`` trace of
    S reports from its (N, T, 2, n_sc, 14) per-period snapshots.

    IQ exists only for the T report periods; period ``t``'s
    ``iq_features`` land on the sequence index the estimator reads for
    period ``t`` (``WINDOW - 1 + t``), and the warm-up prefix carries
    zeros (no estimate is read there)."""
    iq = np.asarray(iq)
    n, t = iq.shape[:2]
    if t + WINDOW > s:
        raise ValueError(f"iq has {t} periods but the trace only "
                         f"fits {s - WINDOW}")
    iqf = np.zeros((n, s, N_IQ_FEATS), np.float32)
    iqf[:, WINDOW - 1:WINDOW - 1 + t] = iq_features(iq)
    return iqf


def rounded_quotient(q, d, s):
    """The correctly rounded f32 ``d / s``, as NumPy divides, from a
    ``q`` within an ulp or two of it. XLA's TPU backend divides by
    multiplying with the divisor's reciprocal, which leaves about a fifth
    of the normalized KPMs one ulp off (TPU v5e). The remainder
    ``d - q * s`` is formed exactly, with ``q`` cut at 12 significant
    bits so that each partial product is exact (for divisors of at most
    12 significant bits; ``KPM_SCALE``'s have at most 9), and then
    settles the last bit. Where ``q`` is already correctly rounded, as on
    the CPU, the result is ``q``."""
    hi = jax.lax.bitcast_convert_type(
        jax.lax.bitcast_convert_type(q, jnp.int32) & -4096, F32)
    rem = (d - hi * s) - (q - hi) * s
    return q + rem / s


def report_alloc(alloc_ratio: np.ndarray, s: int) -> np.ndarray:
    """(N * S,) f32 PRB allocation ratio of each report, flat: each of
    the N sessions' ratio repeated over its S reports."""
    return np.repeat(np.asarray(alloc_ratio, np.float32), s)


@partial(jax.jit, static_argnums=0)
def report_features(shape, kpms, alloc, center, scale, extra=None):
    """(B, S, F) f32 report-stream features on the device for ``shape`` =
    (B, S): B sessions of S reports. ``kpms`` holds their raw KPM reports
    (B x S x 15 values) and ``alloc`` the PRB allocation ratio of each
    report (B x S, ``report_alloc``), each flat or shaped. Channels 0-14
    are the fixed-affine KPM normalisation ``(kpms - center) / scale``,
    channel 15 ``clip(alloc, 0, 1)`` — everything the recurrent estimator
    consumes (no windows) — then ``extra`` (B, S, E) columns, if given
    (``iq_columns``). The single definition of the layout.

    The engine copies ``kpms`` in flat: a (B, S, 15) f32 array's copy to
    a TPU relayouts its 15-wide minor dimension on the host, which takes
    about twice as long (TPU v5e, 8192 x 56 reports: 12.7 ms against 5.6
    ms flat). ``center`` and ``scale`` are ``channel.kpm.KPM_CENTER`` /
    ``KPM_SCALE``, passed as operands so XLA cannot fold the division into
    constants; ``rounded_quotient`` makes it the correctly rounded one, so
    the features are bit-equal to the f32 NumPy form on every backend."""
    b, s = shape
    d = kpms.reshape(b, s, -1).astype(F32) - center
    k = rounded_quotient(d / scale, d, scale)
    cols = [k, jnp.clip(alloc.reshape(b, s, 1).astype(F32), 0.0, 1.0)]
    if extra is not None:
        cols.append(extra.astype(F32))
    return jnp.concatenate(cols, axis=-1)


def episode_features(kpms: np.ndarray, alloc_ratio: np.ndarray,
                     iq: np.ndarray | None = None) -> np.ndarray:
    """(N, S, F) f32 report-stream features from raw (N, S, 15) KPM
    reports and (N,) PRB ratios, as a host array: the host wrapper around
    ``report_features`` for the callers that need one (the mesh path,
    training, the online warm-up prefix).

    ``iq`` (N, T, 2, n_sc, 14) — the per-period spectrogram snapshots of
    an ``include_iq`` episode — appends ``N_IQ_FEATS`` summary channels
    (``iq_columns``)."""
    kpms = np.asarray(kpms, np.float32)
    n, s = kpms.shape[:2]
    extra = None if iq is None else iq_columns(iq, s)
    return np.asarray(report_features(
        (n, s), kpms, report_alloc(alloc_ratio, s), kpmmod.KPM_CENTER,
        kpmmod.KPM_SCALE, extra))


def _project(c: SSMConfig, params, feats):
    """feats (..., F) -> (u (..., nh, hd), dt (..., nh), Bm/Cm
    (..., G, N), A (nh,))."""
    p = params["in"]
    lead = feats.shape[:-1]
    u = (feats @ p["wu"]).reshape(lead + (c.n_heads, c.head_dim))
    dt = jax.nn.softplus(feats @ p["wdt"] + p["bdt"])
    bm = (feats @ p["wb"]).reshape(lead + (c.n_groups, c.state_dim))
    cm = (feats @ p["wc"]).reshape(lead + (c.n_groups, c.state_dim))
    return u, dt, bm, cm, -jnp.exp(params["in"]["a_log"])


def _readout(c: SSMConfig, params, y, feats):
    """(y (..., nh, hd), feats (..., F)) -> (...) Mbps."""
    p = params["head"]
    yf = y.reshape(y.shape[:-2] + (c.n_heads * c.head_dim,))
    yf = yf * jax.lax.rsqrt(jnp.mean(yf * yf, -1, keepdims=True) + 1e-6)
    h = jnp.concatenate([yf * params["norm"]["g"], feats], -1)
    h = jax.nn.relu(h @ p["w1"] + p["b1"])
    return (h @ p["w2"] + p["b2"])[..., 0]


def _forecast_readout(c: SSMConfig, params, y, u, dt, bm, cm, feats, A):
    """(..., K+1) readouts: index 0 is the current estimate, index j the
    j-period-ahead forecast from the closed-form rollout (input held)."""
    outs = [_readout(c, params, y, feats)]
    if c.forecast_horizon:
        d = jnp.exp(dt * A)  # (..., nh) per-head one-period decay
        cb = jnp.sum(cm * bm, -1)  # (..., G) — C.B contraction
        cbh = jnp.repeat(cb, c.heads_per_group, axis=-1)  # groups -> heads
        inj = (dt * cbh)[..., None] * u  # the held input's per-step push
        yj = y
        for _ in range(c.forecast_horizon):
            yj = d[..., None] * yj + inj
            outs.append(_readout(c, params, yj, feats))
    return jnp.stack(outs, -1)


def _fp32(fn):
    """Trace ``fn`` with ``highest`` (fp32) default matmul precision."""
    @wraps(fn)
    def traced(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kwargs)
    return traced


@partial(jax.jit, static_argnums=0)
@_fp32
def ssm_forward_seq(c: SSMConfig, params, feats):
    """Sequence mode: feats (B, S, F) -> ((B, S, K+1) forecasts, final
    state (B,) + ``c.state_shape()``).

    The whole trace runs through one ``ssd_mixer`` call (chunk =
    ``min(c.chunk, S)``; the trace is padded to a chunk multiple with
    dt=0 rows, which leave the state untouched — exp(0)=1 decay, zero
    input — and are sliced off the outputs). Step ``s``'s forecasts see
    reports 0..s, so period ``t`` of an EpisodeBatch trace reads index
    ``WINDOW + t - 1``."""
    feats = constrain(feats.astype(F32), ("batch", None, None))
    u, dt, bm, cm, A = _project(c, params, feats)
    s = feats.shape[1]
    chunk = min(c.chunk, s)
    pad = -s % chunk
    if pad:
        pz = lambda a: jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),)
                               * (a.ndim - 2))
        y, state = ssd_mixer(pz(u), pz(dt), A, pz(bm), pz(cm), chunk=chunk,
                             use_kernel=c.use_kernel)
        y = y[:, :s]
    else:
        y, state = ssd_mixer(u, dt, A, bm, cm, chunk=chunk,
                             use_kernel=c.use_kernel)
    fc = _forecast_readout(c, params, y, u, dt, bm, cm, feats, A)
    return constrain(fc, ("batch", None, None)), state


@partial(jax.jit, static_argnums=0)
@_fp32
def ssm_step(c: SSMConfig, params, state, feats):
    """Step mode: one report per UE, O(1) in history length.

    ``state`` (B,) + ``c.state_shape()``; ``feats`` (B, F). Returns
    (new state, (B, K+1) forecasts). Pure jnp (``ssd_step``), so under a
    ``dist.sharding`` ruleset both the state and the report batch shard
    over the mesh's ``batch`` axis with replicated weights."""
    feats = constrain(feats.astype(F32), ("batch", None))
    state = constrain(state.astype(F32), ("batch",) + (None,) * 4)
    u, dt, bm, cm, A = _project(c, params, feats)
    y, state = ssd_step(u, dt, A, bm, cm, state)
    fc = _forecast_readout(c, params, y, u, dt, bm, cm, feats, A)
    return (constrain(state, ("batch",) + (None,) * 4),
            constrain(fc, ("batch", None)))


def reduce_forecasts(c: SSMConfig, fc: np.ndarray) -> np.ndarray:
    """(..., K+1) forecasts -> (...) effective Mbps per the policy.

    Host-side numpy on purpose: the reduce is trivial, and keeping it out
    of the jitted programs means every engine path (batch, online, pool,
    sharded) collapses forecasts identically. K=0 returns column 0
    unchanged under every policy — the bit-identity pin."""
    fc = np.asarray(fc)
    if c.forecast_horizon == 0 or c.forecast_policy == "last":
        return fc[..., 0]
    if c.forecast_policy == "min":
        return fc.min(axis=-1)
    w = c.forecast_discount ** np.arange(fc.shape[-1], dtype=np.float64)
    w /= w.sum()
    return fc @ w.astype(fc.dtype)


def ssm_warm_state(c: SSMConfig, params, feats_prefix) -> jax.Array:
    """Final recurrent state after consuming a (B, W, F) warmup prefix —
    how the serving paths seed a UE's state from the WINDOW-1 reports
    that precede its first estimate."""
    _, state = ssm_forward_seq(c, params, jnp.asarray(feats_prefix))
    return state
