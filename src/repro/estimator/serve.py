"""int8 estimator serving: quantized weights, integer matmuls, fp32 out.

The frozen serving path's FLOPs are dominated by dense matmuls — the
LSTM's 30-step recurrence and the FC layers (LSTM projection, CNN fc,
regression head). This module pre-quantizes those weights rowwise per
output channel with the existing ``kernels/quant`` quantizer and serves
them through the int8 Pallas kernels (``kernels/lstm``'s quantized scan,
``kernels/qmm``'s int8 x int8 -> int32 matmul): one quarter the weight
bytes, integer MXU throughput, activations quantized rowwise on the fly.
The two 3x3 convolutions (a negligible FLOP share with no matmul form)
and all biases stay fp32.

Numerics: integer accumulation is exact, so ``use_kernel`` only moves
*where* the math runs — the Pallas kernels and the jnp oracles produce
bit-identical outputs, which is also why serving meshes (where GSPMD
cannot partition a ``pallas_call``) run ``use_kernel=False`` with
nothing lost. The int8-vs-fp32 accuracy cost is pinned by
``tests/test_sim_fused.py`` and measured by ``benchmarks/fleet.py``.
The fp32 path (``quant=None`` everywhere) never enters this module.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.dist.sharding import constrain
from repro.estimator.model import EstimatorConfig
from repro.kernels.lstm.ops import lstm_hidden_q
from repro.kernels.qmm.ops import int8_matmul, quantize_weight

F32 = jnp.float32

QUANT_MODES = (None, "int8")


def check_quant(quant) -> None:
    """Validate a ``quant=`` argument (shared by every serving entry)."""
    if quant not in QUANT_MODES:
        raise ValueError(f"quant must be one of {QUANT_MODES}: {quant!r}")


def quantize_estimator(params, *, use_kernel: bool = True,
                       interpret: bool | None = None):
    """fp32 estimator params -> the int8 serving tree.

    Every dense matmul weight (LSTM input/recurrent, LSTM projection, CNN
    fc, both head layers) becomes an ((OUT, IN) int8, (OUT, 1) f32 scale)
    pair — ``kernels/quant`` rowwise quantization of ``w.T``, one scale
    per output channel. Biases and the 3x3 conv filters stay fp32. The
    tree is a plain pytree (tuples for quantized leaves), so
    ``serving.replicate_params`` and jit treat it like any params tree."""
    q = partial(quantize_weight, use_kernel=use_kernel, interpret=interpret)
    lstm, cnn, head = params["lstm"], params["cnn"], params["head"]
    return {
        "lstm": {"wx": q(lstm["wx"]), "wh": q(lstm["wh"]),
                 "b": lstm["b"], "proj": q(lstm["proj"])},
        "cnn": {"conv1": cnn["conv1"], "b1": cnn["b1"],
                "conv2": cnn["conv2"], "b2": cnn["b2"],
                "fc": q(cnn["fc"]), "fcb": cnn["fcb"]},
        "head": {"w1": q(head["w1"]), "b1": head["b1"],
                 "w2": q(head["w2"]), "b2": head["b2"]},
    }


def _conv_trunk(p, iq):
    """The fp32 conv/pool trunk of ``model.cnn_branch`` (everything up to
    the fc layer, which the int8 path runs quantized)."""
    x = iq.transpose(0, 2, 3, 1)  # NHWC
    for w, b in ((p["conv1"], p["b1"]), (p["conv2"], p["b2"])):
        x = lax.conv_general_dilated(
            x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))
        x = jax.nn.relu(x + b)
        x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 2, 2, 1),
                              (1, 2, 2, 1), "VALID")
    return x.reshape(x.shape[0], -1)


def estimator_forward_int8(e: EstimatorConfig, qparams, kpms, iq, alloc, *,
                           use_kernel: bool = True,
                           interpret: bool | None = None):
    """The serving forward on a ``quantize_estimator`` tree: (B,) Mbps.

    Mirrors ``model.estimator_forward`` (inference mode) with every dense
    matmul routed through the int8 kernels; the ``constrain`` annotations
    are kept so the ``use_kernel=False`` form shards under a serving mesh
    exactly like the fp32 program."""
    kpms = constrain(kpms.astype(F32), ("batch", None, None))
    iq = constrain(iq.astype(F32), ("batch", None, None, None))
    alloc = constrain(alloc.astype(F32), ("batch",))
    lq, cq, hq = qparams["lstm"], qparams["cnn"], qparams["head"]
    mm = partial(int8_matmul, use_kernel=use_kernel, interpret=interpret)
    h = lstm_hidden_q(kpms, lq["wx"][0], lq["wx"][1], lq["wh"][0],
                      lq["wh"][1], lq["b"], use_kernel=use_kernel,
                      interpret=interpret)
    v_t = mm(h, *lq["proj"])
    v_s = jax.nn.relu(mm(_conv_trunk(cq, iq), *cq["fc"]) + cq["fcb"])
    w = jnp.clip(alloc, 0.0, 1.0)[:, None]
    fused = constrain(w * v_t + (1.0 - w) * v_s, ("batch", "embed"))
    hh = jax.nn.relu(mm(fused, *hq["w1"]) + hq["b1"])
    out = mm(hh, *hq["w2"]) + hq["b2"]
    return constrain(out[:, 0], ("batch",))


@partial(jax.jit, static_argnums=0,
         static_argnames=("use_kernel", "interpret"))
def fwd_int8(e, qparams, kpms, iq, alloc, *, use_kernel=True,
             interpret=None):
    """One jitted int8 inference forward (the ``estimator.train.fwd``
    counterpart the fused engine path calls per chunk)."""
    return estimator_forward_int8(e, qparams, kpms, iq, alloc,
                                  use_kernel=use_kernel, interpret=interpret)


def predict_int8(e: EstimatorConfig, qparams, data: dict,
                 batch: int | None = 64, *, use_kernel: bool = True,
                 interpret: bool | None = None) -> np.ndarray:
    """int8 twin of ``estimator.train.predict`` — Mbps for every row."""
    outs = []
    n = len(data["tp"])
    batch = max(n, 1) if batch is None else batch
    for i in range(0, n, batch):
        outs.append(np.asarray(fwd_int8(
            e, qparams, jnp.asarray(data["kpms"][i:i + batch]),
            jnp.asarray(data["iq"][i:i + batch]),
            jnp.asarray(data["alloc"][i:i + batch]),
            use_kernel=use_kernel, interpret=interpret)))
    return np.concatenate(outs)
