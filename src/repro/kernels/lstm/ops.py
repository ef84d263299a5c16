"""jit dispatch for the fused LSTM-cell scan (fp32 + int8 serving)."""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels.lstm.kernel import lstm_scan, lstm_scan_q
from repro.kernels.lstm.ref import lstm_scan_q_ref, lstm_scan_ref


@partial(jax.jit, static_argnames=("use_kernel", "interpret"))
def lstm_hidden(kpms, wx, wh, b, *, use_kernel: bool = True,
                interpret: bool | None = None):
    """(B, T, K) windows -> (B, H) final hidden state, fp32.

    The estimator's temporal branch minus its output projection:
    ``lstm_hidden(...) @ proj == lstm_branch(p, kpms)`` to f32 tolerance."""
    if use_kernel:
        return lstm_scan(kpms, wx, wh, b, interpret=interpret)
    return lstm_scan_ref(kpms, wx, wh, b)


@partial(jax.jit, static_argnames=("use_kernel", "interpret"))
def lstm_hidden_q(kpms, wxq, wxs, whq, whs, b, *, use_kernel: bool = True,
                  interpret: bool | None = None):
    """int8-serving variant: pre-quantized weights (``quantize_rows(w.T)``
    layout), per-step dynamic activation quantization."""
    if use_kernel:
        return lstm_scan_q(kpms, wxq, wxs, whq, whs, b, interpret=interpret)
    return lstm_scan_q_ref(kpms, wxq, wxs, whq, whs, b)
