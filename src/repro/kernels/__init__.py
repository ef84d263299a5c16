"""Pallas TPU kernels for the perf-critical compute layers.

Each kernel ships three files: kernel.py (pl.pallas_call + BlockSpec VMEM
tiling), ops.py (jit'd public wrapper), ref.py (pure-jnp oracle). Every
``interpret`` argument defaults to None, which ``resolve_interpret`` turns
into the Pallas interpreter on the cpu backend and a Mosaic-compiled kernel
on tpu; pass True or False to force either.

  flash_attention  tiled online-softmax attention (causal / SWA / GQA)
  dcor             pairwise-distance tiles for distance correlation
  ssd              Mamba2 state-space-dual chunk scan (VMEM-resident state)
  quant            rowwise symmetric int8 quantisation
  featurize        fused KPM window extraction + normalisation
  lstm             fused LSTM-cell scan (fp32 and int8 serving variants)
  qmm              int8 x int8 -> int32 rowwise-scaled serving matmul
  segsum           masked batched segment reduction (sum / max)
"""
import jax


def resolve_interpret(interpret: bool | None = None) -> bool:
    """The ``interpret=`` flag a ``pallas_call`` gets, decided at trace time.

    An explicit bool is kept. None follows ``jax.default_backend()``: the
    Pallas interpreter on cpu, the compiled kernel on tpu. Any other
    backend has no Pallas TPU lowering and no reason to interpret, so it
    is an error rather than a silent fallback."""
    if interpret is not None:
        return interpret
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(f"no Pallas kernel path for backend {backend!r}: "
                       "pass interpret=True/False or use_kernel=False")


# the kernel packages import resolve_interpret from here, so they come last
from repro.kernels import (dcor, featurize,  # noqa: E402,F401
                           flash_attention, lstm, qmm, quant, segsum, ssd)
