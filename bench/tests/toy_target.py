"""A toy split target for the harness's tests: 5 split points and every
served-tail hook (``harness/splitting.py``), at a size a test can hold.

Its tail is one linear layer over the boundary activations that the
offloading UEs of a horizon send. No program serves it, so ``call_kwargs``
adds nothing to the call; ``check`` runs the tail on the device, as a
served tail would, and compares it with the float64 plain reference on the
rows of the UE-periods that the program split (split 0 .. 3).
"""
from __future__ import annotations

import numpy as np

WIDTH, OUT = 4, 3
FLOPS_HEAD = [0.0, 1e9, 2e9, 3e9, 4e9]  # cumulative, per split point
DATA_BYTES = [6e5, 4e5, 2e5, 1e5, 4e3]
PRIVACY = [1.0, 0.8, 0.5, 0.3, 0.2]

names = ("toy_tail_gap",)
calls = []  # the hooks' calls, in order, for the tests to read


def profile(spec):
    calls.append("profile")
    return np.array(FLOPS_HEAD), np.array(DATA_BYTES), np.array(PRIVACY)


def name(spec):
    return "toy-5"


def init_params(spec, key):
    import jax
    calls.append("init_params")
    return {"w": jax.random.normal(key, (WIDTH, OUT))}


def payload(spec, params, target_mix, hz, rng):
    calls.append("payload")
    return rng.standard_normal((target_mix["rows"], WIDTH)).astype(np.float32)


def call_kwargs(spec, params, payload):
    calls.append("call_kwargs")
    return {}


def tail(params, x):
    """The served tail, on the device."""
    import jax
    import jax.numpy as jnp
    return np.asarray(jnp.dot(x, params["w"],
                              precision=jax.lax.Precision.HIGHEST))


def check(spec, params, hz, payload, result):
    calls.append("check")
    split = np.asarray(result.splits)
    offloaded = int(((split >= 0) & (split < len(FLOPS_HEAD) - 1)).sum())
    x = payload[:max(1, min(offloaded, len(payload)))]
    ref = x.astype(np.float64) @ np.asarray(params["w"], np.float64)
    return {"toy_tail_gap": float(np.abs(tail(params, x) - ref).max())}
