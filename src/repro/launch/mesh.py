"""Production mesh builders. Functions, not module constants — importing this
module never touches jax device state."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: the repo shards through
    ``with_sharding_constraint`` / GSPMD-style propagation, which rejects
    the ``Explicit`` axes ``make_mesh`` defaults to on current JAX."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False, ep: bool = False):
    """The 256-chip serving/training mesh (512 across two pods).

    ``ep=True`` returns the expert-parallel variant: the same chip count
    factored as ``(data, expert, model)`` so the ``experts`` logical axis
    (see docs/sharding.md) finally resolves to a physical mesh axis and
    MoE expert weights shard E-ways instead of staying 2D-sharded
    (``fsdp x ff``).
    """
    if ep:
        shape = (2, 8, 4, 4) if multi_pod else (16, 4, 4)
        axes = (("pod", "data", "expert", "model") if multi_pod
                else ("data", "expert", "model"))
    else:
        shape = (2, 16, 16) if multi_pod else (16, 16)
        axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1, expert: int = 1):
    """Small mesh over whatever devices exist (tests / examples).

    Requested axis sizes are clamped to the host's device count and then
    walked down to divisors, so the resulting grid is always constructible
    — e.g. asking for (16, 16) on a 1-device host yields (1, 1) instead of
    a shape/device-count mismatch. ``expert > 1`` asks for an EP host mesh
    ``(data, expert, model)``; the ``expert`` axis is only materialised
    when its clamped size exceeds 1, so 2-axis callers are unaffected.
    """
    n = max(1, len(jax.devices()))
    data = max(1, min(data, n))
    while n % data:
        data -= 1
    expert = max(1, min(expert, n // data))
    while (n // data) % expert:
        expert -= 1
    model = max(1, min(model, n // (data * expert)))
    while (n // (data * expert)) % model:
        model -= 1
    if expert > 1:
        return _auto_mesh((data, expert, model), ("data", "expert", "model"))
    return _auto_mesh((data, model), ("data", "model"))
