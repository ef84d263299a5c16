"""GSPMD-style logical-axis sharding rules (see dist/README.md).

Tensors everywhere in the codebase name their dimensions with *logical*
axis names; a :class:`Ruleset` maps those names onto *mesh* axes. The
model code never mentions a mesh: it calls :func:`constrain` with logical
names, and the active ruleset (installed by :func:`use_rules`) decides
what — if anything — that means physically.

Contract (load-bearing for the CPU test suite):

* **No active ruleset** — ``constrain`` is the identity, ``axis_size``
  returns 1, ``kv_repeat`` returns 1. Pure-CPU tests and examples run
  the exact same model code with zero sharding machinery.
* **Active ruleset** — ``constrain`` lowers to
  ``jax.lax.with_sharding_constraint`` with a ``NamedSharding`` derived
  from the rules. A logical axis silently falls back to replicated when
  (a) its mapped mesh axes are absent from the mesh (e.g. "pod" on a
  2-axis host mesh), (b) the dimension size is not divisible by the
  mapped mesh size, or (c) an earlier dimension of the same tensor
  already claimed the mesh axis (first dimension wins).

Rules are resolved per call, so per-deployment overrides (e.g. serving's
``{"fsdp": None}`` weight replication, or ``{"cache_seq": "model"}`` KV
cache sequence sharding) are one dict away — see
``launch/steps.serve_overrides``.

The active ruleset lives in a ``contextvars.ContextVar`` so it is safe
under threads and under jax tracing (tracing happens in the thread that
entered ``use_rules``; the ruleset is captured at trace time, which is
exactly the AOT-lowering semantics the dry-run relies on).
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import math
from typing import Mapping, Optional, Tuple, Union

import jax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

# A rule value: replicated (None), one mesh axis, or a tuple of mesh axes
# (sharded over their product, major first).
MeshAxes = Union[None, str, Tuple[str, ...]]

# Logical axis -> mesh axes. This table is the whole sharding policy:
#   activations: batch is data-parallel across pods; seq/ctx replicated by
#     default (override ctx -> "model" for Megatron-style sequence
#     parallelism); ctx_attn is the context-parallel fallback used when a
#     config's head count cannot shard over "model".
#   params: fsdp is the ZeRO-3 axis; heads/kv/ff/vocab are the tensor-
#     parallel contractions on "model"; experts maps to the "expert" mesh
#     axis carried by the EP mesh variants (make_production_mesh(ep=True),
#     make_host_mesh(expert=)) — on non-EP meshes it falls back replicated
#     and MoE weights stay 2D-sharded (fsdp x ff).
#   cap: MoE capacity slots; sharding them over "model" turns the expert
#     down-projection's cross-"model" reduction into a reduce-scatter.
#   data/model/pod: passthrough names so launch code can talk about mesh
#     axes through the same interface.
DEFAULT_RULES: dict = {
    # activations
    "batch": ("pod", "data"),
    "seq": None,
    "ctx": None,
    "ctx_attn": "model",
    "cache_seq": None,
    "embed": None,
    "cap": "model",
    # params
    "fsdp": "data",
    "heads": "model",
    "kv": "model",
    "ff": "model",
    "experts": "expert",
    "vocab": "model",
    "layers": None,
    # mesh passthrough
    "data": "data",
    "model": "model",
    "pod": "pod",
}


def _as_tuple(axes: MeshAxes) -> Tuple[str, ...]:
    if axes is None:
        return ()
    if isinstance(axes, str):
        return (axes,)
    return tuple(axes)


def _check_rule(name, axes) -> None:
    if axes is None or isinstance(axes, str):
        return
    if isinstance(axes, (tuple, list)) and all(
        isinstance(a, str) for a in axes
    ):
        return
    raise TypeError(
        f"rule {name!r} must map to None, a mesh axis name, or a tuple of "
        f"mesh axis names; got {axes!r}"
    )


@dataclasses.dataclass(frozen=True)
class Ruleset:
    """An (immutable) mesh + logical->mesh axis mapping."""

    mesh: jax.sharding.Mesh
    rules: Mapping[str, MeshAxes]

    def resolve(self, name: Optional[str]) -> Tuple[str, ...]:
        """Mesh axes a logical name maps to, restricted to axes the mesh
        actually has. Unknown names are an error (catches axis typos)."""
        if name is None:
            return ()
        try:
            axes = self.rules[name]
        except KeyError:
            raise KeyError(
                f"unknown logical axis {name!r}; known: "
                f"{sorted(self.rules)}"
            ) from None
        return tuple(a for a in _as_tuple(axes) if a in self.mesh.shape)

    def axis_size(self, name: Optional[str]) -> int:
        """Total number of shards a logical axis maps onto (1 = replicated)."""
        size = 1
        for a in self.resolve(name):
            size *= self.mesh.shape[a]
        return size

    def spec(self, axes, shape=None) -> P:
        """PartitionSpec for a tensor whose dims carry these logical names.

        ``shape`` (when given) enables the divisibility fallback: a dim
        that can't be evenly split over its mapped mesh axes stays
        replicated rather than erroring inside XLA.
        """
        if shape is not None and len(shape) != len(axes):
            raise ValueError(f"rank mismatch: axes={axes} shape={shape}")
        used: set = set()
        entries = []
        for i, name in enumerate(axes):
            picked = []
            size = 1
            for a in self.resolve(name):
                if a in used:
                    continue
                s = self.mesh.shape[a]
                if shape is not None and int(shape[i]) % (size * s):
                    continue
                picked.append(a)
                size *= s
            used.update(picked)
            if not picked:
                entries.append(None)
            elif len(picked) == 1:
                entries.append(picked[0])
            else:
                entries.append(tuple(picked))
        return P(*entries)

    def sharding(self, axes, shape=None) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec(axes, shape))

    def with_overrides(self, overrides: Optional[Mapping[str, MeshAxes]]):
        if not overrides:
            return self
        for k, v in overrides.items():
            _check_rule(k, v)
        return Ruleset(self.mesh, {**self.rules, **overrides})


_ACTIVE: contextvars.ContextVar[Optional[Ruleset]] = contextvars.ContextVar(
    "repro_dist_ruleset", default=None
)


def active() -> Optional[Ruleset]:
    """The ruleset installed by the innermost ``use_rules``, or None."""
    return _ACTIVE.get()


@contextlib.contextmanager
def use_rules(mesh, overrides: Optional[Mapping[str, MeshAxes]] = None, *,
              base: Optional[Mapping[str, MeshAxes]] = None):
    """Install a Ruleset(mesh, DEFAULT_RULES + overrides) for the block.

    Nestable and re-entrant; yields the ruleset so callers can also pass
    it explicitly (``shardings_from_template(tmpl, rs)``).
    """
    rs = Ruleset(mesh, dict(DEFAULT_RULES if base is None else base))
    rs = rs.with_overrides(overrides)
    token = _ACTIVE.set(rs)
    try:
        yield rs
    finally:
        _ACTIVE.reset(token)


def axis_size(name: str) -> int:
    """Shard count of a logical axis under the active ruleset (1 outside)."""
    rs = active()
    return 1 if rs is None else rs.axis_size(name)


def constrain(x, axes):
    """Pin a tensor's sharding by logical axis names.

    Identity when no ruleset is active or the mesh is a single device, so
    model code is unconditionally callable from plain CPU tests.
    """
    rs = active()
    if rs is None or rs.mesh.size <= 1:
        return x
    spec = rs.spec(axes, x.shape)
    if all(e is None for e in spec):
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(rs.mesh, spec))


def put(x, axes):
    """``jax.device_put`` with the sharding the active rules give these
    logical axis names (with the same divisibility/absent-axis fallbacks
    as :func:`constrain`).

    Identity when no ruleset is active or the mesh is a single device.
    Unlike ``constrain`` this runs *outside* jit: it commits
    the array to the mesh so a jitted program with unspecified
    in_shardings picks the distributed layout up from its arguments.
    """
    rs = active()
    if rs is None or rs.mesh.size <= 1:
        return x
    return jax.device_put(x, rs.sharding(axes, x.shape))


def kv_repeat(kv_heads: int, n_heads: int) -> int:
    """KV-head repeat factor that makes GQA caches shardable over "model".

    With q heads sharded m ways, each shard needs its own whole kv heads;
    when kv_heads doesn't divide by m, repeating kv heads up to
    lcm(kv_heads, m) re-aligns the (KV-major) q groups with the shards.
    Returns 1 when nothing shards (no mesh, heads unshardable, or kv
    already divisible) — i.e. plain GQA on CPU.
    """
    m = axis_size("heads")
    if m <= 1 or n_heads % m or kv_heads % m == 0:
        return 1
    lcm = kv_heads * m // math.gcd(kv_heads, m)
    # lcm divides n_heads here: kv_heads | n_heads (GQA invariant) and
    # m | n_heads (checked above) — so the repeated grouping stays exact.
    if lcm > n_heads:
        return 1
    return lcm // kv_heads
