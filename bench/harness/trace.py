"""Reduce a profiler trace to what the per-layer metrics read: the window
the harness's spans cover, each device's busy time in it (the union of the
intervals in which an XLA op ran), the busy time inside each jit module's
executions on the devices that ran it, the time of each op, the host spans,
and the device's idle gaps named by the innermost host span they fell in.

``load`` reads the newest ``*.xplane.pb`` under a trace directory into
plain tuples; ``reduce`` works on those, so a test can feed it a recorded
trace.
"""
from __future__ import annotations

import dataclasses
import pathlib
import re

import numpy as np

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# host spans a gap may be named by, besides the harness's own: the
# program's stages (``repro.sim.telemetry.stage``)
STAGES = ("featurize", "estimator_fwd", "controller", "sched")
OTHER = "other host work"


@dataclasses.dataclass
class Trace:
    """Events as (name, start_ns, end_ns)."""

    devices: dict  # device plane name -> {"ops": [...], "modules": [...]}
    host: list  # every host-plane event


def load(tdir) -> Trace:
    """The newest trace the profiler wrote under ``tdir``."""
    files = sorted(pathlib.Path(tdir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no *.xplane.pb under {tdir}")
    return load_file(files[-1])


def load_file(path) -> Trace:
    from jax.profiler import ProfileData
    pdata = ProfileData.from_file(str(path))
    devices, host = {}, []
    for plane in pdata.planes:
        if re.match(r"/device:TPU:\d+$", plane.name):
            d = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key:
                    d[key] = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                              for e in line.events]
            devices[plane.name] = d
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                         for e in line.events]
    return Trace(devices, host)


def merge(starts, ends):
    """Union of intervals: sorted, disjoint (starts, ends) arrays."""
    if len(starts) == 0:
        return np.zeros(0), np.zeros(0)
    o = np.argsort(starts, kind="stable")
    s, e = np.asarray(starts, float)[o], np.asarray(ends, float)[o]
    reach = np.maximum.accumulate(e)
    new = np.ones(len(s), bool)
    new[1:] = s[1:] > reach[:-1]
    first = np.flatnonzero(new)
    last = np.append(first[1:] - 1, len(s) - 1)
    return s[first], reach[last]


def covered(ms, me, lo, hi):
    """Seconds of the merged intervals (ms, me) inside each [lo, hi]."""
    cum = np.concatenate([[0.0], np.cumsum(me - ms)])

    def upto(t):  # busy time before t
        i = np.searchsorted(ms, t, side="right")  # intervals starting <= t
        part = np.where(i > 0, np.minimum(t, me[np.maximum(i - 1, 0)])
                        - ms[np.maximum(i - 1, 0)], 0.0)
        return cum[np.maximum(i - 1, 0)] * (i > 0) + np.maximum(part, 0.0)

    return upto(np.asarray(hi, float)) - upto(np.asarray(lo, float))


def module_name(name: str) -> str:
    """``jit_sweep_scheduled(123)`` -> ``sweep_scheduled``."""
    name = re.sub(r"\(.*\)$", "", name).strip()
    return name[4:] if name.startswith("jit_") else name


def innermost(spans):
    """Breakpoints (times, names) of the innermost span in force from each
    time on; the spans are nested (one thread's annotations)."""
    edges = sorted([(s, 1, -(e - s), n) for n, s, e in spans]
                   + [(e, 0, 0, n) for n, s, e in spans])
    stack, times, names = [], [], []
    for t, opening, _, n in edges:
        if opening:
            stack.append(n)
        elif n in stack:
            del stack[len(stack) - 1 - stack[::-1].index(n)]
        times.append(t)
        names.append(stack[-1] if stack else OTHER)
    return np.asarray(times, float), names


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float  # mean over the devices used
    n_spans: int  # harness spans (calls) in the window
    module_s: dict  # module -> busy device seconds in its executions,
    # mean over the devices that ran it
    module_calls: dict  # module -> executions (first device that ran it)
    module_devices: dict  # module -> devices that ran it
    op_s: dict  # op -> device seconds
    gap_s: dict  # host span -> idle device seconds
    host_s: dict  # host span name -> seconds

    @property
    def has_device(self) -> bool:
        return self.busy_s > 0

    def top_ops(self, k):
        return [[n, s] for n, s in sorted(self.op_s.items(),
                                          key=lambda kv: -kv[1])[:k]]

    def top_gaps(self, k):
        return [[n, s] for n, s in sorted(self.gap_s.items(),
                                          key=lambda kv: -kv[1])[:k]]


def reduce(tr: Trace, *, span: str, n_devices: int) -> Reduced:
    """Busy, op and gap times are means over the first ``n_devices``; a
    module's time is the mean over those of them that ran it (a program on
    one chip of four keeps its own time)."""
    calls = [(s, e) for n, s, e in tr.host if n == span]
    if not calls:
        raise ValueError(f"no {span!r} host spans in the trace")
    lo, hi = min(s for s, _ in calls), max(e for _, e in calls)
    named = [(n, max(s, lo), min(e, hi)) for n, s, e in tr.host
             if (n == span or n in STAGES) and e > lo and s < hi]
    host_s = {}
    for n, s, e in named:
        host_s[n] = host_s.get(n, 0.0) + (e - s) / 1e9
    b_t, b_name = innermost(named)
    names = sorted(tr.devices, key=lambda n: int(n.rsplit(":", 1)[1]))
    used = [tr.devices[n] for n in names[:n_devices]]
    k = max(len(used), 1) * 1e9
    busy, module_s, op_s, gap_s, module_calls = 0.0, {}, {}, {}, {}
    module_devices = {}
    for dev in used:
        ops = [(n, max(s, lo), min(e, hi)) for n, s, e in dev["ops"]
               if e > lo and s < hi]
        for n, s, e in ops:
            op_s[n] = op_s.get(n, 0.0) + (e - s) / k
        ms, me = merge([s for _, s, _ in ops], [e for _, _, e in ops])
        busy += float((me - ms).sum()) / k
        mods = [(module_name(n), max(s, lo), min(e, hi))
                for n, s, e in dev["modules"] if e > lo and s < hi]
        if mods and len(ms):
            got = covered(ms, me, [s for _, s, _ in mods],
                          [e for _, _, e in mods])
            here = {m for m, _, _ in mods}
            first = here - set(module_devices)
            for (m, _, _), v in zip(mods, got):
                module_s[m] = module_s.get(m, 0.0) + float(v) / 1e9
                if m in first:
                    module_calls[m] = module_calls.get(m, 0) + 1
            for m in here:
                module_devices[m] = module_devices.get(m, 0) + 1
        g0 = np.concatenate([[lo], me])
        g1 = np.concatenate([ms, [hi]])
        keep = g1 > g0
        mid = (g0[keep] + g1[keep]) / 2
        at = np.searchsorted(b_t, mid, side="right") - 1
        for j, d in zip(at, (g1 - g0)[keep]):
            who = b_name[j] if j >= 0 else OTHER
            gap_s[who] = gap_s.get(who, 0.0) + float(d) / k
    module_s = {m: v / module_devices[m] for m, v in module_s.items()}
    return Reduced(window_s=(hi - lo) / 1e9, busy_s=busy, n_spans=len(calls),
                   module_s=module_s, module_calls=module_calls,
                   module_devices=module_devices, op_s=op_s, gap_s=gap_s,
                   host_s=host_s)
