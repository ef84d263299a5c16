"""The program's own host spans in a traced run, for the per-layer metrics
that read them: each span's time and self time, the ``bytes`` stats of the
``ingest`` spans, and the device time that idled while no program span was
open.

``trace.reduce`` keeps host time for its ``STAGES`` alone, and no stats.
``of(ctx)`` reads the newest trace under ``bench.TRACE_DIR`` (the run has
just written it) once per process and file, keeps the spans below on the
harness's thread with their stats and each device's op intervals, and
reduces them with ``trace``'s interval helpers. A file whose
``bench_horizon`` count differs from the run's is refused as stale.
"""
from __future__ import annotations

import dataclasses
import functools
import pathlib
import re

import numpy as np

from harness import bench, trace

# the program's spans (``repro.sim.telemetry.stage``), nested on the
# calling thread inside the harness's ``bench_horizon``
NAMES = ("featurize", "ingest", "estimator_fwd", "controller", "gather",
         "split_metrics")
# what is innermost while no program span is open
UNATTRIBUTED = (bench.SPAN, trace.OTHER)


@dataclasses.dataclass
class Spans:
    host: list  # (name, start_ns, end_ns, stats dict), harness's thread
    devices: dict  # device plane -> [(start_ns, end_ns)] of its XLA ops


@dataclasses.dataclass
class Reduced:
    n_spans: int  # harness spans (calls)
    total_s: dict  # span -> seconds inside it
    self_s: dict  # span -> seconds it was the innermost open span
    stat: dict  # (span, stat) -> sum over its spans
    idle_unattributed_s: float | None  # device idle while no program
    # span was open (mean over the devices used); None without a device
    window_s: float

    def has(self, name: str) -> bool:
        return name in self.total_s

    def ms(self, name: str, *, own: bool = False) -> float:
        """Milliseconds per horizon inside ``name`` (``own``: minus the
        program spans nested in it)."""
        s = (self.self_s if own else self.total_s).get(name, 0.0)
        return 1e3 * s / self.n_spans


def load_file(path) -> Spans:
    from jax.profiler import ProfileData
    pdata = ProfileData.from_file(str(path))
    keep = {bench.SPAN, *NAMES}
    host, devices = [], {}
    for plane in pdata.planes:
        if re.match(r"/device:TPU:\d+$", plane.name):
            devices[plane.name] = [
                (e.start_ns, e.start_ns + e.duration_ns)
                for line in plane.lines if line.name == trace.OPS_LINE
                for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                got = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                        dict(e.stats)) for e in line.events
                       if e.name in keep]
                if any(n == bench.SPAN for n, *_ in got):
                    host += got
    return Spans(host, devices)


def reduce(sp: Spans, *, n_devices: int) -> Reduced:
    calls = [(s, e) for n, s, e, _ in sp.host if n == bench.SPAN]
    if not calls:
        raise ValueError(f"no {bench.SPAN!r} host spans in the trace")
    lo, hi = min(s for s, _ in calls), max(e for _, e in calls)
    inside = [(n, max(s, lo), min(e, hi), st) for n, s, e, st in sp.host
              if e > lo and s < hi]
    total, stat = {}, {}
    for n, s, e, st in inside:
        total[n] = total.get(n, 0.0) + (e - s) / 1e9
        for k, v in st.items():
            if isinstance(v, (int, float)):
                stat[n, k] = stat.get((n, k), 0) + v
    b_t, b_name = trace.innermost([(n, s, e) for n, s, e, _ in inside])
    b_end = np.append(b_t[1:], hi)
    own = {}
    for n, d in zip(b_name, b_end - b_t):
        if d > 0:
            own[n] = own.get(n, 0.0) + float(d) / 1e9
    free = np.array([n in UNATTRIBUTED for n in b_name])
    u_lo, u_hi = b_t[free], b_end[free]
    names = sorted(sp.devices, key=lambda n: int(n.rsplit(":", 1)[1]))
    idle = []
    for name in names[:n_devices]:
        ops = [(max(s, lo), min(e, hi)) for s, e in sp.devices[name]
               if e > lo and s < hi]
        ms, me = trace.merge([s for s, _ in ops], [e for _, e in ops])
        busy = (float(trace.covered(ms, me, u_lo, u_hi).sum())
                if len(ms) and len(u_lo) else 0.0)
        idle.append((float((u_hi - u_lo).sum()) - busy) / 1e9)
    return Reduced(n_spans=len(calls), total_s=total, self_s=own, stat=stat,
                   idle_unattributed_s=(float(np.mean(idle)) if idle
                                        else None),
                   window_s=(hi - lo) / 1e9)


def newest(tdir) -> pathlib.Path:
    files = sorted(pathlib.Path(tdir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no *.xplane.pb under {tdir}")
    return files[-1]


@functools.lru_cache(maxsize=4)
def _reduced(path: str, mtime_ns: int, n_devices: int) -> Reduced:
    return reduce(load_file(path), n_devices=n_devices)


def of(ctx) -> Reduced:
    """The program spans of the run whose trace reduction is ``ctx.red``."""
    path = newest(bench.TRACE_DIR)
    red = _reduced(str(path), path.stat().st_mtime_ns, ctx.chips)
    if red.n_spans != ctx.red.n_spans:
        raise ValueError(f"{path} holds {red.n_spans} {bench.SPAN!r} spans, "
                         f"the run {ctx.red.n_spans}: a stale trace")
    return red


def absent(ctx, red: Reduced, name: str):
    """What a reader of ``name`` spans reads in a trace without them:
    nothing, which stops the run where the cell is listed, unless the
    trace holds no ``ingest`` span either. The program then predates the
    stage spans and spends no time in them, and the reader reads 0."""
    if red.has("ingest"):
        return None
    ctx.log(f"program spans: no {name!r} span from a program without "
            "ingest spans; reading 0")
    return 0.0


def host_ms(ctx, name: str, *, own: bool = False):
    """Milliseconds per horizon in the program's ``name`` spans."""
    red = of(ctx)
    return red.ms(name, own=own) if red.has(name) else absent(ctx, red, name)
