"""One run of one cell: set-up, the measured window, the traced reduction
and the check against the reference."""
from __future__ import annotations

import json
import os
import shutil
import statistics
import sys
import time
import traceback
import types

import numpy as np

from harness import check, peaks, spec, splitting, system, trace, traffic

SPAN = "bench_horizon"  # the harness's host span around each call
CACHE_DIR = spec.ROOT / ".jax_cache"
TRACE_DIR = spec.BENCH / ".trace"


class NoChip(RuntimeError):
    """The process found no accelerator, too few chips, or a chip whose
    peaks are not in the table."""


def jax_key(seed: int):
    import jax
    s = int(seed) % (1 << 63)
    return jax.random.fold_in(jax.random.PRNGKey(s & 0xFFFFFFFF), s >> 32)


class CompileCounter:
    """Counts XLA compilations and jaxpr traces while ``on``."""

    def __init__(self):
        from jax import monitoring
        self.on, self.compiles, self.traces = False, 0, 0
        monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, *_a, **_k):
        if self.on and name.endswith("backend_compile_duration"):
            self.compiles += 1
        elif self.on and name.endswith("jaxpr_trace_duration"):
            self.traces += 1


def check_devices(jax, chips: int) -> list:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX found {len(devs)}")
    try:
        peaks.peaks(devs[0].device_kind)
    except ValueError as e:
        raise NoChip(str(e)) from None
    return devs


def units_of(result) -> int:
    """UE-periods one call decided: every (UE, period) of a fleet, the
    active slot-periods of a pool."""
    if result.active is None:
        return int(result.splits.size)
    return int(np.asarray(result.active).sum())


def setup(name, seed, *, tiny=False, require_chip=True):
    """Everything before the window; returns the run's state."""
    import jax
    cell = spec.cell(name, tiny=tiny)
    if require_chip:
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        devs = check_devices(jax, cell.chips)
    else:
        devs = jax.devices()
    cfg = cell.config
    tgt = splitting.split_target(cfg["split_target"])
    prog = system.Program(cfg, tgt)
    from repro.channel.scenarios import ChurnSchedule, EpisodeBatch
    horizons = traffic.make(cell.mix, seed, n_sc=cfg.get("n_sc", 1),
                            include_iq=cfg["traffic_iq"],
                            EpisodeBatch=EpisodeBatch,
                            ChurnSchedule=ChurnSchedule)
    params = jax.jit(lambda k: cell.ref.init_params(cfg, k))(jax_key(seed))
    params = cell.ref.calibrate(params, cell.ref.calibration_raw(
        cfg, params, horizons[0].episode))
    for hz in horizons:
        prog.call(hz, params)
    return types.SimpleNamespace(cell=cell, devs=devs, tgt=tgt, prog=prog,
                                 horizons=horizons, params=params)


def window(st, seconds, *, keep_rng):
    """The closed loop: the next horizon is submitted when the previous one
    returns. Keeps the first result of each horizon and one more drawn from
    the seed (reservoir) for the check."""
    import jax
    n_h = len(st.horizons)
    lat, failed, units = [], 0, 0
    kept = [[None, None, 0] for _ in range(n_h)]  # first, drawn, count
    t0 = time.perf_counter()
    i = 0
    while True:
        h = i % n_h
        s = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(SPAN):
                r = st.prog.call(st.horizons[h], st.params)
        except Exception:  # a failed call is counted and shown, not fatal
            traceback.print_exc()
            failed += 1
            r = None
        e = time.perf_counter()
        lat.append(e - s)
        i += 1
        if r is not None:
            units += units_of(r)
            k = kept[h]
            k[2] += 1
            if k[0] is None:
                k[0] = r
            elif keep_rng.random() < 1.0 / (k[2] - 1):
                k[1] = r
        if e - t0 >= seconds:
            break
    return types.SimpleNamespace(lat=lat, failed=failed, units=units,
                                 seconds=e - t0, kept=kept, calls=i)


def memory_peak(devs) -> int:
    out = 0
    for d in devs:
        stats = d.memory_stats() or {}
        out = max(out, int(stats.get("peak_bytes_in_use", 0)))
    return out


def expectations(st, hz) -> dict:
    """The reference's outputs for one horizon at each precision the
    configuration names."""
    cfg = st.cell.config
    return {p: check.reference_outputs(
        hz, st.cell.ref.raw_estimates(cfg, st.params, hz.episode, p), st.tgt,
        cfg, hz.scheduler) for p in cfg["reference_precisions"]}


def run_checks(st, win) -> tuple[dict, dict]:
    cfg = st.cell.config
    rows = []
    for hz, (first, drawn, _) in zip(st.horizons, win.kept):
        got = [r for r in (first, drawn) if r is not None]
        if got:
            expects = expectations(st, hz)
            for r in got:
                try:
                    rows.append(check.numbers(hz, r, expects, st.tgt, cfg,
                                              hz.scheduler))
                except (ValueError, IndexError, TypeError, AttributeError):
                    traceback.print_exc()  # a malformed result is wrong
                    rows.append({k: check.FAR for k in check.NAMES})
    limits = limits_of(st.cell.name)
    if not rows:  # nothing finished: nothing to vouch for
        return {k: check.FAR for k in limits}, limits
    return check.worst(rows), limits


def limits_of(name: str) -> dict:
    path = spec.BENCH / "limits" / f"{name}.json"
    return json.loads(path.read_text())["limits"]


def e2e_metrics(cell, st, win) -> dict:
    lat_ms = [x * 1e3 for x in win.lat]
    vals = {"ue_periods_per_s": win.units / win.seconds,
            "horizon_ms_p50": statistics.median(lat_ms),
            "setup_s": st.setup_s}
    return {m["name"]: {"value": vals[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end}


def run(name, seed, seconds, traced, *, t_start=None, tiny=False,
        require_chip=True, log=sys.stderr):
    """One run of cell ``name``; returns the result's JSON object."""
    t_start = time.perf_counter() if t_start is None else t_start
    import jax
    st = setup(name, seed, tiny=tiny, require_chip=require_chip)
    st.setup_s = time.perf_counter() - t_start
    counter = CompileCounter()
    tdir = TRACE_DIR / name
    if traced:
        shutil.rmtree(tdir, ignore_errors=True)
        jax.profiler.start_trace(str(tdir))
    counter.on = True
    win = window(st, seconds, keep_rng=traffic.seed_rng(seed, 7))
    counter.on = False
    if traced:
        jax.profiler.stop_trace()
    print(f"window: calls={win.calls} failed={win.failed} "
          f"seconds={win.seconds!r} ue_periods={win.units} "
          f"compiles_in_window={counter.compiles} "
          f"traces_in_window={counter.traces} setup_s={st.setup_s!r}",
          file=log, flush=True)
    first = win.kept[0][0]
    if first is not None:  # what the controllers decided in one call
        act = (np.ones(first.splits.shape, bool) if first.active is None
               else np.asarray(first.active, bool))
        sp, n = np.unique(np.asarray(first.splits)[act], return_counts=True)
        print("splits of the first call: "
              + " ".join(f"{a}:{b}" for a, b in zip(sp, n)), file=log,
              flush=True)
    used = st.devs[:st.cell.chips]
    device = {"platform": used[0].platform, "kind": used[0].device_kind,
              "count": len(st.devs), "memory_peak_bytes": memory_peak(used)}
    out = {}
    if traced:
        red = trace.reduce(trace.load(tdir), span=SPAN,
                           n_devices=st.cell.chips)
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        ctx = types.SimpleNamespace(
            red=red, config=st.cell.config, ref=st.cell.ref,
            peaks=peaks.peaks(used[0].device_kind) if require_chip else None,
            chips=st.cell.chips, horizons=st.horizons, window=win,
            log=lambda s: print(s, file=log, flush=True))
        metrics = {}
        for m, reader in st.cell.per_layer:
            v = reader.read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
            elif red.has_device and require_chip:
                # the cell is listed for this metric: a device trace in
                # which its reader finds nothing has lost a module or a span
                raise RuntimeError(f"{m['name']}: nothing to read in the "
                                   f"trace of {name}")
        out["breakdown"] = {"device_ops": red.top_ops(10),
                            "idle_gaps": red.top_gaps(10)}
    else:
        metrics = e2e_metrics(st.cell, st, win)
    nums, limits = run_checks(st, win)
    correct = (win.failed == 0 and win.units > 0
               and check.verdict(nums, limits))
    for k in limits:
        print(f"check {k}={nums[k]!r} limit={limits[k]!r}", file=log,
              flush=True)
    result = {"correct": bool(correct), "attempted": win.calls,
              "failed": win.failed, "metrics": metrics, "device": device}
    result.update(out)
    result["checks"] = {k: {"value": nums[k], "limit": v}
                        for k, v in limits.items()}
    return result
