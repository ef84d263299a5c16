"""The trace reduction: busy time is the union of op intervals inside the
harness's spans, module time is the busy time inside a module's
executions, and each idle gap is named by the innermost host span it fell
in. Checked on a hand-made trace with TPU-shaped planes: a device plane's
"XLA Ops" and "XLA Modules" lines, and the host's annotations."""
from __future__ import annotations

import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from harness import trace  # noqa: E402

NS = 1e-9


def _made():
    host = [("bench_horizon", 0, 100), ("bench_horizon", 100, 200),
            ("featurize", 10, 30), ("estimator_fwd", 30, 60),
            ("PjitFunction(fwd)", 31, 33)]
    dev = {"ops": [("conv", 40, 50), ("dot", 45, 55), ("while", 150, 170),
                   ("late", 190, 260)],
           "modules": [("jit_fwd(7)", 40, 56), ("jit_sweep(9)", 150, 170)]}
    return trace.Trace({"/device:TPU:0": dev}, host)


def test_reduce_made_trace():
    r = trace.reduce(_made(), span="bench_horizon", n_devices=1)
    assert r.window_s == pytest.approx(200 * NS)
    assert r.n_spans == 2
    # [40, 55) + [150, 170) + [190, 200): the late op is clipped
    assert r.busy_s == pytest.approx(45 * NS)
    assert r.module_s == pytest.approx({"fwd": 15 * NS, "sweep": 20 * NS})
    assert r.module_calls == {"fwd": 1, "sweep": 1}
    assert r.module_devices == {"fwd": 1, "sweep": 1}
    assert r.op_s == pytest.approx({"conv": 10 * NS, "dot": 10 * NS,
                                    "while": 20 * NS, "late": 10 * NS})
    # [0, 40) mid 20 in featurize; [55, 150) mid 102.5 and [170, 190) in
    # the second call's span
    assert r.gap_s == pytest.approx({"featurize": 40 * NS,
                                     "bench_horizon": 115 * NS})
    assert r.host_s == pytest.approx({"bench_horizon": 200 * NS,
                                      "featurize": 20 * NS,
                                      "estimator_fwd": 30 * NS})
    assert r.top_ops(2) == [["while", pytest.approx(20 * NS)],
                            ["conv", pytest.approx(10 * NS)]]


def test_merge_and_covered():
    ms, me = trace.merge([5, 0, 20, 3], [8, 4, 30, 6])
    assert list(ms) == [0, 20] and list(me) == [8, 30]
    got = trace.covered(ms, me, [0, 2, 10, 25], [100, 21, 15, 40])
    assert list(got) == [18, 7, 0, 5]


def test_module_name():
    assert trace.module_name("jit_sweep_scheduled(123)") == "sweep_scheduled"
    assert trace.module_name("jit_fwd") == "fwd"


def test_load_a_real_trace(tmp_path):
    """A profiler trace written here: the harness's spans and the
    program's stage spans come back from the xplane file; a backend with
    no TPU plane has no device time."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: x * 2.0)
    f(jnp.ones(4)).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench_horizon"):
                with jax.profiler.TraceAnnotation("featurize"):
                    f(jnp.ones(4)).block_until_ready()
    r = trace.reduce(trace.load(tmp_path), span="bench_horizon", n_devices=1)
    assert r.n_spans == 3 and r.window_s > 0
    assert 0 < r.host_s["featurize"] <= r.host_s["bench_horizon"]
    assert not r.has_device or r.busy_s <= r.window_s


def test_reduce_a_tpu_trace():
    """A traced run of ssm.pool16k.solo recorded on one TPU v5e (one call,
    ``--seconds 0.3``): the device plane, its op and module lines, the jit
    module names and the host spans the readers match are all found, and
    the readers of the cell read a number."""
    import types

    from harness import spec
    tr = trace.load_file(BENCH / "tests" / "tpu_solo.xplane.pb")
    assert list(tr.devices) == ["/device:TPU:0"]
    r = trace.reduce(tr, span="bench_horizon", n_devices=1)
    assert r.n_spans == 1 and 0 < r.busy_s < r.window_s
    assert r.module_calls["sweep"] == 1
    assert r.module_s["ssm_forward_seq"] > 0 and r.module_s["sweep"] > 0
    assert r.host_s["estimator_fwd"] > 0
    assert sum(r.gap_s.values()) == pytest.approx(r.window_s - r.busy_s)
    ctx = types.SimpleNamespace(red=r, peaks=None)
    for name in ("device_idle", "estimator_fwd.device_ms",
                 "control_scan.device_ms"):
        reader = spec.load_module(BENCH / "metrics" / f"{name}.py")
        assert reader.read(ctx) > 0, name


def _mesh():
    """Four chips. The mesh forward runs on all four, a little longer on
    each later chip; the controller's sweep on device 0 alone, a copy on
    device 2 alone."""
    host = [("bench_horizon", 0, 100), ("bench_horizon", 100, 200)]
    devices = {}
    for d in range(4):
        ops = [("conv", 10, 30 + d), ("conv", 110, 120)]
        mods = [("jit_fwd_sharded(3)", 10, 30 + d),
                ("jit_fwd_sharded(3)", 110, 130)]
        if d == 0:
            ops.append(("while", 50, 90))
            mods.append(("jit_sweep(9)", 50, 90))
        if d == 2:
            ops.append(("copy", 150, 160))
            mods.append(("jit_copy(4)", 150, 160))
        devices[f"/device:TPU:{d}"] = {"ops": ops, "modules": mods}
    return trace.Trace(devices, host)


def test_reduce_mesh_trace():
    """A module's time is the mean over the devices that ran it; busy, op
    and gap times stay means over all four."""
    r = trace.reduce(_mesh(), span="bench_horizon", n_devices=4)
    assert r.module_devices == {"fwd_sharded": 4, "sweep": 1, "copy": 1}
    # fwd_sharded: (20 + d) + 10 on device d; sweep and copy their own
    assert r.module_s == pytest.approx({"fwd_sharded": 31.5 * NS,
                                        "sweep": 40 * NS, "copy": 10 * NS})
    # executions counted on the first device that ran each module
    assert r.module_calls == {"fwd_sharded": 2, "sweep": 1, "copy": 1}
    # busy 70, 31, 42, 33 ns on the four devices
    assert r.busy_s == pytest.approx(44 * NS)
    assert r.op_s == pytest.approx({"conv": 31.5 * NS, "while": 10 * NS,
                                    "copy": 2.5 * NS})
    assert sum(r.gap_s.values()) == pytest.approx(r.window_s - r.busy_s)


def test_readers_on_mesh_trace():
    """The estimator readers find the mesh forward, the scan reader the
    sweep at its own time, and the roofline reads the replicated weights
    once per execution on each of the four chips."""
    import types

    from harness import spec
    r = trace.reduce(_mesh(), span="bench_horizon", n_devices=4)
    calls = []

    def estimator_cost(c, shape, n):  # 1 GFLOP, 1 MB of rows, 1 kB weights
        calls.append(n)
        return 1e9, 1e6 + 1e3 * n

    ctx = types.SimpleNamespace(
        red=r, config={}, ref=types.SimpleNamespace(
            estimator_cost=estimator_cost),
        peaks={"bf16_flops": 1e15, "hbm_bytes_per_s": 1e9}, chips=4,
        horizons=[types.SimpleNamespace(shape={})], log=lambda s: None)

    def read(name):
        return spec.load_module(BENCH / "metrics" / f"{name}.py").read(ctx)

    assert read("estimator_fwd.device_ms") == pytest.approx(31.5e-6 / 2)
    assert read("control_scan.device_ms") == pytest.approx(40e-6 / 2)
    assert read("device_idle") == pytest.approx(100 * (1 - 44 / 200))
    roof = read("estimator_fwd_roofline")
    assert calls == [4, 4]  # 1 execution per call, on 4 chips
    # 2 x (1 MB + 4 kB) over 4 chips' HBM, against 31.5 ns per chip
    assert roof == pytest.approx(100 * (2 * 1.004e6 / 4e9) / (31.5 * NS))


# each listed reader's value on the committed TPU traces, read with the
# one-chip rules (module time over all devices, weights once per
# execution): with one chip the per-device rules give the same numbers.
# Only the readers named here are read; a reader added later is not held
# to these traces.
PINNED = {
    "tpu_solo": {
        "control_scan.device_ms": 37.431483,
        "control_scan.host_ms": 0.0,
        "device_idle": 95.08736626068155,
        "episode_features.host_ms": 0.0,
        "estimator_fwd.device_ms": 36.699960999999995,
        "estimator_fwd.host_ms": 7.3702689999999995,
        "estimator_fwd_roofline": 0.8305815269370355,
        "featurize.host_ms": None,
        "gather.host_ms": 0.0,
        "idle_unattributed": 94.60704696101864,
        "ingest.host_ms": 0.0,
        "ingest.mb": 0.0,
        "mfu": 0.0013034022424794583,
        "split_metrics.host_ms": 0.0,
    },
    "tpu_solo_spans": {
        "control_scan.device_ms": 37.442814,
        "control_scan.host_ms": 76.064818,
        "device_idle": 94.78405061523976,
        "episode_features.host_ms": 1200.2949800000001,
        "estimator_fwd.device_ms": 36.783338,
        "estimator_fwd.host_ms": 108.313007,
        "estimator_fwd_roofline": 0.8286988430987327,
        "featurize.host_ms": 1200.2949800000001,
        "gather.host_ms": 18.64649,
        "idle_unattributed": 0.22941226755314606,
        "ingest.host_ms": 8.722581,
        "ingest.mb": 250.528324,
        "mfu": 0.0013821390652417529,
        "split_metrics.host_ms": 9.58688,
    },
}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_readers_on_tpu_traces_are_pinned(name, tmp_path, monkeypatch):
    import shutil
    import types

    from harness import bench, peaks, spec
    src = BENCH / "tests" / f"{name}.xplane.pb"
    (tmp_path / "cell").mkdir()
    shutil.copy(src, tmp_path / "cell" / "host.xplane.pb")
    monkeypatch.setattr(bench, "TRACE_DIR", tmp_path)
    cell = spec.cell("ssm.pool16k.solo")
    ctx = types.SimpleNamespace(
        red=trace.reduce(trace.load_file(src), span=bench.SPAN,
                         n_devices=1),
        config=cell.config, ref=cell.ref, peaks=peaks.peaks("TPU v5 lite"),
        chips=1, horizons=[types.SimpleNamespace(
            shape={"sessions": 65536, "steps": 56})],
        window=types.SimpleNamespace(units=16384 * 40), target=cell.target,
        target_params=None, log=lambda s: None)
    got = {m: spec.load_module(BENCH / "metrics" / f"{m}.py").read(ctx)
           for m in PINNED[name]}
    assert got == PINNED[name]
