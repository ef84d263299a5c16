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
