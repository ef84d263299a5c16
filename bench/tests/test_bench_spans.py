"""The readers of the program's own spans (``harness/program_spans.py``
and the metrics that use it): self time subtracts the nested spans, idle
device time goes to the innermost program span, a stale trace file is
refused, a program that predates the stage spans reads 0, a traced run of
every cell at tiny widths gives each host-span metric it lists a value,
``ingest.mb`` is what the shapes give, and the spans recorded on one TPU
v5e put the device's idle gaps down to program stages. The traced runs
share ``bench/.trace``, so they stay in this one file."""
from __future__ import annotations

import io
import json
import math
import pathlib
import shutil
import sys
import types

import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from harness import bench, program_spans, spec, system, trace  # noqa: E402

system.import_program()  # the program under test, from the checkout's src/
SEED = 2 ** 33 + 29
NS = 1e-9
NEW = ("ingest.host_ms", "ingest.mb", "episode_features.host_ms",
       "estimator_fwd.host_ms", "control_scan.host_ms", "gather.host_ms",
       "split_metrics.host_ms", "idle_unattributed")
HOST = NEW[:-1]  # every one but the device share
TPU_TRACE = BENCH / "tests" / "tpu_solo_spans.xplane.pb"


def _made():
    host = [("bench_horizon", 0, 100, {}), ("bench_horizon", 100, 200, {}),
            ("featurize", 5, 25, {}), ("estimator_fwd", 30, 70, {}),
            ("ingest", 32, 40, {"bytes": 1000}),
            ("controller", 110, 150, {}),
            ("ingest", 112, 118, {"bytes": 500}),
            ("split_metrics", 160, 170, {})]
    dev = [(45, 60), (120, 140), (190, 260)]
    return program_spans.Spans(host, {"/device:TPU:0": dev})


def _read(name, red, monkeypatch, ctx=None):
    monkeypatch.setattr(program_spans, "of", lambda _ctx: red)
    reader = spec.load_module(BENCH / "metrics" / f"{name}.py")
    return reader.read(ctx or types.SimpleNamespace(log=lambda s: None))


def test_reduce_made_trace(monkeypatch):
    r = program_spans.reduce(_made(), n_devices=1)
    assert r.n_spans == 2 and r.window_s == pytest.approx(200 * NS)
    assert r.total_s == pytest.approx({
        "bench_horizon": 200 * NS, "featurize": 20 * NS,
        "estimator_fwd": 40 * NS, "ingest": 14 * NS,
        "controller": 40 * NS, "split_metrics": 10 * NS})
    # self time: the ingest children come off their stages; the harness's
    # span keeps what no program span covers
    assert r.self_s == pytest.approx({
        "bench_horizon": 90 * NS, "featurize": 20 * NS,
        "estimator_fwd": 32 * NS, "ingest": 14 * NS,
        "controller": 34 * NS, "split_metrics": 10 * NS})
    assert r.stat == {("ingest", "bytes"): 1500}
    # no program span open in [0,5) [25,30) [70,110) [150,160) [170,200):
    # 90 ns, of which the late op covers [190, 200)
    assert r.idle_unattributed_s == pytest.approx(80 * NS)
    want = {"ingest.host_ms": 7e-6, "ingest.mb": 750e-6,
            "estimator_fwd.host_ms": 16e-6, "control_scan.host_ms": 17e-6,
            "split_metrics.host_ms": 5e-6, "idle_unattributed": 40.0}
    for name, v in want.items():
        assert _read(name, r, monkeypatch) == pytest.approx(v), name
    # a span this program emits but the trace lacks reads nothing
    assert _read("gather.host_ms", r, monkeypatch) is None


def test_program_without_stage_spans_reads_0(monkeypatch):
    sp = _made()
    sp.host = [h for h in sp.host if h[0] in ("bench_horizon",
                                              "estimator_fwd")]
    r = program_spans.reduce(sp, n_devices=1)
    assert r.self_s["estimator_fwd"] == pytest.approx(40 * NS)
    for name in ("ingest.host_ms", "ingest.mb", "control_scan.host_ms",
                 "gather.host_ms"):
        assert _read(name, r, monkeypatch) == 0.0, name
    assert _read("estimator_fwd.host_ms", r, monkeypatch) == \
        pytest.approx(20e-6)


def test_no_device_plane_reads_no_idle(monkeypatch):
    sp = _made()
    sp.devices = {}
    r = program_spans.reduce(sp, n_devices=1)
    assert r.idle_unattributed_s is None
    assert _read("idle_unattributed", r, monkeypatch) is None


def _tpu_ctx(monkeypatch, tmp_path, n_spans=None):
    tdir = tmp_path / "trace" / "ssm.pool16k.solo"
    tdir.mkdir(parents=True)
    shutil.copy(TPU_TRACE, tdir / "host.xplane.pb")
    monkeypatch.setattr(bench, "TRACE_DIR", tmp_path / "trace")
    red = trace.reduce(trace.load_file(TPU_TRACE), span=bench.SPAN,
                       n_devices=1)
    if n_spans is not None:
        red.n_spans = n_spans
    return types.SimpleNamespace(red=red, chips=1, log=lambda s: None)


def test_stale_trace_is_refused(monkeypatch, tmp_path):
    ctx = _tpu_ctx(monkeypatch, tmp_path, n_spans=3)
    with pytest.raises(ValueError, match="stale"):
        program_spans.of(ctx)


def test_tpu_trace_spans():
    """One ssm.pool16k.solo call recorded on one TPU v5e with the stage
    spans (python tracer off): every new reader listed for the cell reads
    a positive number, and the device's idle gaps fall under program
    spans on the shared clock, featurize first."""
    ctx = types.SimpleNamespace(log=lambda s: None, chips=1)
    ctx.red = trace.reduce(trace.load_file(TPU_TRACE), span=bench.SPAN,
                           n_devices=1)
    assert ctx.red.n_spans == 1 and ctx.red.has_device
    idle = sum(ctx.red.gap_s.values())
    top = max(ctx.red.gap_s, key=ctx.red.gap_s.get)
    assert top == "featurize"
    assert ctx.red.gap_s.get(bench.SPAN, 0.0) < 0.15 * idle
    red = program_spans.reduce(program_spans.load_file(TPU_TRACE),
                               n_devices=1)
    assert 0 < red.idle_unattributed_s < 0.15 * idle
    assert red.stat[("ingest", "bytes")] > 0


def test_tpu_trace_readers(monkeypatch, tmp_path):
    ctx = _tpu_ctx(monkeypatch, tmp_path)
    listed = {m["name"] for m, _ in spec.cell("ssm.pool16k.solo").per_layer}
    assert set(NEW) <= listed
    for name in NEW:
        reader = spec.load_module(BENCH / "metrics" / f"{name}.py")
        assert reader.read(ctx) > 0, name


def _ingest_bytes(cfg, hz, serving):
    """Bytes one horizon copies to the device, from the shapes."""
    ep = hz.episode
    row = cfg["split_target"]["tp_max_mbps"] + 1  # one PSO lookup row
    if hz.kind == "pool":  # features; shared row, warm split, true tp,
        # cell, dwell, arrival, estimates, ready_end
        m, el = ep.n_ues, ep.n_steps
        feats = m * ep.kpms.shape[1] * (cfg["n_kpms"] + 1)
        return 4 * (feats + row + 1 + 2 * m * el + 3 * m
                    + hz.schedule.horizon)
    n, t = ep.n_ues, ep.n_steps
    rows = cfg["window"] * cfg["n_kpms"] + int(np.prod(ep.iq.shape[2:]))
    # the PRB ratio goes with each row, or once per UE on the mesh path;
    # then the controller's table rows, warm split and estimates
    alloc = n if serving else n * t
    return 4 * (n * t * rows + alloc + n * row + 1 + n * t)


@pytest.mark.parametrize("name", [w["name"] for w in
                                  spec.benchmark()["workloads"]])
def test_traced_run_reads_host_spans(name):
    log = io.StringIO()
    out = json.loads(json.dumps(bench.run(name, SEED, 1.0, True, tiny=True,
                                          require_chip=False, log=log)))
    assert out["correct"] is True, log.getvalue()
    listed = {m["name"] for m, _ in spec.cell(name, tiny=True).per_layer}
    host = [m for m in HOST if m in listed]
    assert len(host) >= 5
    for m in host:
        v = out["metrics"][m]["value"]
        assert math.isfinite(v) and v > 0, (m, v)
    # one cell's calls cycle its horizons
    st = bench.setup(name, SEED, tiny=True, require_chip=False)
    n_h, calls = len(st.horizons), out["attempted"]
    want = sum(_ingest_bytes(st.cell.config, st.horizons[i % n_h],
                             st.cell.mix.get("serving"))
               for i in range(calls)) / calls / 1e6
    assert out["metrics"]["ingest.mb"]["value"] == pytest.approx(want)
