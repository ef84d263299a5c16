"""The benchmark harness at tiny widths on the CPU: every cell resolves to
its files and runs a short window end to end; the generator reproduces its
pinned seed-0 output; a device without published peaks is refused; the
cost functions agree with XLA's own FLOP count."""
from __future__ import annotations

import io
import json
import pathlib
import sys

import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from harness import bench, check, peaks, spec, system, traffic  # noqa: E402

system.import_program()  # the program under test, from the checkout's src/
WORKLOADS = [w["name"] for w in spec.benchmark()["workloads"]]
SEED = 2 ** 33 + 17  # wider than 32 bits: seeds may be


@pytest.mark.parametrize("name", WORKLOADS)
def test_cell_resolves_to_its_files(name):
    b = spec.benchmark()
    cell = spec.cell(name)
    w = next(x for x in b["workloads"] if x["name"] == name)
    conf = next(c for c in b["configs"] if c["name"] == w["config"])
    assert (spec.ROOT / conf["file"]).is_file()
    assert json.loads((spec.ROOT / conf["file"]).read_text()) == \
        json.loads((BENCH / "configs" / f"{w['config']}.json").read_text())
    for fn in ("init_params", "calibrate", "raw_estimates", "decision_flops",
               "estimator_cost"):
        assert callable(getattr(cell.ref, fn))
    assert cell.mix["kind"] in ("fleet", "pool")
    limits = bench.limits_of(name)
    assert {"splits_off", "metrics_off"} <= set(limits) <= set(
        check.names(cell.target))
    assert limits["splits_off"] == limits["metrics_off"] == 0  # exact
    assert {m["name"] for m in cell.end_to_end} >= {"ue_periods_per_s",
                                                    "setup_s"}
    assert cell.per_layer and all(callable(r.read) for _, r in cell.per_layer)
    for m, _ in cell.per_layer:
        assert m["moves"] in {e["name"] for e in cell.end_to_end}


@pytest.mark.parametrize("name", WORKLOADS)
def test_cell_runs_a_short_window(name):
    log = io.StringIO()
    out = bench.run(name, SEED, 1.5, False, tiny=True, require_chip=False,
                    log=log)
    line = json.loads(json.dumps(out))
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert line["correct"] is True, log.getvalue()
    assert line["failed"] == 0 and line["attempted"] >= 1
    want = {m["name"] for m in spec.cell(name).end_to_end}
    assert set(line["metrics"]) == want
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert "compiles_in_window=0" in log.getvalue()
    lines = log.getvalue().strip().splitlines()
    limits = bench.limits_of(name)
    assert [x.split("=")[0] for x in lines[-len(limits):]] == [
        f"check {k}" for k in limits]
    assert list(line["checks"]) == list(limits)


def _checksums():
    pins = {}
    for f in sorted((BENCH / "tests").glob("generator_seed0*.json")):
        pins.update(json.loads(f.read_text())["checksums"])
    return sorted(pins.items())


@pytest.mark.parametrize("name,digest", _checksums())
def test_generator_reproduces_seed0(name, digest):
    from repro.channel.scenarios import ChurnSchedule, EpisodeBatch
    cell = spec.cell(name, tiny=True)
    hs = traffic.make(cell.mix, 0, n_sc=cell.config.get("n_sc", 1),
                      include_iq=cell.config["traffic_iq"],
                      EpisodeBatch=EpisodeBatch, ChurnSchedule=ChurnSchedule)
    assert traffic.checksum(hs) == digest
    for hz in hs:
        iq = hz.episode.iq
        assert iq is None or iq.dtype == np.float32


def test_unknown_device_kind_is_refused():
    with pytest.raises(ValueError, match="no published peaks"):
        peaks.peaks("cpu")
    assert peaks.peaks("TPU v5 lite")["bf16_flops"] == 197e12


def test_run_without_a_chip_exits_nonzero_and_prints_nothing(capsys):
    import jax
    if jax.devices()[0].platform == "tpu":
        pytest.skip("a TPU is present")
    sys.path.insert(0, str(BENCH))
    import run
    rc = run.main(["--workload", WORKLOADS[0], "--seed", "1", "--seconds",
                   "1", "--trace", "0"])
    assert rc != 0
    assert capsys.readouterr().out == ""


def _lstm_cost(cell):
    import jax
    import jax.numpy as jnp
    c, b = cell.config, 4
    args = (jax.ShapeDtypeStruct((b, c["window"], c["n_kpms"]), jnp.float32),
            jax.ShapeDtypeStruct((b, 2, c["n_sc"], c["n_sym"]), jnp.float32),
            jax.ShapeDtypeStruct((b,), jnp.float32))
    params = jax.eval_shape(lambda k: cell.ref.init_params(c, k),
                            jax.random.PRNGKey(0))
    f = jax.jit(lambda p, *a: cell.ref.forward(c, p, *a, "highest"))
    xla = f.lower(params, *args).compile().cost_analysis()["flops"]
    return xla, cell.ref.estimator_cost(c, {"ues": b, "periods": 1}, 1)[0]


def _ssm_cost(cell):
    import jax
    import jax.numpy as jnp
    c, b, s = cell.config, 4, 8
    feats = jax.ShapeDtypeStruct((b, s, c["n_kpms"] + 1), jnp.float32)
    params = jax.eval_shape(lambda k: cell.ref.init_params(c, k),
                            jax.random.PRNGKey(0))
    f = jax.jit(lambda p, x: cell.ref.forward_seq(c, p, x, "highest"))
    xla = f.lower(params, feats).compile().cost_analysis()["flops"]
    return xla, cell.ref.estimator_cost(c, {"sessions": b, "steps": s}, 1)[0]


@pytest.mark.parametrize("name,cost,factor", [
    # the same multiply-adds: XLA read 0.987 x ours at these sizes
    ("lstm.paper225", _lstm_cost, 1.05),
    # XLA read 0.875 x ours: it counts the recurrence's scan body once
    ("ssm.pool16k.pf4k", _ssm_cost, 1.25),
])
def test_cost_functions_agree_with_xla(name, cost, factor):
    xla, ours = cost(spec.cell(name, tiny=True))
    assert ours / factor <= xla <= ours * factor, (xla, ours)
