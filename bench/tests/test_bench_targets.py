"""The split target as a plug-in of the harness, on the CPU: VGG16's
profile and lookup table, made through ``bench/targets/vgg16.py``, are the
ones the harness made before the split target had a module of its own; a
toy target with every served-tail hook runs end to end through set-up, the
call, the check and the verdict; a limited number that is missing fails
the verdict; a mix's ``serving`` key reaches ``simulate_fleet``."""
from __future__ import annotations

import dataclasses
import hashlib
import io
import json
import pathlib
import sys

import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from harness import bench, check, spec, splitting, system  # noqa: E402

system.import_program()  # the program under test, from the checkout's src/
SEED = 2 ** 33 + 41
CELL = "lstm.paper225"
TOY = BENCH / "tests" / "toy_target.py"

# sha256 of the arrays the harness made for VGG16 before it had a module
# (float64 profile and per-split costs, int32 table), for both
# configurations' ``split_target`` blocks
VGG16 = {
    "profile": "f7620c8711c9f179867c6afe701c5a4f"
               "a4eb189d494b51927c72f69169f04384",
    "costs": "fd23d1c7ba443718257e8411b8e16fe7"
             "72e2f256a67debb5ebce755dcd1bc19e",
    "table": "69414787e4fbf71f4ce2a5421ec90688"
             "38865624dc77659e5e1234e0a2775871",
}


def _digest(tgt, keys, dtype):
    h = hashlib.sha256()
    for k in keys:
        h.update(np.ascontiguousarray(tgt[k], dtype).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("config", [c["name"] for c in
                                    spec.benchmark()["configs"]])
def test_vgg16_target_is_the_parents(config):
    block = json.loads((BENCH / "configs" / f"{config}.json").read_text())[
        "split_target"]
    target = spec.load_module(BENCH / "targets" / f"{block['model']}.py")
    tgt = splitting.split_target(block, target)
    assert tgt["name"] == "vgg16-224px"
    assert tgt["table"].dtype == np.int32 and tgt["table"].shape == (131,)
    assert _digest(tgt, ("flops_head", "data_bytes", "privacy"),
                   np.float64) == VGG16["profile"]
    assert _digest(tgt, ("d_ue", "d_ser", "e_ue"), np.float64) == \
        VGG16["costs"]
    assert _digest(tgt, ("table",), np.int32) == VGG16["table"]
    for hook in ("init_params", "payload", "call_kwargs", "check", "names"):
        assert not hasattr(target, hook)  # VGG16's tail is served by none


def _toy():
    toy = spec.load_module(TOY)
    toy.calls.clear()
    return toy


def test_toy_target_table():
    toy = _toy()
    block = spec.cell(CELL).config["split_target"]
    tgt = splitting.split_target(block, toy)
    assert tgt["name"] == "toy-5" and len(tgt["d_ue"]) == 5
    assert set(np.unique(tgt["table"])) <= {splitting.NO_SPLIT, 0, 1, 2, 3,
                                            4}
    assert len(set(tgt["table"][1:])) > 1  # the rate moves the split
    assert check.names(toy) == check.NAMES + ("toy_tail_gap",)


@pytest.fixture
def toy_cell(monkeypatch):
    """``CELL`` with the toy as its split target, a ``target`` block in its
    mix and a limit on the toy's number."""
    toy = _toy()
    real_cell, real_limits = spec.cell, bench.limits_of

    def cell(name, **kw):
        c = real_cell(name, **kw)
        return dataclasses.replace(c, target=toy,
                                   mix={**c.mix, "target": {"rows": 8}})

    monkeypatch.setattr(bench.spec, "cell", cell)
    monkeypatch.setattr(bench, "limits_of",
                        lambda name: {**real_limits(name),
                                      "toy_tail_gap": 1e-4})
    return toy


def _run():
    log = io.StringIO()
    out = bench.run(CELL, SEED, 0.5, False, tiny=True, require_chip=False,
                    log=log)
    return out, log.getvalue()


def test_toy_target_runs_end_to_end(toy_cell):
    st = bench.setup(CELL, SEED, tiny=True, require_chip=False)
    assert st.tgt["name"] == "toy-5" and st.prog.profile.name == "toy-5"
    assert st.target_params["w"].shape == (4, 3)
    assert all(hz.payload.shape == (8, 4) for hz in st.horizons)
    kw = st.prog.kwargs(st.horizons[0])
    assert "serving" not in kw and set(kw) == {"ue", "server"}
    calls = toy_cell.calls
    assert calls[:3] == ["profile", "init_params", "payload"]
    assert calls.count("call_kwargs") == 1 + len(st.horizons)

    out, log = _run()
    assert out["correct"] is True, log
    assert list(out["checks"]) == ["est_gap_mbps", "splits_off",
                                   "metrics_off", "toy_tail_gap"]
    assert 0.0 <= out["checks"]["toy_tail_gap"]["value"] <= 1e-4
    assert log.strip().splitlines()[-1].startswith("check toy_tail_gap=")
    assert "check" in toy_cell.calls


def test_toy_target_fault_is_not_correct(toy_cell, monkeypatch):
    monkeypatch.setattr(toy_cell, "tail",
                        lambda params, x: np.asarray(x @ params["w"]) + 1e-3)
    out, log = _run()
    assert out["correct"] is False
    assert out["checks"]["toy_tail_gap"]["value"] > 1e-4, log


def test_missing_number_is_not_correct(toy_cell, monkeypatch):
    monkeypatch.setattr(toy_cell, "check", lambda *a: {})
    out, log = _run()
    assert out["correct"] is False
    assert out["checks"]["toy_tail_gap"]["value"] is None
    assert "check toy_tail_gap=None limit=0.0001" in log


@pytest.mark.parametrize("nums,ok", [
    ({"a": 0.0, "b": 1.0}, True),
    ({"a": 0.0, "b": 2.0}, False),
    ({"a": 0.0}, False),  # b is limited and has no number
    ({}, False),
])
def test_verdict_needs_every_limited_number(nums, ok):
    assert check.verdict(nums, {"a": 0.0, "b": 1.0}) is ok


def test_serving_mesh_reaches_the_call(monkeypatch):
    import jax
    sim = system.import_program()
    cell = spec.cell(CELL, tiny=True)
    tgt = splitting.split_target(cell.config["split_target"], cell.target)
    prog = system.Program(cell.config, tgt, {**cell.mix, "serving": "4x1"},
                          cell.target)
    assert isinstance(prog.serving, sim.ServingMesh)
    assert prog.serving.mesh.devices.size == min(4, len(jax.devices()))
    seen = {}

    def fake(*a, **kw):
        seen.update(kw)
        return "result"

    monkeypatch.setattr(sim, "simulate_fleet", fake)
    hz = bench.traffic.Horizon("fleet", episode=None)
    assert prog.call(hz, params={}) == "result"
    assert seen["serving"] is prog.serving
    plain = system.Program(cell.config, tgt, cell.mix, cell.target)
    assert plain.serving is None and "serving" not in plain.kwargs(hz)


def test_serving_mesh_cell_is_correct(monkeypatch):
    """A mix with ``serving`` runs the sharded estimator and checks
    ``correct`` against the same reference."""
    import repro.sim.engine as engine
    real_cell, real_est = spec.cell, engine.sharded_fleet_estimate
    sharded = []

    def cell(name, **kw):
        c = real_cell(name, **kw)
        return dataclasses.replace(c, mix={**c.mix, "serving": "4x1"})

    def est(*a, **kw):
        sharded.append(1)
        return real_est(*a, **kw)

    monkeypatch.setattr(bench.spec, "cell", cell)
    monkeypatch.setattr(engine, "sharded_fleet_estimate", est)
    out, log = _run()
    assert out["correct"] is True, log
    assert sharded and "compiles_in_window=0" in log
