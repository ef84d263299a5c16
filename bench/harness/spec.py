"""Resolve a cell of ``BENCHMARK.json`` to its files, by name:

  bench/configs/<config>.json   the configuration's sizes
  bench/configs/<config>.py     its plain reference, weights and cost
  bench/traffic/<traffic>.json  the traffic mix's parameters
  bench/metrics/<metric>.py     the reader of one per-layer metric

A new cell, mix or metric is new files and entries, never an edit here.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import pathlib

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"


@functools.lru_cache(maxsize=None)
def load_module(path: pathlib.Path):
    """Import one file under a name of its own (once per process, so that
    what it compiled is kept)."""
    name = "bench_" + path.stem.replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict  # the configuration's file, tiny overrides applied
    ref: object  # its reference module
    mix: dict  # the traffic mix's file, tiny overrides applied
    end_to_end: list  # this cell's end-to-end metrics
    per_layer: list  # (metric entry, reader module) of this cell


def benchmark(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell(name: str, *, tiny: bool = False, root: pathlib.Path = ROOT) -> Cell:
    """The cell ``name`` with its files loaded; ``tiny`` applies each
    file's ``tiny`` overrides (the CPU tests' sizes)."""
    bench = benchmark(root)
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{sorted(work)}")
    w = work[name]
    bdir = root / "bench"
    config = json.loads((bdir / "configs" / f"{w['config']}.json").read_text())
    mix = json.loads((bdir / "traffic" / f"{w['traffic']}.json").read_text())
    if tiny:
        config.update(config.get("tiny", {}))
        mix.update(mix.get("tiny", {}))
    ref = load_module(bdir / "configs" / f"{w['config']}.py")
    e2e = [m for m in bench["end_to_end"] if _applies(m, name)]
    layer = [(m, load_module(bdir / "metrics" / f"{m['name']}.py"))
             for m in bench["per_layer"] if _applies(m, name)]
    return Cell(name, int(w["chips"]), config, ref, mix, e2e, layer)
