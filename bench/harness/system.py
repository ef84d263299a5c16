"""The system under test, as a user calls it: ``repro.sim.simulate_fleet``
on the library's default paths. This is the one harness file that imports
the program; what it takes from there is the entry point, the program's
config and data types.
"""
from __future__ import annotations

import dataclasses
import importlib
import sys

from harness.spec import ROOT


def import_program():
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    import repro.sim  # noqa: F401  (fails where the checkout lacks it)
    return sys.modules["repro.sim"]


def _class(dotted: str):
    mod, _, name = dotted.rpartition(".")
    return getattr(importlib.import_module(mod), name)


def estimator_config(config: dict):
    """The program's estimator config from the configuration's sizes."""
    cls = _class(config["program_config"])
    kw = {f.name: config[f.name] for f in dataclasses.fields(cls)
          if f.name in config}
    return cls(**{k: tuple(v) if isinstance(v, list) else v
                  for k, v in kw.items()})


class Program:
    """What one cell hands the program: the split target's profile and
    table, the controller settings, the device profiles, the serving mesh
    the mix names (``serving``, a ``DxM`` string such as ``"4x1"``) and,
    per horizon, the keyword arguments of ``simulate_fleet``, with those
    the split target's ``call_kwargs`` hook adds."""

    def __init__(self, config: dict, tgt: dict, mix: dict, target=None,
                 target_params=None):
        self.sim = import_program()
        from repro.core.controller import ControllerConfig
        from repro.core.energy import DeviceProfile
        from repro.core.profiles import SplitProfile
        from repro.core.pso import LookupTable
        st = config["split_target"]
        self.profile = SplitProfile(
            name=tgt["name"], flops_head=tgt["flops_head"],
            data_bytes=tgt["data_bytes"], privacy=tgt["privacy"],
            layer_names=[str(i) for i in range(len(tgt["flops_head"]))])
        self.table = LookupTable(self.profile.name, tgt["table"], None, None)
        self.ctl = ControllerConfig(**config["controller"])
        self.ue, self.edge = (DeviceProfile(**st[k]) for k in ("ue", "edge"))
        self.ecfg = estimator_config(config)
        self.serving = (self.sim.make_serving_mesh(mix["serving"])
                        if mix.get("serving") else None)
        self.split_spec = st
        self.call_kwargs = getattr(target, "call_kwargs", None)
        self.target_params = target_params

    def kwargs(self, hz, **extra) -> dict:
        kw = dict(ue=self.ue, server=self.edge, **extra)
        if self.serving is not None:
            kw.update(serving=self.serving)
        if self.call_kwargs is not None:
            kw.update(self.call_kwargs(self.split_spec, self.target_params,
                                       hz.payload))
        if hz.kind == "pool":
            kw.update(churn=hz.schedule, capacity=hz.capacity,
                      n_cells=hz.n_cells)
            if hz.scheduler is not None:
                kw.update(sched=self.sim.SchedulerConfig(**hz.scheduler),
                          cell_idx=hz.cell)
        return kw

    def call(self, hz, params, **extra):
        """One horizon through ``simulate_fleet``."""
        return self.sim.simulate_fleet(hz.episode, self.table, self.profile,
                                       self.ctl, estimator=(self.ecfg, params),
                                       **self.kwargs(hz, **extra))
