"""The split target every configuration partitions: VGG16's 43 split points
(paper arXiv:2509.01906, Fig. 5), their analytic FLOPs and activation
bytes, the paper's dCor privacy curve, and the PSO lookup table of
Algorithm 1 (one objective evaluation over every split and integer Mbps).

A copy of the program's ``models.vgg`` profile, ``core.objective``,
``core.energy`` and ``core.pso``, so that the profile and the table the
program is handed, and the reference's split metrics, are made here and not
by the code under test.
"""
from __future__ import annotations

import numpy as np

NO_SPLIT = -1
TP_FLOOR_BPS = 1.0  # a starved link's delay stays finite
PRB_FLOOR_MBPS = 0.01

_FEATURES = [("conv", 64), ("relu", 0), ("conv", 64), ("relu", 0),
             ("pool", 2), ("conv", 128), ("relu", 0), ("conv", 128),
             ("relu", 0), ("pool", 2)]
_FEATURES += [("conv", 256), ("relu", 0)] * 3 + [("pool", 2)]
_FEATURES += ([("conv", 512), ("relu", 0)] * 3 + [("pool", 2)]) * 2


def vgg16_ops(num_classes):
    ops = [("input", 0)] + _FEATURES + [("avgpool", 7), ("flatten", 0)]
    ops += [("fc", 4096), ("relu", 0), ("dropout", 0),
            ("fc", 4096), ("relu", 0), ("dropout", 0),
            ("fc", num_classes), ("softmax", 0), ("output", 0)]
    assert len(ops) == 43
    return ops


def vgg16_profile(image_size, num_classes, bytes_per_el, in_channels=3):
    """(flops_head cumulative, data_bytes) per split point."""
    h = w = image_size
    c = c_in = in_channels
    flat = flat_in = None
    flops, data = [], []
    for kind, arg in vgg16_ops(num_classes):
        if kind == "conv":
            c = arg
            flops.append(2 * 9 * c_in * c * h * w)
            c_in = c
        elif kind == "pool":
            h //= arg
            w //= arg
            flops.append(float(h * w * c))
        elif kind == "avgpool":
            h = w = min(h, arg)
            flops.append(float(h * w * c))
        elif kind == "flatten":
            flat = flat_in = h * w * c
            flops.append(0.0)
        elif kind == "fc":
            flat = arg
            flops.append(2 * flat_in * flat)
            flat_in = flat
        elif kind in ("relu", "softmax"):
            flops.append(float(flat if flat is not None else h * w * c))
        else:
            flops.append(0.0)
        size = flat if flat is not None else h * w * c
        data.append(float(size) * bytes_per_el)
    return np.cumsum(flops).astype(float), np.array(data)


def paper_privacy():
    """dCor(input, activation) per split, the paper's Fig. 5b curve."""
    l = np.arange(1, 44)
    p = 0.97 - 0.45 * (l / 43) ** 1.5 - 0.30 / (1.0 + np.exp(-(l - 24.5)
                                                             * 1.5))
    p = np.clip(p, 0.2, 1.0)
    p[0], p[24], p[37], p[42] = 1.0, 0.215, 0.220, 0.210
    return p


def split_target(spec):
    """Per-split arrays and the lookup table for a config's
    ``split_target`` block."""
    if spec["model"] != "vgg16":
        raise ValueError(f"unknown split target {spec['model']!r}")
    flops_head, data = vgg16_profile(spec["image_size"], spec["num_classes"],
                                     spec["bytes_per_el"])
    ue, edge = spec["ue"], spec["edge"]
    d_ue = flops_head / ue["flops_per_s"]
    d_ser = edge["fixed_latency_s"] + (flops_head[-1] - flops_head) / edge[
        "flops_per_s"]
    e_ue = ue["tdp_w"] / ue["threads"] * d_ue
    privacy = paper_privacy()
    tgt = dict(flops_head=flops_head, data_bytes=data, privacy=privacy,
               d_ue=d_ue, d_ser=d_ser, e_ue=e_ue)
    tgt["table"] = pso_table(tgt, spec)
    return tgt


def pso_table(t, spec):
    """Algorithm 1: for every integer Mbps, the feasible split with the
    least normalised weighted delay + privacy + energy."""
    w_d, w_p, w_e = spec["weights"]
    tau, rho, e_max = spec["tau_max_s"], spec["rho_max"], spec["e_max_j"]
    tps = np.arange(1, spec["tp_max_mbps"] + 1) * 1e6
    d_e2e = (t["d_ue"] + t["d_ser"])[:, None] + t["data_bytes"][:, None] \
        * 8.0 / tps[None]
    p, e = t["privacy"], t["e_ue"]
    f = (w_d * d_e2e / max(float(np.max(t["d_ue"] + t["d_ser"])), 1e-9)
         + w_p * (p / max(float(p.max()), 1e-9))[:, None]
         + w_e * (e / max(float(e.max()), 1e-9))[:, None])
    pre = (p <= rho) & (e <= e_max)
    slack = tau - t["d_ue"] - t["d_ser"]
    with np.errstate(divide="ignore"):
        tp_min = np.where(slack > 0, t["data_bytes"] * 8.0
                          / np.maximum(slack, 1e-12), np.inf)
    usable = (pre[:, None] & (tp_min[:, None] <= tps[None])
              & (d_e2e <= tau))
    f = np.where(usable, f, np.inf)
    best = np.argmin(f, axis=0)
    ok = np.isfinite(f[best, np.arange(len(tps))])
    table = np.full(len(tps) + 1, NO_SPLIT, np.int32)
    table[1:] = np.where(ok, best, NO_SPLIT)
    return table
