"""VGG16 as a split target: its 43 split points (paper arXiv:2509.01906,
Fig. 5), their analytic FLOPs and activation bytes, and the paper's dCor
privacy curve.

A copy of the program's ``models.vgg`` profile, so that the profile the
program is handed is made here and not by the code under test. VGG16 is
profiled analytically and served by no program, so it defines none of the
served-tail hooks (see ``harness/splitting.py``).
"""
from __future__ import annotations

import numpy as np

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


def profile(spec):
    """(flops_head cumulative, data_bytes, privacy) per split point of a
    configuration's ``split_target`` block."""
    flops_head, data = vgg16_profile(spec["image_size"], spec["num_classes"],
                                     spec["bytes_per_el"])
    return flops_head, data, paper_privacy()


def name(spec):
    """The profile's name as the program's ``SplitProfile`` carries it."""
    return f"vgg16-{spec['image_size']}px"
