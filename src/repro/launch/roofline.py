"""Roofline-term extraction from compiled (AOT) artifacts.

All quantities are PER DEVICE (the SPMD module is the per-device program);
dividing per-device work by per-chip peak rates equals dividing global work
by (chips x peak), so the terms match the spec formulas.

Hardware model: TPU v5e-class — 197 TFLOP/s bf16, 819 GB/s HBM, 50 GB/s ICI.
"""
from __future__ import annotations

import re
from dataclasses import dataclass

PEAK_FLOPS = 197e12  # bf16 per chip
HBM_BW = 819e9  # bytes/s per chip
ICI_BW = 50e9  # bytes/s per link

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "ragged-all-to-all")

_DEF_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*"
    r"((?:\([^)]*\))|(?:[a-z0-9_]+\[[^\]]*\](?:\{[^}]*\})?))\s+"
    r"([a-z][\w\-]*)\("
)
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_BRACES_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")


def type_bytes(t: str) -> int:
    """Bytes of an HLO type string, e.g. 'f32[16,128]{1,0}' or a tuple."""
    total = 0
    for m in re.finditer(r"([a-z0-9_]+)\[([^\]]*)\]", t):
        dt, dims = m.group(1), m.group(2)
        sz = _DTYPE_BYTES.get(dt)
        if sz is None:
            continue
        n = 1
        for d in dims.split(","):
            d = d.strip()
            if d:
                n *= int(d)
        total += n * sz
    return total


@dataclass
class CollectiveStats:
    operand_bytes: float = 0.0
    wire_bytes: float = 0.0
    count: int = 0


def collective_stats(hlo_text: str, n_devices: int):
    """Per-collective-op accounting from post-optimization HLO.

    operand_bytes: sum of operand sizes (spec metric).
    wire_bytes: ring-algorithm bytes actually crossing links per device.
    """
    symtab: dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = _DEF_RE.match(line)
        if m:
            symtab[m.group(1)] = type_bytes(m.group(2))
    stats: dict[str, CollectiveStats] = {}
    for line in hlo_text.splitlines():
        m = _DEF_RE.match(line)
        if not m:
            continue
        name, typ, op = m.groups()
        base = op[:-6] if op.endswith("-start") else op
        if op.endswith("-done"):
            continue
        if base not in COLLECTIVES:
            continue
        out_bytes = type_bytes(typ)
        # operand sizes via symbol table (fallback: output size)
        ops_str = line[line.index("(") + 1 :]
        depth, j = 1, 0
        while j < len(ops_str) and depth:
            if ops_str[j] == "(":
                depth += 1
            elif ops_str[j] == ")":
                depth -= 1
            j += 1
        operands = [o.strip().lstrip("%") for o in ops_str[: j - 1].split(",")]
        in_bytes = sum(symtab.get(o, 0) for o in operands if o)
        if in_bytes == 0:
            in_bytes = out_bytes
        gm = _GROUPS_RE.search(line)
        if gm:
            gsize = int(gm.group(2))
        else:
            bm = _GROUPS_BRACES_RE.search(line)
            gsize = len(bm.group(1).split(",")) if bm else n_devices
        gsize = max(gsize, 1)
        ring = (gsize - 1) / gsize
        if base == "all-reduce":
            wire = 2 * in_bytes * ring
        elif base == "all-gather":
            wire = out_bytes * ring
        elif base == "reduce-scatter":
            wire = in_bytes * ring
        elif base in ("all-to-all", "ragged-all-to-all"):
            wire = in_bytes * ring
        else:  # collective-permute
            wire = in_bytes
        st = stats.setdefault(base, CollectiveStats())
        st.operand_bytes += in_bytes
        st.wire_bytes += wire
        st.count += 1
    return stats


# ------------------------------------------------------------------ calibration
def _costvec(compiled, n_dev) -> dict:
    ca = compiled.cost_analysis() or {}
    vec = {"flops": float(ca.get("flops", 0.0)),
           "bytes": float(ca.get("bytes accessed", 0.0))}
    stats = collective_stats(compiled.as_text(), n_dev)
    vec["coll_operand"] = sum(s.operand_bytes for s in stats.values())
    vec["coll_wire"] = sum(s.wire_bytes for s in stats.values())
    for k, s in stats.items():
        vec[f"wire:{k}"] = s.wire_bytes
        vec[f"count:{k}"] = float(s.count)
    return vec


def _vec_op(a: dict, b: dict, f) -> dict:
    keys = set(a) | set(b)
    return {k: f(a.get(k, 0.0), b.get(k, 0.0)) for k in keys}


def calibrated_costs(cfg, shape_name: str, mesh, overrides, *, remat="full",
                     grad_accum: int = 1, bf16_gather: bool = False) -> dict:
    """Loop-corrected per-device cost vector.

    XLA's cost analysis counts while-loop bodies ONCE, so the scanned-layer
    full compile undercounts. We compile unrolled 1-pattern and 2-pattern
    variants (still AOT, still the production mesh), take the difference as
    the exact per-pattern cost, and extrapolate linearly in layer count; for
    train we isolate the optimizer term with a grad-only compile so gradient
    accumulation only scales the microbatch part.
    """
    import dataclasses as dc  # noqa: F401

    from repro.configs.base import SHAPES as _SHAPES
    from repro.dist import sharding as sh
    from repro.launch import steps
    from repro.models import blocks

    pat = len(cfg.pattern)
    seq, gb, kind = _SHAPES[shape_name]
    n_dev = mesh.devices.size
    prev_flag = blocks.INNER_UNROLL
    blocks.INNER_UNROLL = True
    try:
        with sh.use_rules(mesh, overrides) as rs:
            def measure(n_layers, variant):
                cell = steps.build_calibration_cell(
                    cfg, shape_name, rs, n_layers=n_layers, variant=variant,
                    remat=remat, bf16_gather=bf16_gather,
                    micro_rows=gb // grad_accum if kind == "train" else None)
                compiled = steps.lower_cell(cell, mesh, overrides).compile(
                    steps.COMPILER_OPTS)
                return _costvec(compiled, n_dev)

            if kind == "train":
                c1 = measure(pat, "train")
                c2 = measure(2 * pat, "train")
                cg = measure(pat, "grad")
                per_layer = _vec_op(c2, c1, lambda x, y: max(x - y, 0.0) / pat)
                opt = _vec_op(c1, cg, lambda x, y: max(x - y, 0.0))
                lp = _vec_op(per_layer, {}, lambda x, _: x * pat)
                edge = _vec_op(cg, lp, lambda x, y: max(x - y, 0.0))
                micro = _vec_op(edge, per_layer,
                                lambda e, l: e + l * cfg.n_layers)
                total = _vec_op(micro, opt,
                                lambda m, o: m * grad_accum + o)
            else:
                variant = "prefill" if kind == "prefill" else "decode"
                c1 = measure(pat, variant)
                c2 = measure(2 * pat, variant)
                per_layer = _vec_op(c2, c1, lambda x, y: max(x - y, 0.0) / pat)
                lp = _vec_op(per_layer, {}, lambda x, _: x * pat)
                edge = _vec_op(c1, lp, lambda x, y: max(x - y, 0.0))
                total = _vec_op(edge, per_layer,
                                lambda e, l: e + l * cfg.n_layers)
            total["calibrated"] = 1.0
            return total
    finally:
        blocks.INNER_UNROLL = prev_flag


def model_flops(cfg, shape_name: str, shapes: dict) -> float:
    """6·N_active·tokens (train) or 2·N_active·tokens (serve), global."""
    seq, gb, kind = shapes[shape_name]
    n = cfg.active_param_count()
    tokens = gb * seq if kind in ("train", "prefill") else gb
    mult = 6 if kind == "train" else 2
    return float(mult * n * tokens)


def roofline(compiled, mesh, cfg, shape_name: str, shapes: dict,
             grad_accum: int = 1, costvec: dict | None = None) -> dict:
    """Derive the three roofline terms (seconds, per device == global).

    costvec: loop-corrected costs from calibrated_costs(); when None, raw
    compiled numbers are used (undercounted inside scans)."""
    n_dev = mesh.devices.size
    if costvec is not None:
        flops_dev = costvec["flops"]
        bytes_dev = costvec["bytes"]
        operand_bytes = costvec["coll_operand"]
        wire_bytes = costvec["coll_wire"]
        stats = {k[5:]: CollectiveStats(wire_bytes=v)
                 for k, v in costvec.items() if k.startswith("wire:")}
        for k in stats:
            stats[k].count = int(costvec.get("count:" + k, 0))
    else:
        ca = compiled.cost_analysis() or {}
        flops_dev = float(ca.get("flops", 0.0))
        bytes_dev = float(ca.get("bytes accessed", 0.0))
        stats = collective_stats(compiled.as_text(), n_dev)
        operand_bytes = sum(s.operand_bytes for s in stats.values())
        wire_bytes = sum(s.wire_bytes for s in stats.values())
    t_compute = flops_dev / PEAK_FLOPS
    t_memory = bytes_dev / HBM_BW
    t_collective = wire_bytes / ICI_BW
    mf = model_flops(cfg, shape_name, shapes)
    mf_dev = mf / n_dev
    terms = {
        "chips": n_dev,
        "grad_accum": grad_accum,
        "calibrated": costvec is not None,
        "flops_per_device": flops_dev,
        "hlo_bytes_per_device": bytes_dev,
        "collective_operand_bytes": operand_bytes,
        "collective_wire_bytes": wire_bytes,
        "collectives": {
            k: {"operand_bytes": s.operand_bytes, "wire_bytes": s.wire_bytes,
                "count": s.count} for k, s in stats.items()
        },
        "t_compute_s": t_compute,
        "t_memory_s": t_memory,
        "t_collective_s": t_collective,
        "model_flops_global": mf,
        "useful_flops_ratio": (mf_dev / flops_dev) if flops_dev else 0.0,
    }
    dom = max(("compute", t_compute), ("memory", t_memory),
              ("collective", t_collective), key=lambda kv: kv[1])
    terms["bottleneck"] = dom[0]
    step_time = max(t_compute, t_memory, t_collective)
    terms["roofline_step_time_s"] = step_time
    # fraction of compute roofline achieved if the bottleneck were hit
    terms["mfu_bound"] = (mf_dev / PEAK_FLOPS) / step_time if step_time else 0.0
    mem = compiled.memory_analysis()
    if mem is not None:
        terms["memory_per_device"] = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
            "output_bytes": getattr(mem, "output_size_in_bytes", 0),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
            "alias_bytes": getattr(mem, "alias_size_in_bytes", 0),
        }
        live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
        terms["memory_per_device"]["live_bytes"] = int(live)
        terms["fits_16gb_hbm"] = bool(live < 16e9)
    return terms
