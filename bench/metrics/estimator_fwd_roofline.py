"""estimator_fwd_roofline: the estimator forward's share of its roofline,
in percent: the least time the chips that ran it could take for the traced
calls' work, max(FLOPs / peak FLOP/s, bytes / HBM bytes/s), over the
device time of the estimator's jit programs (``fwd``, the mesh path's
``fwd_sharded``, ``ssm_forward_seq``; per chip). FLOPs and bytes come from
the configuration's shapes: inputs, outputs, and the weights once per
execution on each chip that ran it (a mesh replicates them); the peak is
bf16's."""

MODULES = ("fwd", "fwd_sharded", "ssm_forward_seq")


def read(ctx):
    red = ctx.red
    mods = [m for m in MODULES if m in red.module_s]
    if ctx.peaks is None or not mods:
        return None
    busy = sum(red.module_s[m] for m in mods)
    chips = max(red.module_devices[m] for m in mods)
    execs = sum(red.module_calls[m] for m in mods) / red.n_spans
    flops = nbytes = 0.0
    for i in range(red.n_spans):  # the traced calls cycle the horizons
        hz = ctx.horizons[i % len(ctx.horizons)]
        f, b = ctx.ref.estimator_cost(ctx.config, hz.shape, execs * chips)
        flops, nbytes = flops + f, nbytes + b
    t_flops = flops / (ctx.peaks["bf16_flops"] * chips)
    t_bytes = nbytes / (ctx.peaks["hbm_bytes_per_s"] * chips)
    ctx.log(f"estimator_fwd_roofline: bound="
            f"{'compute' if t_flops >= t_bytes else 'memory'} chips={chips} "
            f"flops_s={t_flops!r} bytes_s={t_bytes!r} device_s={busy!r}")
    return 100.0 * max(t_flops, t_bytes) / busy
