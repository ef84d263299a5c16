"""estimator_fwd.device_ms: device milliseconds per horizon spent in the
estimator's jit programs: the windowed forward (``train.fwd``), the mesh
path's per-period forward (``serving.fwd_sharded``, its time the mean over
the chips that ran it) and the recurrent sequence pass
(``ssm.ssm_forward_seq``)."""

MODULES = ("fwd", "fwd_sharded", "ssm_forward_seq")


def read(ctx):
    got = [ctx.red.module_s[m] for m in MODULES if m in ctx.red.module_s]
    if not got:
        return None
    return 1e3 * sum(got) / ctx.red.n_spans
