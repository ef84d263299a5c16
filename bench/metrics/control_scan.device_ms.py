"""control_scan.device_ms: device milliseconds per horizon spent in the
controller scan: the engine's ``sweep`` / ``sweep_scheduled`` programs and
the slot pool's ``sweep`` (admission, PF scheduler, controllers,
retirement)."""

MODULES = ("sweep", "sweep_scheduled")


def read(ctx):
    got = [ctx.red.module_s[m] for m in MODULES if m in ctx.red.module_s]
    if not got:
        return None
    return 1e3 * sum(got) / ctx.red.n_spans
