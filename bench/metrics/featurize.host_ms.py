"""featurize.host_ms: host milliseconds per horizon inside the program's
own ``featurize`` spans (``estimate_fleet`` building the KPM windows on
the host)."""


def read(ctx):
    s = ctx.red.host_s.get("featurize")
    if s is None:
        return None
    return 1e3 * s / ctx.red.n_spans
