"""idle_unattributed: the share of the traced window, in percent, in which
the device ran no XLA op while no program span was open (the innermost
host span was the harness's ``bench_horizon``, or none): idle time the
program's spans do not put down to a stage. Mean over the cell's chips."""
from harness import program_spans


def read(ctx):
    red = program_spans.of(ctx)
    if red.idle_unattributed_s is None:
        return None
    return 100.0 * red.idle_unattributed_s / red.window_s
