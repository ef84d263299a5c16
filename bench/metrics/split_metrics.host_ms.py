"""split_metrics.host_ms: host milliseconds per horizon inside the
program's ``split_metrics`` spans: the float64 delay, privacy and energy
of every decided split."""
from harness import program_spans


def read(ctx):
    return program_spans.host_ms(ctx, "split_metrics")
