"""ingest.mb: megabytes (1e6 bytes) copied from host to device per
horizon: the sum of the ``bytes`` stats of the program's ``ingest`` spans,
each the ``nbytes`` of the arrays that span copied."""
from harness import program_spans


def read(ctx):
    red = program_spans.of(ctx)
    if not red.has("ingest"):
        return program_spans.absent(ctx, red, "ingest")
    return red.stat.get(("ingest", "bytes"), 0) / 1e6 / red.n_spans
