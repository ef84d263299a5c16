"""control_scan.host_ms: host milliseconds per horizon inside the
program's ``controller`` spans, less their ``ingest`` children: the
controller (or slot-pool) scan's dispatch, the wait for it and the
readback of its outputs."""
from harness import program_spans


def read(ctx):
    return program_spans.host_ms(ctx, "controller", own=True)
