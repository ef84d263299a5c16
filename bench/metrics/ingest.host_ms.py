"""ingest.host_ms: host milliseconds per horizon inside the program's
``ingest`` spans: every host-to-device copy on the fleet path, with the
host cast and reshape that build the copied arrays (the windowed path's
KPM/IQ/alloc rows, the recurrent path's feature chunks, the controller
scan's tables, estimates and traces)."""
from harness import program_spans


def read(ctx):
    return program_spans.host_ms(ctx, "ingest")
