"""estimator_fwd.host_ms: host milliseconds per horizon inside the
program's ``estimator_fwd`` spans, less their ``ingest`` children: the
forward's dispatch, the wait for its result and the readback of the
estimates."""
from harness import program_spans


def read(ctx):
    return program_spans.host_ms(ctx, "estimator_fwd", own=True)
