"""gather.host_ms: host milliseconds per horizon inside the slot pool's
``gather`` span: the post-scan ``[sid, age]`` gathers of each slot's
throughputs and estimates, the masking and the PRB scaling."""
from harness import program_spans


def read(ctx):
    return program_spans.host_ms(ctx, "gather")
