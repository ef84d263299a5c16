"""episode_features.host_ms: host milliseconds per horizon inside the
recurrent path's ``featurize`` span: ``episode_features`` normalizing the
whole report stream on the host before the SSD sequence pass."""
from harness import program_spans


def read(ctx):
    return program_spans.host_ms(ctx, "featurize")
