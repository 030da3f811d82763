"""Self time of the event loop's ``event-loop:dispatch/*`` spans, as a
percentage of the traced window.  Placement on arrival is inside it:
``find_host`` has no span of its own."""
from bench.metrics._spans import self_share


def read(ctx):
    return self_share(ctx, ("event-loop",), "dispatch/")
