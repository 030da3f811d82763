"""Self time of the event loop's ``event-loop:dispatch/*`` spans, as a
percentage of the traced window.  Placement on arrival is a child span of
its own (``allocation:place``), so its time is not counted here."""
from bench.metrics._spans import self_share


def read(ctx):
    return self_share(ctx, ("event-loop",), "dispatch/")
