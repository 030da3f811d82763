"""Self time of the float64 host picks that follow a device fallback, the
``allocation:pick/host-exact`` spans, as a percentage of the traced
window."""
from bench.metrics._spans import self_share


def read(ctx):
    return self_share(ctx, ("allocation",), "pick/host-exact")
