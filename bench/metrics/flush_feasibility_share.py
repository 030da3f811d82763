"""Self time of the resubmission flush's B x hosts feasibility matrix, the
``allocation:flush/feasibility`` spans, as a percentage of the traced
window."""
from bench.metrics._spans import self_share


def read(ctx):
    return self_share(ctx, ("allocation",), "flush/feasibility")
