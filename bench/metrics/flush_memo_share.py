"""Self time of the resubmission flush's gain-log memo filter, the
``allocation:flush/memo`` spans, as a percentage of the traced window."""
from bench.metrics._spans import self_share


def read(ctx):
    return self_share(ctx, ("allocation",), "flush/memo")
