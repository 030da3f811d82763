"""Self time of the resubmission flush, ``allocation:flush/*`` spans, as a
percentage of the traced window."""
from bench.metrics._spans import self_share


def read(ctx):
    return self_share(ctx, ("allocation",), "flush/")
