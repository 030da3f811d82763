"""Self time of the market tick: spans in the ``market-tick``,
``market-engine`` and ``migration`` categories, as a percentage of the
traced window.  A run with no market has none."""
from bench.metrics._spans import self_share


def read(ctx):
    return self_share(ctx, ("market-tick", "market-engine", "migration"))
