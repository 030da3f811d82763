"""Mean wall time of the device scorer's call, in microseconds: the total
of the ``allocation:pick/call`` spans (host conversion of the arguments,
their transfer, the enqueue) over their count."""
from bench.metrics._mean import mean_us


def read(ctx):
    return mean_us(ctx, "allocation", "pick/call")
