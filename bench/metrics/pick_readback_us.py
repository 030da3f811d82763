"""Mean wall time of a device pick's read-back, in microseconds: the total
of the ``allocation:pick/readback`` spans (the host blocked on the device,
then the scores and the bound copied back) over their count."""
from bench.metrics._mean import mean_us


def read(ctx):
    return mean_us(ctx, "allocation", "pick/readback")
