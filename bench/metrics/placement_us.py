"""Mean wall time of one placement, in microseconds: the total of the
``allocation:place`` spans (the policy's ``find_host`` on submission and
after an interruption's commit, its picks included) over their count."""
from bench.metrics._mean import mean_us


def read(ctx):
    return mean_us(ctx, "allocation", "place")
