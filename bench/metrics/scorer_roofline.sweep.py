"""``scorer_roofline`` in the cells that report ``host_s_per_sim_day.sweep``."""
from bench.metrics.scorer_roofline import read  # noqa: F401
