"""``placement_us`` in the cells that report ``host_s_per_sim_day.sweep``."""
from bench.metrics.placement_us import read  # noqa: F401
