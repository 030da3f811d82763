"""``device_idle_share`` in the cells that report ``host_s_per_sim_day.sweep``."""
from bench.metrics.device_idle_share import read  # noqa: F401
