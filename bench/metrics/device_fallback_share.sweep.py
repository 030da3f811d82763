"""``device_fallback_share`` in the cells that report ``host_s_per_sim_day.sweep``."""
from bench.metrics.device_fallback_share import read  # noqa: F401
