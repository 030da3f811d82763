"""``flush_self_share`` in the cells that report ``host_s_per_sim_day.sweep``."""
from bench.metrics.flush_self_share import read  # noqa: F401
