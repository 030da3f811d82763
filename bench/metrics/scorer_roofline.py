"""Share of its roofline that the device scorer reaches: the least time of
its calls' bytes and operations at the scored shape (``bench/roofline.py``,
peaks by device kind) over its device time in the profiler trace."""
from bench import roofline

#: the jitted scorer's program name in the trace
PROGRAM = "jit_hlem_scores_tol_jax"


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not ctx.get("scorer_rows"):
        return None
    calls = secs = 0
    for name, m in tr["modules"].items():
        if name.startswith(PROGRAM):
            calls += m["calls"]
            secs += m["seconds"]
    if calls == 0 or secs <= 0:
        return None
    least = roofline.scorer_least_s(ctx["scorer_rows"], ctx["device_kind"])
    return 100.0 * calls * least / secs
