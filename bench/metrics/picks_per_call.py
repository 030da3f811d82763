"""Device-scored picks per device program call over the window:
``HlemVmp.device_picks`` (a single pick, or a run step the host used) over
the counter ``pick/device_calls`` (every call, a single pick's or a run's).
None where the program counts no calls."""


def read(ctx):
    calls = ctx.get("counters", {}).get("pick/device_calls", 0)
    if calls <= 0:
        return None
    return ctx["device_picks"] / calls
