"""Percentage of the window's device-scored picks that float32 could not
decide and that went on to the exact host pick
(``HlemVmp.device_fallbacks / device_picks``)."""


def read(ctx):
    if ctx["device_picks"] <= 0:
        return None
    return 100.0 * ctx["device_fallbacks"] / ctx["device_picks"]
