"""Shared arithmetic of the span readers: self time of the span sites in
some categories, as a percentage of the traced window's wall time."""


def self_share(ctx, cats, prefix=""):
    got = [v[2] for (cat, name), v in ctx["profile"].items()
           if cat in cats and name.startswith(prefix)]
    if not got or ctx["window_s"] <= 0:
        return None
    return 100.0 * sum(got) / ctx["window_s"]
