"""Shared by the readers of a span's mean duration."""


def mean_us(ctx, cat, name):
    """Mean duration of the span site ``(cat, name)``, in microseconds; None
    where the window holds no such span."""
    n, total, _ = ctx["profile"].get((cat, name), (0, 0.0, 0.0))
    if n <= 0:
        return None
    return 1e6 * total / n
