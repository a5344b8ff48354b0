"""The process CPU no span covers, per completed scan: Δ process CPU
less Δ self CPU of every span over the window."""

from benchmark import spans

snapshot = spans.snapshot


def read(ctx, before, after):
    return spans.untraced_ms(ctx, before, after)
