"""The secret engine's CPU per completed scan: self CPU of the
`secret.*` spans (the keyword prefilter and the rule confirmation)."""

from benchmark import spans

PREFIXES = ("secret",)
snapshot = spans.snapshot


def read(ctx, before, after):
    return spans.per_scan_ms(ctx, before, after, PREFIXES)
