"""The scan core's CPU per completed scan: self CPU of the spans
`scan`, `scan.*` and `fanal.*` (on the server: applying layers,
building queries, assembling results)."""

from benchmark import spans

PREFIXES = ("scan", "fanal")
snapshot = spans.snapshot


def read(ctx, before, after):
    return spans.per_scan_ms(ctx, before, after, PREFIXES)
