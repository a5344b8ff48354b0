"""fanald ingest's CPU per completed scan: self CPU of the `fanal.*`
spans (cache check, layer walks, analyzers, applier)."""

from benchmark import spans

PREFIXES = ("fanal",)
snapshot = spans.snapshot


def read(ctx, before, after):
    return spans.per_scan_ms(ctx, before, after, PREFIXES)
