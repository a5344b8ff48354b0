"""detectd's and the detector's CPU per completed scan: self CPU of
the `detect.*` and `detectd.*` spans (prepare, merged dispatch, fetch,
waits, assembly)."""

from benchmark import spans

PREFIXES = ("detect", "detectd")
snapshot = spans.snapshot


def read(ctx, before, after):
    return spans.per_scan_ms(ctx, before, after, PREFIXES)
