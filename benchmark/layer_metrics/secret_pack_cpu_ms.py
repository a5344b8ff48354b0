"""The secret engine's host pack per completed scan: self CPU of the
`secret.pack` span (lowercase and chunk the prefilter's rows, dedup
them). A program without that span reads nothing."""

from benchmark import spans

SPAN = "secret.pack"
snapshot = spans.snapshot


def read(ctx, before, after):
    if after is None or SPAN not in after["spans"]:
        return None
    return spans.per_scan_ms(ctx, before, after, (SPAN,))
