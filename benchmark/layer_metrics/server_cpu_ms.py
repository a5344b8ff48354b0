"""The scan server's own CPU per completed scan: self CPU of the
`server.*` spans (Twirp decode and encode, admission, the RPC root)."""

from benchmark import spans

PREFIXES = ("server",)
snapshot = spans.snapshot


def read(ctx, before, after):
    return spans.per_scan_ms(ctx, before, after, PREFIXES)
