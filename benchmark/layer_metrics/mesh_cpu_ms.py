"""The mesh dispatch's own host CPU per completed scan: self CPU of the
`detect.mesh.*` spans (the domain probe, the partition, the sharded
launch with its gather, the scatter). A program without those spans
reads nothing."""

from benchmark import spans

PREFIXES = ("detect.mesh",)
snapshot = spans.snapshot


def read(ctx, before, after):
    if after is None or not any(spans.matches(n, PREFIXES)
                                for n in after["spans"]):
        return None
    return spans.per_scan_ms(ctx, before, after, PREFIXES)
