"""The mean wall time of one sharded join on the mesh, launch through
the synchronous gather, in ms: Δ self seconds over Δ count of the
`detect.mesh.join` span (`trivy_tpu_span_self_seconds_total`,
`trivy_tpu_spans_total`). None where no such span finished in the
window."""

SPAN = "detect.mesh.join"


def snapshot():
    from trivy_tpu.metrics import METRICS
    return (METRICS.get("trivy_tpu_span_self_seconds_total", span=SPAN),
            METRICS.get("trivy_tpu_spans_total", span=SPAN))


def read(ctx, before, after):
    n = after[1] - before[1]
    if not n:
        return None
    return 1e3 * (after[0] - before[0]) / n
