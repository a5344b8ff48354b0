"""detectd's mean queue wait over the window: Δsum ÷ Δcount of
`trivy_tpu_detect_queue_wait_seconds` (a request's submit to the first
merged dispatch that carries it), in ms."""

NAME = "trivy_tpu_detect_queue_wait_seconds"


def snapshot():
    from trivy_tpu.metrics import METRICS
    _row, total, count = METRICS.hist_get(NAME)
    return total, count


def read(ctx, before, after):
    n = after[1] - before[1]
    if not n:
        return None
    return 1e3 * (after[0] - before[0]) / n
