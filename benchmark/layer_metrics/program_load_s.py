"""Seconds JAX spent obtaining XLA programs inside the window, compiled
or loaded from the persistent cache: Δ
`trivy_tpu_xla_program_seconds_total`, both outcomes. None where the
program does not count them."""

OUTCOMES = ("compiled", "loaded")


def snapshot():
    from trivy_tpu import compile_cache
    from trivy_tpu.metrics import METRICS
    if not getattr(compile_cache, "listening", lambda: False)():
        return None
    return {o: METRICS.get("trivy_tpu_xla_program_seconds_total", outcome=o)
            for o in OUTCOMES}


def read(ctx, before, after):
    if before is None or after is None:
        return None
    return sum(after[o] - before[o] for o in OUTCOMES)
