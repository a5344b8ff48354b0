"""Share of the secret engine's host regex confirmations, one per
(file, rule) candidate, that took the literal-anchored path in the
window: Δ `path="anchored"` over Δ all of
`trivy_tpu_secret_confirm_total`. None where the program does not count
them."""

NAME = "trivy_tpu_secret_confirm_total"
PATHS = ("anchored", "full")


def snapshot():
    from trivy_tpu.metrics import METRICS
    return {p: METRICS.get(NAME, path=p) for p in PATHS}


def read(ctx, before, after):
    d = {p: after[p] - before[p] for p in PATHS}
    total = sum(d.values())
    if not total:
        return None
    return 100.0 * d["anchored"] / total
