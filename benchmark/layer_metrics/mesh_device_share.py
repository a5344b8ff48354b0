"""Share of the mesh's detect dispatches in the window that the chips
served: Δ `path="device"` over Δ all of
`trivy_tpu_mesh_dispatches_total` (the rest fell back to the host
join). None where the program does not count them."""

NAME = "trivy_tpu_mesh_dispatches_total"
PATHS = ("device", "host")


def snapshot():
    from trivy_tpu.metrics import METRICS
    return {p: METRICS.get(NAME, path=p) for p in PATHS}


def read(ctx, before, after):
    d = {p: after[p] - before[p] for p in PATHS}
    total = sum(d.values())
    if not total:
        return None
    return 100.0 * d["device"] / total
