"""The program's span aggregate, read by the per-layer CPU readers.

The program counts the self CPU seconds of every finished span by name
(`trivy_tpu_span_self_cpu_seconds_total{span}`): a span's CPU on its
own thread less its children's there. A reader takes `snapshot()` at
the window's open and close. A span prefix matches the name itself or
the name followed by a dot. A program without the aggregate gives no
snapshot, and its readers no value.
"""

from __future__ import annotations

import time

FAMILY = "trivy_tpu_span_self_cpu_seconds_total"


def snapshot():
    """→ {"spans": {name: self CPU s}, "process_cpu": s}, or None."""
    from trivy_tpu.metrics import METRICS
    family = getattr(METRICS, "family", None)
    if family is None:
        return None
    return {"spans": {dict(labels).get("span", ""): v
                      for labels, v in family(FAMILY).items()},
            "process_cpu": time.process_time()}


def matches(name: str, prefixes) -> bool:
    return any(name == p or name.startswith(p + ".") for p in prefixes)


def span_cpu_s(before, after, prefixes=None) -> float:
    """Δ self CPU seconds of the spans under `prefixes` (all if None)."""
    return sum(v - before["spans"].get(n, 0.0)
               for n, v in after["spans"].items()
               if prefixes is None or matches(n, prefixes))


def per_scan_ms(ctx, before, after, prefixes):
    """Δ self CPU of the spans under `prefixes`, ms per completed scan."""
    if before is None or after is None or not ctx.scans:
        return None
    return 1e3 * span_cpu_s(before, after, prefixes) / len(ctx.scans)


def untraced_ms(ctx, before, after):
    """Δ process CPU less Δ self CPU of every span, ms per completed
    scan: the CPU no span covers."""
    if before is None or after is None or not ctx.scans:
        return None
    cpu = after["process_cpu"] - before["process_cpu"]
    return 1e3 * (cpu - span_cpu_s(before, after)) / len(ctx.scans)
