"""JAX's persistent compilation cache, shared by every entry point.

`cli.main` and `chip_smoke.py` call `enable()` before any compile. Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself
and nothing is set in code. Otherwise the cache lives at a fixed
`.jax_cache/` in the checkout: the directory is part of what a later
process looks up, so it is never a temp name, a pid or a time.

`enable()` also registers, once per process, listeners on JAX's
monitoring events that count each XLA program the process obtains by
outcome: "loaded" when the persistent cache answered (a cache-hit
event fired during that compile request), "compiled" otherwise
(`trivy_tpu_xla_programs_total`, `trivy_tpu_xla_program_seconds_total`).
`watch()` reports the same outcomes for the programs one call on the
calling thread obtained — the graftprof ledger's compile/load split.
"""

from __future__ import annotations

import contextlib
import os
import threading

from .metrics import METRICS

CHECKOUT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")

_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_lock = threading.Lock()
_listening = False
# per thread: a cache hit seen inside the compile request in progress,
# and the programs obtained so far as {"compiled": n, "loaded": n}
_local = threading.local()


def listening() -> bool:
    """→ whether the program-outcome listeners are registered."""
    return _listening


def _counts() -> dict:
    c = getattr(_local, "counts", None)
    if c is None:
        c = _local.counts = {"compiled": 0, "loaded": 0}
    return c


def _on_event(event: str, **_kw) -> None:
    if event == _HIT_EVENT:
        _local.hit = True


def _on_duration(event: str, secs: float, **_kw) -> None:
    if event != _COMPILE_EVENT:
        return
    outcome = "loaded" if getattr(_local, "hit", False) else "compiled"
    _local.hit = False
    _counts()[outcome] += 1
    METRICS.inc("trivy_tpu_xla_programs_total", outcome=outcome)
    METRICS.inc("trivy_tpu_xla_program_seconds_total", secs,
                outcome=outcome)


def _listen() -> None:
    global _listening
    with _lock:
        if _listening:
            return
        import jax.monitoring
        jax.monitoring.register_event_listener(_on_event)
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _listening = True


class Outcome:
    """Programs one `watch()` block obtained on its thread; `compiled`
    is None where the listeners are not registered (unknown)."""

    __slots__ = ("compiled", "loaded")

    def __init__(self):
        self.compiled = None
        self.loaded = None


@contextlib.contextmanager
def watch():
    """→ an Outcome filled in when the block ends: the programs the
    calling thread compiled and loaded inside it."""
    out = Outcome()
    before = dict(_counts())
    try:
        yield out
    finally:
        if _listening:
            now = _counts()
            out.compiled = now["compiled"] - before["compiled"]
            out.loaded = now["loaded"] - before["loaded"]


def enable() -> str:
    """→ the directory compiled programs are cached in."""
    _listen()
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_DIR)
    return CHECKOUT_DIR
