"""The per-layer readers of the program's spans and counters, on real
counters: each reader's snapshots taken around a tiny run of its cell
on the CPU, then read as the `--trace 1` run reads them."""

from __future__ import annotations

import os
from types import SimpleNamespace

import pytest

from benchmark import spans
from benchmark.spec import Bench
from test_benchmark_harness import ROOT, _run

SWEEP = ("server_cpu_ms", "scan_cpu_ms", "detect_cpu_ms",
         "untraced_cpu_ms.sweep")
STANDALONE = ("ingest_cpu_ms", "secret_cpu_ms",
              "untraced_cpu_ms.standalone")


def _around(cell: str, names) -> tuple:
    """Snapshots of each reader around one tiny run of `cell` → (the
    readers, before, after, the run's line)."""
    bench = Bench(ROOT)
    readers = {n: bench.reader(n) for n in names}
    before = {n: r.snapshot() for n, r in readers.items()}
    got = _run(cell)
    after = {n: r.snapshot() for n, r in readers.items()}
    assert got["correct"] is True
    return readers, before, after, got


def _ctx(got: dict):
    return SimpleNamespace(scans=[None] * (got["attempted"] - got["failed"]))


@pytest.fixture(scope="module")
def sweep():
    from trivy_tpu import compile_cache
    compile_cache.enable()
    return _around("cs-alpine.sweep", SWEEP + (
        "detectd_queue_wait_ms", "program_load_s"))


@pytest.fixture(scope="module")
def standalone():
    return _around("image-alpine-secret.sweep", STANDALONE)


def test_every_new_metric_is_a_cells(sweep, standalone):
    bench = Bench(ROOT)
    for cell, names in (("cs-alpine.sweep", sweep[0]),
                        ("image-alpine-secret.sweep", standalone[0])):
        listed = {m["name"] for m in bench.metrics(cell, "per_layer")}
        assert set(names) <= listed


@pytest.mark.parametrize("name", SWEEP)
def test_sweep_cpu_readers(sweep, name):
    readers, before, after, got = sweep
    v = readers[name].read(_ctx(got), before[name], after[name])
    assert v is not None and v >= 0
    if name != "untraced_cpu_ms.sweep":
        assert v > 0


def test_sweep_cpu_metrics_add_up_to_the_process_cpu(sweep):
    """server + scan core + detect + untraced is the process's CPU per
    scan: every span in the server's process is under one of the three
    prefixes, and untraced is the rest."""
    readers, before, after, got = sweep
    ctx = _ctx(got)
    total = sum(readers[n].read(ctx, before[n], after[n]) for n in SWEEP)
    b, a = before[SWEEP[0]], after[SWEEP[0]]
    cpu = 1e3 * (a["process_cpu"] - b["process_cpu"]) / len(ctx.scans)
    # each reader took its own snapshot, microseconds apart
    assert total == pytest.approx(cpu, rel=1e-3)
    # no span in the server's process falls outside the three layers
    assert spans.span_cpu_s(b, a) == pytest.approx(sum(
        spans.span_cpu_s(b, a, readers[n].PREFIXES) for n in SWEEP[:3]))


def test_queue_wait_and_program_load(sweep):
    readers, before, after, got = sweep
    ctx = _ctx(got)
    wait = readers["detectd_queue_wait_ms"].read(
        ctx, before["detectd_queue_wait_ms"], after["detectd_queue_wait_ms"])
    assert wait is not None and 0 <= wait < 60e3
    load = readers["program_load_s"].read(
        ctx, before["program_load_s"], after["program_load_s"])
    # a cold run compiles: the join's programs at least
    assert load is not None and load > 0


@pytest.mark.parametrize("name", STANDALONE)
def test_standalone_cpu_readers(standalone, name):
    readers, before, after, got = standalone
    v = readers[name].read(_ctx(got), before[name], after[name])
    assert v is not None and v >= 0
    if name != "untraced_cpu_ms.standalone":
        assert v > 0


def test_readers_give_nothing_without_the_program_counters(monkeypatch):
    """Where the program lacks the span aggregate or the program
    counters, the readers report nothing and do not raise."""
    from trivy_tpu import compile_cache
    from trivy_tpu.metrics import Registry
    bench = Bench(ROOT)
    monkeypatch.delattr(Registry, "family")
    monkeypatch.setattr(compile_cache, "listening", lambda: False)
    ctx = SimpleNamespace(scans=[None] * 3)
    for name in SWEEP + STANDALONE + ("program_load_s",):
        r = bench.reader(name)
        assert r.read(ctx, r.snapshot(), r.snapshot()) is None, name
    r = bench.reader("detectd_queue_wait_ms")
    snap = r.snapshot()
    assert r.read(ctx, snap, snap) is None


def test_no_scans_no_value():
    bench = Bench(ROOT)
    r = bench.reader("server_cpu_ms")
    snap = r.snapshot()
    assert r.read(SimpleNamespace(scans=[]), snap, snap) is None
    assert os.path.exists(os.path.join(ROOT, "benchmark", "spans.py"))
