"""The four-chip registry sweep `cs-alpine-dp4.sweep` on the CPU: a tiny
served run on four of the suite's virtual devices (`--mesh-devices 4
--mesh-db-shards 1`), a fault drill in the mesh scatter, and the cell's
program-counter readers around a run and on a program that counts
nothing."""

from __future__ import annotations

import contextlib
import io
import os
from types import SimpleNamespace

import numpy as np
import pytest

from benchmark.spec import Bench

from test_benchmark_harness import _run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "cs-alpine-dp4.sweep"
MESH_METRICS = ("mesh_cpu_ms", "mesh_dispatch_ms", "mesh_device_share")
# the served cells' accepted per-layer metrics, read on the mesh too
SWEEP_COUNTERS = ("pad_waste_share", "server_cpu_ms", "scan_cpu_ms",
                  "detect_cpu_ms", "untraced_cpu_ms.sweep",
                  "detectd_queue_wait_ms", "program_load_s")
SWEEP_TRACE = ("device_idle_share.sweep", "join_roofline")
COUNTERS = MESH_METRICS + SWEEP_COUNTERS


def _snapshots(bench) -> dict:
    return {m: bench.reader(m).snapshot() for m in COUNTERS}


@pytest.fixture(scope="module")
def tiny():
    """One tiny run of the cell, the readers' snapshots around it, and
    what it printed on stderr. The compile listeners are on, as the
    harness's start-up turns them on."""
    from trivy_tpu import compile_cache
    compile_cache._listen()
    bench = Bench(ROOT)
    before = _snapshots(bench)
    err = io.StringIO()
    with contextlib.redirect_stderr(err):
        got = _run(CELL)
    after = _snapshots(bench)
    return SimpleNamespace(bench=bench, got=got, err=err.getvalue(),
                           before=before, after=after)


def test_every_new_metric_is_listed_for_the_cell():
    bench = Bench(ROOT)
    cell = bench.cell(CELL)
    assert cell["chips"] == 4 and cell["traffic"] == "sweep16"
    assert cell["config_doc"]["server_flags"] == [
        "--detect-warmup", "--mesh-devices", "4", "--mesh-db-shards", "1"]
    per_layer = {m["name"] for m in bench.metrics(CELL, "per_layer")}
    assert per_layer == set(COUNTERS + SWEEP_TRACE)
    # the one-chip served cell reads the same accepted metrics
    assert set(SWEEP_COUNTERS + SWEEP_TRACE) <= {
        m["name"] for m in bench.metrics("cs-alpine.sweep", "per_layer")}
    assert {m["name"] for m in bench.metrics(CELL, "end_to_end")} == \
        {"scans_per_s", "setup_s"}
    for m in per_layer:
        assert callable(bench.reader(m).read)


def test_the_tiny_mesh_run_is_correct_and_names_the_mesh(tiny):
    got = tiny.got
    assert got["correct"] is True and got["failed"] == 0
    assert got["attempted"] > 0
    assert all(c["value"] == 0 for c in got["checks"].values())
    assert "archives_ran_out" in got["checks"]
    assert {"scans_per_s", "setup_s"} <= set(got["metrics"])
    assert got["metrics"]["scans_per_s"]["value"] > 0
    # the layout server_flags ask for is the one the server runs
    assert "server up, MeshDetector on Mesh('dp': 4, 'db': 1" in tiny.err


def _ctx(n_scans: int):
    return SimpleNamespace(scans=[None] * n_scans)


@pytest.mark.parametrize("metric", COUNTERS)
def test_cell_readers_read_a_run(tiny, metric):
    v = tiny.bench.reader(metric).read(
        _ctx(tiny.got["attempted"]), tiny.before[metric],
        tiny.after[metric])
    assert v is not None
    if metric in MESH_METRICS:
        assert v > 0
    if metric == "mesh_device_share":
        assert v == 100.0
    if metric == "pad_waste_share":
        assert 0 <= v < 100


@pytest.mark.parametrize("metric", MESH_METRICS)
def test_mesh_readers_read_nothing_without_the_counters(monkeypatch,
                                                        metric):
    """A program that never counts the mesh (a fresh registry: no span,
    no counter) reads None, as the parent of this cell does."""
    from trivy_tpu import metrics
    monkeypatch.setattr(metrics, "METRICS", metrics.Registry())
    reader = Bench(ROOT).reader(metric)
    before = reader.snapshot()
    assert reader.read(_ctx(5), before, reader.snapshot()) is None


def _drop_one_cell(monkeypatch):
    """The mesh scatter loses the first cell's bits that hold a hit."""
    from trivy_tpu.parallel import mesh
    real_hits, real_dense = mesh._scatter_hits, mesh._scatter_dense

    def first_cell(counts):
        hit = np.argwhere(counts > 0)
        return tuple(hit[0]) if hit.size else None

    def hits(part, hit_idx, hit_bits, n_hits, n_pairs):
        n_hits = n_hits.copy()
        cell = first_cell(n_hits)
        if cell is not None:
            n_hits[cell] = 0
        return real_hits(part, hit_idx, hit_bits, n_hits, n_pairs)

    def dense(part, bits, n_pairs):
        bits = bits.copy()
        cell = first_cell(bits.reshape(bits.shape[0], bits.shape[1],
                                       -1).sum(-1))
        if cell is not None:
            bits[cell] = 0
        return real_dense(part, bits, n_pairs)
    monkeypatch.setattr(mesh, "_scatter_hits", hits)
    monkeypatch.setattr(mesh, "_scatter_dense", dense)


def test_a_cell_dropped_in_the_mesh_scatter_is_not_correct(monkeypatch):
    _drop_one_cell(monkeypatch)
    got = _run(CELL)
    assert got["correct"] is False
    assert got["checks"]["vuln_mismatch"]["value"] > 0
