"""`confirm_anchored_share`: the secret engine's anchored share of host
regex confirmations, read around a tiny run of the standalone cell on
the CPU, and on a program that counts nothing."""

from __future__ import annotations

import os
from types import SimpleNamespace

import pytest

from benchmark.spec import Bench

from test_benchmark_harness import _run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "image-alpine-secret.sweep"
METRIC = "confirm_anchored_share"


def test_the_metric_is_the_standalone_cells():
    bench = Bench(ROOT)
    entry = {m["name"]: m for m in bench.metrics(CELL, "per_layer")}[METRIC]
    assert entry["layer"] == "secret engine"
    assert entry["moves"] == "scans_per_s.standalone"
    assert entry["workloads"] == [CELL]
    for cell in ("cs-alpine.sweep", "cs-alpine-dp4.sweep"):
        assert METRIC not in {m["name"]
                              for m in bench.metrics(cell, "per_layer")}


def test_a_run_of_the_cell_confirms_every_candidate_anchored():
    reader = Bench(ROOT).reader(METRIC)
    before = reader.snapshot()
    got = _run(CELL)
    after = reader.snapshot()
    assert got["correct"] is True
    ctx = SimpleNamespace(scans=[None] * got["attempted"])
    # every planted key's file is confirmed, all by built-in rules
    assert after["anchored"] > before["anchored"]
    assert reader.read(ctx, before, after) == pytest.approx(100.0)


def test_nothing_to_read_without_the_counter(monkeypatch):
    """A program that never counts confirmations (a fresh registry, as
    on a tree without the counter) reads None and does not raise."""
    from trivy_tpu import metrics
    monkeypatch.setattr(metrics, "METRICS", metrics.Registry())
    reader = Bench(ROOT).reader(METRIC)
    snap = reader.snapshot()
    assert reader.read(SimpleNamespace(scans=[None] * 3), snap,
                       reader.snapshot()) is None


def test_a_full_path_confirmation_lowers_the_share():
    reader = Bench(ROOT).reader(METRIC)
    before = {"anchored": 10.0, "full": 2.0}
    after = {"anchored": 13.0, "full": 3.0}
    assert reader.read(None, before, after) == pytest.approx(75.0)
