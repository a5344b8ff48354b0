"""Find a cell's configuration, traffic, metrics and readers by name.

Everything is looked up from `BENCHMARK.json` at the checkout's root:
a cell names its configuration (whose `file` is read) and its traffic
(`benchmark/traffic/<traffic>.json`), and the configuration names its
OS package family (`benchmark/families/<family>.py`, `"family"`,
default alpine); a metric applies to a cell when
its `workloads` lists the cell or it has no `workloads`; a per-layer
metric is read by `benchmark/layer_metrics/<metric>.py`, a kernel's
least bytes come from `benchmark/kernels/<kernel>.py`, and peaks from
`benchmark/peaks.json` by `device_kind`. Adding a cell, a
configuration, a family or a metric adds files and entries and edits
none.
"""

from __future__ import annotations

import importlib.util
import json
import os


def family(root: str, name: str):
    """The OS package family module `benchmark/families/<name>.py` of
    the checkout at `root`."""
    return _load(os.path.join(root, "benchmark", "families", name + ".py"),
                 f"benchmark.families.{name}")


def _load(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Bench:
    def __init__(self, root: str):
        self.root = root
        self.dir = os.path.join(root, "benchmark")
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            self.doc = json.load(f)

    def cell(self, name: str) -> dict:
        """→ the cell's entry with its `config_doc`, `traffic_doc` and
        `family`, the name of its configuration's OS package family."""
        cells = {w["name"]: w for w in self.doc["workloads"]}
        if name not in cells:
            raise SystemExit(f"no workload {name!r} in BENCHMARK.json "
                             f"(have {', '.join(sorted(cells))})")
        cell = dict(cells[name])
        conf = {c["name"]: c for c in self.doc["configs"]}[cell["config"]]
        with open(os.path.join(self.root, conf["file"])) as f:
            cell["config_doc"] = json.load(f)
        with open(os.path.join(self.dir, "traffic",
                               cell["traffic"] + ".json")) as f:
            cell["traffic_doc"] = json.load(f)
        cell["family"] = cell["config_doc"].get("family", "alpine")
        return cell

    def metrics(self, cell: str, kind: str) -> list:
        """`kind` "end_to_end" or "per_layer" → the entries that the
        cell reports."""
        return [m for m in self.doc[kind]
                if cell in m.get("workloads", [cell])]

    def _module(self, sub: str, name: str):
        return _load(os.path.join(self.dir, sub, name + ".py"),
                     f"benchmark.{sub}.{name}")

    def reader(self, metric: str):
        return self._module("layer_metrics", metric)

    def kernel(self, name: str):
        return self._module("kernels", name)

    def peaks(self, device_kind: str) -> dict:
        with open(os.path.join(self.dir, "peaks.json")) as f:
            table = json.load(f)
        if device_kind not in table["devices"]:
            raise SystemExit(f"device {device_kind!r} is not in "
                             f"benchmark/peaks.json")
        return table["devices"][device_kind]
