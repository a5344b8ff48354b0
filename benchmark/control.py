"""Readings for the limits of `correct`, on the chip at a cell's size.

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3 \\
        --seconds <s>

For each seed, in one process that holds the chip, it runs the cell's
traffic for a short window at the cell's own load, then prints one JSON
line with

- the program's readings: every number `run.py` compares (findings
  that differ from the plain reference, scans never answered); the
  lower readings of the limits;
- the control's readings: the reference at the precision below the one
  the configuration states (the family's `cmp(precision="minor")`;
  Alpine's compares versions by major.minor only), put in the program's
  place on the same scans; the upper readings.

The benchmark's own runs never run it. PERF.md gives the readings and
the limits set from them.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def control_readings(scans, adv) -> dict:
    """The control in the program's place: its findings for each
    answered scan against the full-precision reference."""
    from benchmark import reference
    full = reference.VulnReference(adv)
    low = reference.VulnReference(adv, precision="minor")
    bad = sum(reference.mismatches(low.findings(s.spec.pkgs),
                                   full.findings(s.spec.pkgs))
              for s in scans if s.ok)
    return {"vuln_mismatch": bad}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from benchmark import local, run, served
    from benchmark.spec import Bench
    from benchmark.window import Window
    bench = Bench(ROOT)
    cell = bench.cell(args.workload)
    devices, programs, compiles = run.start_jax(cell["chips"])
    conf = cell["config_doc"]
    entry = {"server": served.run, "image": local.run}[conf["entry"]]
    for seed in [int(s) for s in args.seeds.split(",")]:
        win = Window({}, False, devices, programs, compiles)
        adv, scans = entry(ROOT, cell, seed, args.seconds, win)
        program = run.compare(scans, adv, "secret" in conf["scanners"],
                              win.ran_out)
        print(json.dumps({
            "seed": seed, "scans": len(scans),
            "program": {k: v for k, (v, _lim) in program.items()},
            "control": control_readings(scans, adv)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
