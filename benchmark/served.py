"""A served cell: the Twirp scan server in this process, its clients in
child processes.

The server is built as `trivy server` builds it (`cli.server_options`
with the configuration's flags) and holds the chip. Each client is a
`benchmark.client` process with JAX_PLATFORMS=cpu, running Trivy's
client half (`PutBlob` through `RemoteCache`, then `Scan`) on archives
of its own.
"""

from __future__ import annotations

import contextlib
import json
import os
import queue
import subprocess
import sys
import tempfile
import threading
import time

from . import client as client_mod
from . import spec, synth
from .window import Scan


class Child:
    """One client process and the JSON lines it says."""

    def __init__(self, root: str, job: dict, log: str):
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   PYTHONPATH=root + os.pathsep
                   + os.environ.get("PYTHONPATH", ""))
        self.log = log
        self._err = open(log, "wb")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "benchmark.client"], cwd=root, env=env,
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=self._err, text=True, bufsize=1)
        self._lines: queue.Queue = queue.Queue()
        threading.Thread(target=self._pump, daemon=True).start()
        self.send(job)

    def _pump(self):
        for line in self.proc.stdout:
            self._lines.put(json.loads(line))
        self._lines.put(None)

    def send(self, obj) -> None:
        self.proc.stdin.write(json.dumps(obj) + "\n")
        self.proc.stdin.flush()

    def expect(self, key: str, deadline: float) -> list:
        """→ the messages before and including the one holding `key`."""
        got = []
        while True:
            try:
                msg = self._lines.get(
                    timeout=max(deadline - time.monotonic(), 0.01))
            except queue.Empty:
                raise RuntimeError(f"client timed out waiting for {key!r}"
                                   f"; its log:\n{self.tail()}") from None
            if msg is None:
                raise RuntimeError(f"client exited waiting for {key!r}; "
                                   f"its log:\n{self.tail()}")
            got.append(msg)
            if key in msg:
                return got

    def tail(self) -> str:
        self._err.flush()
        with open(self.log, "rb") as f:
            return f.read()[-3000:].decode(errors="replace")

    def stop(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait()
        self._err.close()


@contextlib.contextmanager
def server(table, cache_dir: str, flags):
    """The scan server as `trivy_tpu.cli server` configures it, on an
    ephemeral localhost port → url."""
    from trivy_tpu import cli
    from trivy_tpu.server.listen import serve_background
    args = cli.build_parser().parse_args(
        ["server", "--listen", "127.0.0.1:0", "--cache-dir", cache_dir,
         *flags])
    httpd, state = serve_background("127.0.0.1", 0, table, args.cache_dir,
                                    **cli.server_options(args))
    try:
        yield f"http://127.0.0.1:{httpd.server_address[1]}", state
    finally:
        httpd.shutdown()
        httpd.server_close()
        state.close()


def warm_round(kids, seconds: float, r: int, limit: float) -> None:
    """Round r of warm-up: the cell's own loop on the clients' warm-up
    archives for `seconds`."""
    t0 = time.monotonic() + 0.05
    for k in kids:
        k.send({"phase": "warm", "round": r, "t0": t0, "seconds": seconds})
    for k in kids:
        k.expect("warmed", limit)


def run(root: str, cell: dict, seed: int, seconds: float, window) -> list:
    """Set up, warm up, measure for `seconds`; `window` opens and closes
    the measurement. → (the advisories, the window's scans)."""
    conf, traf = cell["config_doc"], cell["traffic_doc"]
    tcell = dict(traf, seconds=seconds)
    kids = []
    t_run = time.monotonic()

    def phase(what):
        window.note(f"# {what}: {time.monotonic() - t_run:.3f} s")

    with tempfile.TemporaryDirectory(prefix="bench-") as tmp:
        try:
            for c in range(traf["clients"]):
                kids.append(Child(root, {
                    "root": root, "cell": tcell, "seed": seed, "client": c,
                    "dir": os.path.join(tmp, f"c{c}"),
                    "scanners": conf["scanners"], "table": conf["table"],
                    "family": cell["family"]},
                    os.path.join(tmp, f"client{c}.log")))
            adv = synth.advisories(
                seed, family=spec.family(root, cell["family"]),
                **conf["table"])
            table = synth.program_table(adv)
            phase("table built")
            with server(table, os.path.join(tmp, "srv-cache"),
                        conf["server_flags"]) as (url, state):
                det = getattr(getattr(state, "_scanner", None), "detector",
                              None)
                phase(f"server up, {type(det).__name__} on "
                      f"{getattr(det, 'mesh', None) or 'one device'}")
                limit = time.monotonic() + 600
                for k in kids:
                    k.expect("ready", limit)
                phase("clients ready")
                for k in kids:
                    k.send({"url": url})
                window.warm_up(traf, lambda r: warm_round(
                    kids, traf["warmup_seconds"], r,
                    time.monotonic() + 600))
                phase("warm")
                window.open()
                t0 = time.monotonic() + 0.05
                for k in kids:
                    k.send({"phase": "window", "t0": t0,
                            "seconds": seconds})
                cpu0 = time.process_time()
                window.hold(t0, t0 + seconds)
                server_cpu = time.process_time() - cpu0
                window.close(t0, t0 + seconds)
                # every scan the window started is waited for, a
                # minute past the close at most
                records, ends = [], []
                for k in kids:
                    got = k.expect("done", time.monotonic() + 90)
                    records += [m for m in got if "i" in m]
                    ends.append(got[-1])
                window.ran_out = sum(e["ran_out"] for e in ends)
                window.host_cpu(server_cpu / seconds, sum(
                    e["cpu_s"] / e["wall_s"] for e in ends))
                window.read_memory()
        finally:
            for k in kids:
                k.stop()
    specs = {}
    for c in range(traf["clients"]):
        _warm, mine = client_mod.client_specs(adv, tcell, seed, c)
        specs.update(mine)
    scans = [Scan(r["i"], specs[r["i"]], r["due"], r["start"], r["end"],
                  r["ok"], r["err"], r["vulns"]) for r in records]
    return adv, scans
