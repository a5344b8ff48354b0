"""graftguard failpoints: named fault-injection sites.

The chaos suite (tests/test_resilience.py) and operators exercising a
deployment need *deterministic* faults: "the next dispatch errors",
"every device fetch stalls 50 ms", "5% of scans flake, seeded". A
failpoint is a named site on a production code path that normally does
nothing (one dict probe on a registry whose empty state is a plain
attribute read) and, when armed, injects one of four modes:

  error       raise FailpointError at the site
  hang(ms)    sleep ms — simulates a wedged call; long enough to trip
              the device watchdog (resilience.breaker)
  slow(ms)    sleep ms — degradation below the watchdog deadline
  flaky(p)    raise FailpointError with probability p from a SEEDED
              stream (same arming → same fault sequence, so a chaos
              run is reproducible bit for bit)

Arming: the TRIVY_TPU_FAILPOINTS env var or repeated `--failpoint`
server flags, both in the spec grammar

  site=mode[:arg[:seed]]  or  site=mode(arg[,seed])
  e.g.  detect.dispatch=hang:100
        rpc.scan=flaky:0.05:7 ; db.download=error

Sites are a closed catalog (SITES) so a typo'd spec fails loudly at
parse time instead of silently never firing.
"""

from __future__ import annotations

import random
import re
import threading
import time
from dataclasses import dataclass

# the failpoint catalog: every injection site compiled into the tree.
# graftlint's TPU108 keeps these out of device code; the host call
# sites are listed next to each name.
SITES = (
    "detect.dispatch",    # detect/engine.py _launch (join dispatch)
    "detect.device_get",  # detect/engine.py _fetch_bits (result fetch)
    "detect.compile",     # detect/engine.py _launch, new-shape compiles
    "detect.query_upload",  # detect/feed.py upload_queries (graftfeed
    #                         staged/inline query-column H2D transfer)
    "cache.backend",      # fanal/cache.py FSCache blob/artifact IO
    "cache.redis",        # fanal/redis_cache.py shared-backend IO
    "cache.s3",           # fanal/s3_cache.py shared-backend IO
    "rpc.scan",           # server/listen.py Scan handler
    "rpc.route",          # fleet/router.py per-replica forward
    "admission.quota",    # resilience/admission.py quota bookkeeping
    #                       (graftfair; fails CLOSED — injected faults
    #                       become well-formed 429 sheds, never 500s)
    "db.download",        # db/download.py OCI artifact pull
    "fanal.walk",         # fanal/pipeline.py per-layer walker stage
    "fanal.analyze",      # fanal/pipeline.py analyzer-batch stage
    "secret.prefilter",   # secret/engine.py device keyword engine
    "memo.get",           # fleet/memo.py result-memo reads (graftmemo)
    "memo.put",           # fleet/memo.py result-memo writes
    "sbom.parse",         # sbom/artifact.py SBOMArtifact.inspect
    #                       (graftbom document decode stage)
    "libscan.flatten",    # detect/libscan.py LibraryIndex.build
    #                       (fingerprint-corpus table flattening)
)

# site FAMILIES: a family member is `<family>:<instance>` (e.g.
# `detect.mesh:2` = mesh device 2's fault domain, probed by
# parallel/mesh.py + resilience/meshguard.py). Families keep the
# catalog closed — the instance part is open (device ids come from the
# runtime) but the family must be compiled in.
FAMILIES = (
    "detect.mesh",        # meshguard per-device domain probes
)

MODES = ("error", "hang", "slow", "flaky")

# site part allows digits after the first letter (`cache.s3`); the
# closed catalog (known_site) still rejects typos at parse time
_SPEC_RE = re.compile(
    r"^(?P<site>[a-z][a-z0-9_.]*(?::[a-z0-9_]+)?)=(?P<mode>[a-z]+)"
    r"(?:[:(](?P<arg>[0-9.]+)(?:[:,](?P<seed>\d+))?\)?)?$")


def known_site(site: str) -> bool:
    """Exact catalog members, plus `<family>:<instance>` members of the
    compiled-in families."""
    if site in SITES:
        return True
    fam, sep, inst = site.partition(":")
    return bool(sep) and bool(inst) and fam in FAMILIES


class FailpointError(RuntimeError):
    """The injected fault. Sites raise it where a real backend error
    would surface, so the recovery machinery under test cannot tell
    the difference."""

    def __init__(self, site: str):
        super().__init__(f"failpoint {site} fired")
        self.site = site


@dataclass
class _Spec:
    mode: str
    arg: float          # ms for hang/slow, probability for flaky
    rng: random.Random  # flaky only; seeded at arm time


def parse_spec(text: str) -> dict[str, _Spec]:
    """Parse `site=mode[:arg[:seed]]` specs joined by `;` or `,` (a
    comma inside `mode(p,seed)` parens binds to the mode — the same
    paren-aware splitter flagcfg applies to env/config flag values)."""
    from ..flagcfg import split_commas
    specs: dict[str, _Spec] = {}
    # split on ';' always; on ',' only outside parens
    parts: list[str] = []
    for chunk in text.split(";"):
        parts.extend(split_commas(chunk))
    for raw in parts:
        raw = raw.strip()
        if not raw:
            continue
        m = _SPEC_RE.match(raw)
        if m is None:
            raise ValueError(f"bad failpoint spec {raw!r} "
                             f"(want site=mode[:arg[:seed]])")
        site, mode = m.group("site"), m.group("mode")
        if not known_site(site):
            raise ValueError(
                f"unknown failpoint site {site!r} "
                f"(known: {', '.join(SITES)}; families: "
                f"{', '.join(f + ':<id>' for f in FAMILIES)})")
        if mode not in MODES:
            raise ValueError(f"unknown failpoint mode {mode!r} "
                             f"(known: {', '.join(MODES)})")
        arg = float(m.group("arg")) if m.group("arg") else 0.0
        if mode in ("hang", "slow") and arg <= 0:
            raise ValueError(f"{raw!r}: {mode} needs a millisecond arg")
        if mode == "flaky" and not 0.0 < arg <= 1.0:
            raise ValueError(f"{raw!r}: flaky needs a probability in "
                             f"(0, 1]")
        seed = int(m.group("seed")) if m.group("seed") else 0
        specs[site] = _Spec(mode, arg, random.Random(seed))
    return specs


class FailpointRegistry:
    """Process-wide failpoint state. `fire(site)` is the only call on
    hot paths; with nothing armed it is one attribute read."""

    def __init__(self):
        self._lock = threading.Lock()
        self._specs: dict[str, _Spec] = {}
        # lock-free fast-path flag: plain bool read is atomic in
        # CPython; set only under the lock
        self._armed = False
        self._armed_sites: frozenset = frozenset()

    def configure(self, text: str) -> None:
        """Replace the armed set from a spec string ('' clears)."""
        specs = parse_spec(text) if text.strip() else {}
        with self._lock:
            self._specs = specs
            self._armed = bool(specs)
            self._armed_sites = frozenset(specs)

    def set(self, site: str, mode: str, arg: float = 0.0,
            seed: int = 0) -> None:
        if not known_site(site):
            raise ValueError(f"unknown failpoint site {site!r}")
        if mode not in MODES:
            raise ValueError(f"unknown failpoint mode {mode!r}")
        with self._lock:
            self._specs = dict(self._specs)
            self._specs[site] = _Spec(mode, arg, random.Random(seed))
            self._armed = True
            self._armed_sites = frozenset(self._specs)

    def clear(self, site: str | None = None) -> None:
        with self._lock:
            if site is None:
                self._specs = {}
            else:
                self._specs = {k: v for k, v in self._specs.items()
                               if k != site}
            self._armed = bool(self._specs)
            self._armed_sites = frozenset(self._specs)

    @property
    def armed(self) -> bool:
        """Anything armed at all? Lock-free (plain bool read) — the
        meshguard domain-probe loop skips its per-device watches
        entirely when nothing is armed, keeping the mesh hot path at
        one attribute read like every other disarmed site."""
        return self._armed

    @property
    def armed_sites(self) -> frozenset:
        """Immutable snapshot of the armed site names (lock-free plain
        attribute read). meshguard probes ONLY devices whose
        `detect.mesh:<id>` site appears here — arming an unrelated
        failpoint (e.g. cache.backend) costs the mesh hot path
        nothing."""
        return self._armed_sites

    def active(self) -> dict[str, str]:
        """→ {site: 'mode(arg)'} snapshot for /healthz and logs."""
        with self._lock:
            specs = dict(self._specs)
        return {s: (sp.mode if sp.mode == "error"
                    else f"{sp.mode}({sp.arg:g})")
                for s, sp in specs.items()}

    def fire(self, site: str) -> None:
        """Run the armed fault for `site`, if any. Called from the
        production sites; a disarmed registry returns immediately."""
        if not self._armed:
            return
        with self._lock:
            spec = self._specs.get(site)
            # flaky draws happen under the lock: the seeded stream must
            # be a single sequence even with concurrent callers
            flake = (spec is not None and spec.mode == "flaky"
                     and spec.rng.random() < spec.arg)
        if spec is None:
            return
        if spec.mode == "error" or flake:
            _note_fault(site, spec.mode)
            raise FailpointError(site)
        if spec.mode in ("hang", "slow"):
            if spec.mode == "hang":
                # a hang is watchdog-trip material; slow-mode
                # degradation below the deadline is not incident-worthy
                _note_fault(site, "hang")
            time.sleep(spec.arg / 1e3)


def _note_fault(site: str, mode: str) -> None:
    """graftwatch hook: an injected fault that actually FIRED pins the
    active trace and auto-captures a (cooldown-limited) incident —
    the chaos drill's artifacts look exactly like a real outage's."""
    try:
        from ..obs.recorder import RECORDER
        RECORDER.note_event("failpoint", incident=True, site=site,
                            mode=mode)
    except Exception:  # noqa: BLE001 — observability never sinks a site
        pass


FAILPOINTS = FailpointRegistry()


def failpoint(site: str) -> None:
    """Module-level convenience used at every injection site."""
    FAILPOINTS.fire(site)


def spec_from_sources(flag_values, env=None) -> str:
    """Resolve the armed spec from its two sources: explicit
    `--failpoint` values (which flagcfg also feeds from the standard
    per-flag TRIVY_FAILPOINT binding and trivy.yaml) beat the global
    TRIVY_TPU_FAILPOINTS env var — one resolution path, tested, so the
    two spellings never fight."""
    import os
    env = os.environ if env is None else env
    return ";".join(flag_values or []) \
        or env.get("TRIVY_TPU_FAILPOINTS", "")
