"""Tier-1 gate for graftlint (trivy_tpu/analysis): the tree must be
clean, seeded violations must be caught with file:line findings, the
jaxpr contracts must hold, and the baseline mechanism must suppress
only what it is explicitly told to."""

import json
import os
import sys

from trivy_tpu import analysis
from trivy_tpu.analysis import astlint, crosscheck, jaxpr_check
from trivy_tpu.analysis.__main__ import main as cli_main
from trivy_tpu.analysis.registry import (
    RULES, apply_baseline, load_baseline,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# the tree is clean (the actual CI gate)

def test_tree_is_clean():
    findings = analysis.run_all()
    assert findings == [], "\n".join(f.render() for f in findings)


def test_cli_main_clean_output(tmp_path, capsys):
    """Clean-path CLI formatting/exit code, against a tiny clean tree
    (the full three-engine clean sweep is covered once by
    test_tree_is_clean and end-to-end by the subprocess test)."""
    pkg = tmp_path / "cleanpkg"
    pkg.mkdir()
    (pkg / "mod.py").write_text("X = 1\n")
    assert cli_main(["--root", str(pkg), "--json"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["findings"] == [] and out["suppressed"] == []


# ---------------------------------------------------------------------------
# engine 1: seeded violations on fixture snippets

def _lint(path, src):
    return astlint.lint_source(path, src)


def test_host_sync_in_core_detected():
    src = (
        "import jax\n"
        "def _pair_core(x, y):\n"
        "    n = int(x[0])\n"
        "    return x.item() + n\n"
        "pair = jax.jit(_pair_core)\n"
    )
    fs = _lint("trivy_tpu/ops/fixture.py", src)
    assert [(f.rule, f.line) for f in fs] == [("TPU101", 3),
                                             ("TPU101", 4)]
    # findings carry file:line for CI output
    assert fs[0].render().startswith("trivy_tpu/ops/fixture.py:3:")


def test_shape_access_is_not_a_host_sync():
    src = (
        "import jax\n"
        "def _ok_core(x, t_pad: int):\n"
        "    n = int(x.shape[0])\n"
        "    m = len(x)\n"
        "    return x[:t_pad]\n"
        "j = jax.jit(_ok_core, static_argnums=(1,))\n"
    )
    assert _lint("trivy_tpu/ops/fixture.py", src) == []


def test_numpy_call_in_device_code_detected():
    src = (
        "import jax\n"
        "import numpy as np\n"
        "def _np_core(x):\n"
        "    return np.sum(x)\n"
        "j = jax.jit(_np_core)\n"
    )
    fs = _lint("trivy_tpu/ops/fixture.py", src)
    assert [f.rule for f in fs] == ["TPU101"]
    assert "np.sum" in fs[0].message


def test_data_dependent_control_flow_detected():
    src = (
        "import jax\n"
        "def _branch_core(x, t_pad: int):\n"
        "    if x[0] > 0:\n"
        "        return x\n"
        "    for v in x:\n"
        "        pass\n"
        "    if t_pad > 4:\n"          # static: not flagged
        "        return x\n"
        "    return x\n"
        "j = jax.jit(_branch_core, static_argnums=(1,))\n"
    )
    fs = _lint("trivy_tpu/ops/fixture.py", src)
    assert [(f.rule, f.line) for f in fs] == [("TPU102", 3),
                                             ("TPU102", 5)]


def test_flag_constant_drift_detected():
    # the acceptance-criteria case: a drifted copy of a flag bit in
    # db/table.py must produce a finding
    src = "HAS_LO = 2\nNEEDS_RECHECK = 8\nUNRELATED = 7\n"
    fs = _lint("trivy_tpu/db/table.py", src)
    assert [f.rule for f in fs] == ["TPU103", "TPU103"]
    assert "HAS_LO" in fs[0].message


def test_flag_drift_via_tuple_unpack_detected():
    src = "SATISFIED, NEEDS_RECHECK = 1, 2\n"
    fs = _lint("trivy_tpu/db/table.py", src)
    assert sorted(f.context for f in fs) == ["NEEDS_RECHECK",
                                            "SATISFIED"]
    assert {f.rule for f in fs} == {"TPU103"}


def test_constants_module_itself_is_exempt():
    src = "HAS_LO = 1\n"
    assert _lint("trivy_tpu/ops/constants.py", src) == []


def test_static_argument_hygiene():
    src = (
        "import functools, jax\n"
        "@functools.partial(jax.jit, static_argnames=('cfg',))\n"
        "def f(x, cfg):\n"
        "    return x\n"
    )
    fs = _lint("trivy_tpu/ops/fixture.py", src)
    assert [f.rule for f in fs] == ["TPU104"]

    src_ok = src.replace("cfg):", "cfg: int):")
    assert _lint("trivy_tpu/ops/fixture.py", src_ok) == []

    src_nonlit = (
        "import jax\n"
        "S = (1,)\n"
        "def g(x, t):\n"
        "    return x\n"
        "j = jax.jit(g, static_argnums=S)\n"
    )
    fs = _lint("trivy_tpu/ops/fixture.py", src_nonlit)
    assert [f.rule for f in fs] == ["TPU104"]
    assert "literal" in fs[0].message


def test_debug_in_device_code_detected():
    src = (
        "import jax\n"
        "def _dbg_core(x):\n"
        "    jax.debug.print('x={}', x)\n"
        "    return x\n"
    )
    fs = _lint("trivy_tpu/ops/fixture.py", src)
    assert [(f.rule, f.line) for f in fs] == [("TPU105", 3)]


def test_pallas_kernel_is_device_code():
    src = (
        "from jax.experimental import pallas as pl\n"
        "def my_kern(x_ref, o_ref):\n"
        "    print('trace')\n"
        "def launch(x):\n"
        "    return pl.pallas_call(my_kern, grid=(1,))(x)\n"
    )
    fs = _lint("trivy_tpu/ops/fixture.py", src)
    assert [f.rule for f in fs] == ["TPU105"]


def test_lock_hygiene_detected_including_alias():
    src = (
        "import threading\n"
        "class Reg:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._vals = {}\n"
        "    def bad(self, k):\n"
        "        self._vals[k] = 1\n"
        "    def bad_alias(self, k):\n"
        "        v = self._vals\n"
        "        v.update({k: 2})\n"
        "    def good(self, k):\n"
        "        with self._lock:\n"
        "            self._vals[k] = 3\n"
    )
    fs = _lint("trivy_tpu/server/fixture.py", src)
    assert [(f.rule, f.line) for f in fs] == [("TPU106", 7),
                                             ("TPU106", 10)]
    # v2: the whole tree is in scope — the same class is checked
    # anywhere it lives (the _LOCK_SCOPE path list is gone)
    assert [(f.rule, f.line) for f in
            _lint("trivy_tpu/iac/fixture.py", src)] == [("TPU106", 7),
                                                        ("TPU106", 10)]


def test_lock_hygiene_catches_value_position_mutators():
    src = (
        "import threading\n"
        "class Srv:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._vals = {}\n"
        "    def consumed(self, k):\n"
        "        return self._vals.pop(k)\n"      # mutator in a return
        "    def in_test(self, k):\n"
        "        if self._vals.pop(k):\n"         # mutator in a branch
        "            return 1\n"
        "    def nested(self, k):\n"
        "        def helper():\n"
        "            self._vals[k] = 1\n"         # closure, outside lock
        "        return helper\n"
    )
    fs = _lint("trivy_tpu/server/fixture.py", src)
    assert [(f.rule, f.line) for f in fs] == [("TPU106", 7),
                                             ("TPU106", 9),
                                             ("TPU106", 13)]


def test_instrumentation_in_device_code_detected():
    src = (
        "import time, jax\n"
        "from trivy_tpu.metrics import METRICS\n"
        "from trivy_tpu.obs import span\n"
        "def _timed_core(x):\n"
        "    t0 = time.perf_counter()\n"
        "    with span('detect.inner'):\n"
        "        y = x + 1\n"
        "    METRICS.inc('trivy_tpu_oops_total')\n"
        "    METRICS.observe('trivy_tpu_oops_seconds',\n"
        "                    time.perf_counter() - t0)\n"
        "    return y\n"
        "j = jax.jit(_timed_core)\n"
    )
    fs = _lint("trivy_tpu/ops/fixture.py", src)
    assert all(f.rule == "TPU107" for f in fs)
    # perf_counter x2, span entry, METRICS.inc, METRICS.observe
    assert [f.line for f in fs] == [5, 6, 8, 9, 10]
    assert all(f.context == "_timed_core" for f in fs)


def test_instrumentation_on_host_side_is_fine():
    src = (
        "import time, jax\n"
        "from trivy_tpu.metrics import METRICS\n"
        "from trivy_tpu.obs import span\n"
        "def _ok_core(x):\n"
        "    return x + 1\n"
        "j = jax.jit(_ok_core)\n"
        "def host_wrapper(x):\n"         # host orchestration: allowed
        "    t0 = time.perf_counter()\n"
        "    with span('detect.dispatch'):\n"
        "        y = j(x)\n"
        "    METRICS.observe('trivy_tpu_x_seconds',\n"
        "                    time.perf_counter() - t0)\n"
        "    return y\n"
    )
    assert _lint("trivy_tpu/ops/fixture.py", src) == []


def test_sched_is_in_lock_hygiene_scope():
    """detectd (detect/sched.py) is shared across server handler
    threads and the dispatcher — TPU106 must cover it."""
    src = (
        "import threading\n"
        "class Sched:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._pending = []\n"
        "    def bad(self, req):\n"
        "        self._pending.append(req)\n"
        "    def good(self, req):\n"
        "        with self._lock:\n"
        "            self._pending.append(req)\n"
    )
    fs = _lint("trivy_tpu/detect/sched.py", src)
    assert [(f.rule, f.line) for f in fs] == [("TPU106", 7)]
    # v2: whole-tree scope — the same class is checked anywhere
    assert [(f.rule, f.line) for f in
            _lint("trivy_tpu/report/fixture.py", src)] \
        == [("TPU106", 7)]


def test_sched_no_clocks_in_device_code():
    """TPU107 covers jitted cores wherever they appear — a timed core
    sneaking into detect/sched.py must be caught."""
    src = (
        "import time, jax\n"
        "def _sched_core(x):\n"
        "    t0 = time.perf_counter()\n"
        "    return x + t0\n"
        "j = jax.jit(_sched_core)\n"
    )
    fs = _lint("trivy_tpu/detect/sched.py", src)
    assert [(f.rule, f.line) for f in fs] == [("TPU107", 3)]


def test_resilience_in_device_code_detected():
    """TPU108: failpoint probes, breaker reads, and deadline clocks in
    a jitted core run once at trace time — all three shapes must be
    caught (the TPU107 pattern extended to graftguard)."""
    src = (
        "import jax\n"
        "from trivy_tpu.resilience import (Deadline, FAILPOINTS,\n"
        "                                  GUARD, failpoint)\n"
        "def _guarded_core(x):\n"
        "    failpoint('detect.dispatch')\n"
        "    FAILPOINTS.fire('detect.device_get')\n"
        "    if GUARD.allow_device():\n"
        "        x = x + 1\n"
        "    deadline = Deadline(1.0)\n"
        "    return x + deadline.remaining()\n"
        "j = jax.jit(_guarded_core)\n"
    )
    fs = _lint("trivy_tpu/ops/fixture.py", src)
    assert all(f.rule == "TPU108" for f in fs)
    # failpoint, FAILPOINTS.fire, GUARD.allow_device, Deadline(),
    # deadline.remaining() — the clock-read ban keys on deadline-NAMED
    # values, like TPU107 keys on names
    assert [f.line for f in fs] == [5, 6, 7, 9, 10]
    assert all(f.context == "_guarded_core" for f in fs)


def test_resilience_on_host_side_is_fine():
    src = (
        "import jax\n"
        "from trivy_tpu.resilience import GUARD, failpoint\n"
        "def _plain_core(x):\n"
        "    return x + 1\n"
        "j = jax.jit(_plain_core)\n"
        "def host_wrapper(x):\n"          # host orchestration: allowed
        "    if not GUARD.allow_device():\n"
        "        return None\n"
        "    failpoint('detect.dispatch')\n"
        "    with GUARD.watch('detect.dispatch'):\n"
        "        return j(x)\n"
    )
    assert _lint("trivy_tpu/ops/fixture.py", src) == []


def test_breaker_method_on_breaker_named_value_detected():
    src = (
        "import jax\n"
        "def _b_core(x, my_breaker: tuple):\n"
        "    my_breaker.record_failure()\n"
        "    return x\n"
        "j = jax.jit(_b_core, static_argnums=(1,))\n"
    )
    fs = _lint("trivy_tpu/detect/fixture.py", src)
    assert [(f.rule, f.line) for f in fs] == [("TPU108", 3)]


def test_sched_failpoint_in_device_code_detected():
    """TPU108 covers jitted cores wherever they appear — a failpoint
    sneaking into a detect/sched.py core must be caught."""
    src = (
        "import jax\n"
        "from trivy_tpu.resilience import failpoint\n"
        "def _sched_core(x):\n"
        "    failpoint('detect.dispatch')\n"
        "    return x + 1\n"
        "j = jax.jit(_sched_core)\n"
    )
    fs = _lint("trivy_tpu/detect/sched.py", src)
    assert [(f.rule, f.line) for f in fs] == [("TPU108", 4)]


def test_feed_staging_state_in_lock_hygiene_scope():
    """Satellite (PR 18): graftfeed's staged-upload bookkeeping is
    shared between handler threads and the dispatcher — TPU106 must
    cover detect/feed.py like the rest of the detect package."""
    src = (
        "import threading\n"
        "class Stager:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._staged = {}\n"
        "    def bad(self, k, s):\n"
        "        self._staged[k] = s\n"
        "    def good(self, k, s):\n"
        "        with self._lock:\n"
        "            self._staged[k] = s\n"
    )
    fs = _lint("trivy_tpu/detect/feed.py", src)
    assert [(f.rule, f.line) for f in fs] == [("TPU106", 7)]


def test_feed_no_clocks_in_device_code():
    """Satellite (PR 18): the scatter-back must stay host-side — a
    stall clock leaking into a jitted expand core in detect/feed.py
    is TPU107 material."""
    src = (
        "import time, jax\n"
        "def _expand_core(bits_u, take):\n"
        "    t0 = time.perf_counter()\n"
        "    return bits_u[take] + t0\n"
        "j = jax.jit(_expand_core)\n"
    )
    fs = _lint("trivy_tpu/detect/feed.py", src)
    assert [(f.rule, f.line) for f in fs] == [("TPU107", 3)]


def test_feed_upload_failpoint_in_device_code_detected():
    """The detect.query_upload probe is a HOST call site; one traced
    into a jitted core would fire once at trace time — TPU108 must
    catch it."""
    src = (
        "import jax\n"
        "from trivy_tpu.resilience import failpoint\n"
        "def _upload_core(cols):\n"
        "    failpoint('detect.query_upload')\n"
        "    return cols\n"
        "j = jax.jit(_upload_core)\n"
    )
    assert [(f.rule, f.line) for f in
            _lint("trivy_tpu/detect/feed.py", src)] \
        == [("TPU108", 4)]


def test_parallel_rebuild_code_in_lock_hygiene_scope():
    """Satellite (PR 5): the whole parallel/ package — the meshguard
    rebuild/coordinator surface and the ingest queue are shared across
    handler threads, the dispatcher, and the maintenance thread — is
    in TPU106 scope."""
    src = (
        "import threading\n"
        "class Rebuilder:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._lost = []\n"
        "    def bad(self, dev):\n"
        "        self._lost.append(dev)\n"
        "    def good(self, dev):\n"
        "        with self._lock:\n"
        "            self._lost.append(dev)\n"
    )
    fs = _lint("trivy_tpu/parallel/mesh.py", src)
    assert [(f.rule, f.line) for f in fs] == [("TPU106", 7)]
    # v2: whole-tree scope — the same class is checked anywhere
    assert [(f.rule, f.line) for f in
            _lint("trivy_tpu/report/fixture.py", src)] \
        == [("TPU106", 7)]


def test_shard_map_body_is_device_code_for_tpu108():
    """Satellite (PR 5): a failpoint probe or breaker read inside a
    shard_map body runs once at trace time, exactly like in a jitted
    core — TPU108 must see inside the mesh path's collective
    launches."""
    src = (
        "from jax.experimental.shard_map import shard_map\n"
        "from trivy_tpu.resilience import GUARD, failpoint\n"
        "def _mesh_local(x):\n"
        "    failpoint('detect.mesh:0')\n"
        "    if GUARD.allow_device():\n"
        "        x = x + 1\n"
        "    return x\n"
        "f = shard_map(_mesh_local, mesh=None, in_specs=(),\n"
        "              out_specs=())\n"
    )
    fs = _lint("trivy_tpu/parallel/mesh.py", src)
    assert [(f.rule, f.line) for f in fs] == [("TPU108", 4),
                                              ("TPU108", 5)]
    assert all(f.context == "_mesh_local" for f in fs)


def test_shard_map_body_clock_is_tpu107():
    """TPU107 rides the same shard_map device-fn detection: a clock
    read inside the per-device local function measures trace time."""
    src = (
        "import time\n"
        "from jax import shard_map\n"
        "def local(x):\n"
        "    t0 = time.perf_counter()\n"
        "    return x + t0\n"
        "f = shard_map(local, mesh=None, in_specs=(), out_specs=())\n"
    )
    fs = _lint("trivy_tpu/parallel/mesh.py", src)
    assert [(f.rule, f.line) for f in fs] == [("TPU107", 4)]


def test_fleet_in_lock_hygiene_scope():
    """Satellite (PR 6): trivy_tpu/fleet/ — the ring and replica
    supervisor are shared across router handler threads and the
    readmission loop — is in TPU106 scope."""
    src = (
        "import threading\n"
        "class Ring:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._points = []\n"
        "    def bad(self, p):\n"
        "        self._points.append(p)\n"
        "    def good(self, p):\n"
        "        with self._lock:\n"
        "            self._points.append(p)\n"
    )
    fs = _lint("trivy_tpu/fleet/ring.py", src)
    assert [(f.rule, f.line) for f in fs] == [("TPU106", 7)]
    # v2: whole-tree scope — the same class is checked anywhere
    assert [(f.rule, f.line) for f in
            _lint("trivy_tpu/report/fixture.py", src)] \
        == [("TPU106", 7)]


def test_fleet_clock_in_device_code_detected():
    """Satellite (PR 6): TPU107 covers jitted cores wherever they
    appear — a timed core sneaking into fleet/ must be caught."""
    src = (
        "import time, jax\n"
        "def _route_core(x):\n"
        "    t0 = time.perf_counter()\n"
        "    return x + t0\n"
        "j = jax.jit(_route_core)\n"
    )
    fs = _lint("trivy_tpu/fleet/router.py", src)
    assert [(f.rule, f.line) for f in fs] == [("TPU107", 3)]


def test_fleet_failpoint_in_device_code_detected():
    """Satellite (PR 6): TPU108 — a failpoint probe or breaker read in
    a jitted core inside fleet/ must be caught."""
    src = (
        "import jax\n"
        "from trivy_tpu.resilience import GUARD, failpoint\n"
        "def _fleet_core(x):\n"
        "    failpoint('rpc.route')\n"
        "    if GUARD.allow_device():\n"
        "        x = x + 1\n"
        "    return x\n"
        "j = jax.jit(_fleet_core)\n"
    )
    fs = _lint("trivy_tpu/fleet/supervisor.py", src)
    assert [(f.rule, f.line) for f in fs] == [("TPU108", 4),
                                              ("TPU108", 5)]


def test_resilience_registry_in_lock_hygiene_scope():
    """Satellite: the failpoint registry (trivy_tpu/resilience/) is
    shared across handler threads and the watchdog — TPU106 must
    cover it."""
    src = (
        "import threading\n"
        "class Registry:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._specs = {}\n"
        "    def bad(self, site, spec):\n"
        "        self._specs[site] = spec\n"
        "    def good(self, site, spec):\n"
        "        with self._lock:\n"
        "            self._specs[site] = spec\n"
    )
    fs = _lint("trivy_tpu/resilience/failpoints.py", src)
    assert [(f.rule, f.line) for f in fs] == [("TPU106", 7)]
    # v2: whole-tree scope — the same class is checked anywhere
    assert [(f.rule, f.line) for f in
            _lint("trivy_tpu/report/fixture.py", src)] \
        == [("TPU106", 7)]


def test_regex_match_span_is_not_a_trace_span():
    # m.span() (re.Match) in device code must not trip the span ban;
    # it is caught by nothing here (host-ish API, but not TPU107's
    # target) — the rule keys on the bare/obs-qualified name only
    src = (
        "import jax\n"
        "def _m_core(x, m: tuple):\n"
        "    s, e = m\n"
        "    return x[s:e]\n"
        "j = jax.jit(_m_core, static_argnums=(1,))\n"
    )
    assert _lint("trivy_tpu/ops/fixture.py", src) == []


def test_seeded_violation_in_real_pair_core():
    """The acceptance-criteria demo: an int() on a traced value seeded
    into the REAL _pair_core source produces a file:line finding."""
    with open(os.path.join(REPO, "trivy_tpu", "ops", "join.py")) as f:
        src = f.read()
    marker = "    flags = adv_flags[pair_row]"
    assert marker in src
    seeded = src.replace(
        marker, "    bad = int(adv_flags[0])\n" + marker)
    fs = _lint("trivy_tpu/ops/join.py", seeded)
    assert [f.rule for f in fs] == ["TPU101"]
    assert fs[0].context == "_pair_core"
    assert fs[0].line == seeded[:seeded.index("bad = int")].count("\n") + 1


# ---------------------------------------------------------------------------
# engine 2: jaxpr contracts

def _contract(name):
    with open(os.path.join(REPO, "trivy_tpu", "analysis", "contracts",
                           name)) as f:
        return json.load(f)


def test_contracts_hold_on_tree():
    assert jaxpr_check.run() == []


def test_primitive_budget_catches_unroll():
    c = _contract("csr_pair_join.json")
    c["max_primitives"] = 1
    c.pop("golden", None)
    fs = jaxpr_check.check_contract("csr_pair_join.json", c)
    assert [f.rule for f in fs] == ["JAX204"]


def test_unexpected_convert_is_a_finding():
    c = _contract("pair_join.json")
    c["allowed_converts"] = [["bool", "int32"]]  # drop the int8 packing
    fs = jaxpr_check.check_contract("pair_join.json", c)
    assert {f.rule for f in fs} == {"JAX202"}
    assert any("bool→int8" in f.message for f in fs)


def test_output_dtype_drift_is_a_finding():
    c = _contract("pair_join.json")
    c["out_dtypes"] = ["int32"]
    fs = jaxpr_check.check_contract("pair_join.json", c)
    assert [f.rule for f in fs] == ["JAX201"]


def test_trace_failure_is_reported_not_raised():
    c = _contract("pair_join.json")
    c["args"] = c["args"][:2]  # wrong arity
    fs = jaxpr_check.check_contract("pair_join.json", c)
    assert [f.rule for f in fs] == ["JAX205"]


def test_golden_jaxpr_diff_detected(tmp_path, monkeypatch):
    src_dir = os.path.join(REPO, "trivy_tpu", "analysis", "contracts")
    golden = tmp_path / "csr_pair_join.jaxpr.txt"
    with open(os.path.join(src_dir, "csr_pair_join.jaxpr.txt")) as f:
        lines = f.read().splitlines()
    lines[5] = lines[5] + "  # drifted"
    golden.write_text("\n".join(lines) + "\n")
    c = _contract("csr_pair_join.json")
    monkeypatch.setattr(jaxpr_check, "CONTRACTS_DIR", str(tmp_path))
    fs = jaxpr_check.check_contract("csr_pair_join.json", c)
    assert [f.rule for f in fs] == ["JAX206"]
    assert fs[0].line == 6


def test_compact_contract_budget_catches_unroll():
    c = _contract("csr_pair_join_compact.json")
    c["max_primitives"] = 10
    c.pop("golden", None)
    fs = jaxpr_check.check_contract("csr_pair_join_compact.json", c)
    assert [f.rule for f in fs] == ["JAX204"]


def test_compact_contract_forbidden_primitive_sees_epilogue():
    """The no-sort ban must actually see the compaction epilogue's
    primitives: forbidding cumsum (which the epilogue's prefix scan
    lowers to) proves a sort would be caught the same way."""
    c = _contract("csr_pair_join_compact.json")
    c["forbidden_primitives"] = ["cumsum"]
    c.pop("golden", None)
    fs = jaxpr_check.check_contract("csr_pair_join_compact.json", c)
    assert fs and {f.rule for f in fs} == {"JAX203"}
    assert any("cumsum" in f.message for f in fs)


def test_compact_contract_convert_allowlist_enforced():
    c = _contract("csr_pair_join_compact.json")
    c["allowed_converts"] = [["bool", "int8"], ["int32", "int32"]]
    c.pop("golden", None)
    fs = jaxpr_check.check_contract("csr_pair_join_compact.json", c)
    # the epilogue's mask widening (bool→int32 for the prefix scan)
    # is no longer allowlisted
    assert fs and {f.rule for f in fs} == {"JAX202"}
    assert any("bool→int32" in f.message for f in fs)


def test_iter_eqns_sees_inside_cond_branches():
    """The host-callback ban must see through lax.cond: its sub-jaxprs
    live in a tuple param ('branches'), not a bare ClosedJaxpr."""
    import jax
    import jax.numpy as jnp

    def f(x):
        return jax.lax.cond(x[0] > 0,
                            lambda v: jnp.sum(v).astype(jnp.float32),
                            lambda v: jnp.float32(0.0), x)

    closed = jax.make_jaxpr(f)(jax.ShapeDtypeStruct((4,), jnp.int32))
    prims = {e.primitive.name for e in jaxpr_check._iter_eqns(
        closed.jaxpr)}
    assert "cond" in prims
    # reduce_sum only exists inside the true branch
    assert "reduce_sum" in prims


def test_shiftor_contract_budget_catches_unroll():
    """The secret kernel loops over keywords on the device; a budget
    below its primitive count reports JAX204, as a statically unrolled
    keyword or column loop would against the real budget."""
    c = _contract("secret_shiftor.json")
    c["max_primitives"] = 40
    c.pop("golden", None)
    fs = jaxpr_check.check_contract("secret_shiftor.json", c)
    assert [f.rule for f in fs] == ["JAX204"]


def test_shiftor_contract_convert_allowlist_enforced():
    c = _contract("secret_shiftor.json")
    c["allowed_converts"] = [
        p for p in c["allowed_converts"] if p != ["bool", "int32"]]
    c.pop("golden", None)
    fs = jaxpr_check.check_contract("secret_shiftor.json", c)
    # the kernel's per-word equality fold (bool→int32 for the Mosaic-
    # safe AND chain) is no longer allowlisted
    assert fs and {f.rule for f in fs} == {"JAX202"}
    assert any("bool→int32" in f.message for f in fs)


def test_shiftor_contract_dtype_surface_enforced():
    c = _contract("secret_shiftor.json")
    c["out_dtypes"] = ["uint32"]
    c.pop("golden", None)
    fs = jaxpr_check.check_contract("secret_shiftor.json", c)
    assert any(f.rule == "JAX201" for f in fs)


def test_shiftor_contract_host_callback_ban_sees_kernel():
    """The host-callback ban must see INSIDE the pallas_call lowering:
    forbidding a primitive the kernel genuinely uses (broadcast_in_dim,
    the column→lane fan-out) proves an io_callback would be caught the
    same way."""
    c = _contract("secret_shiftor.json")
    c["forbidden_primitives"] = ["broadcast_in_dim"]
    c.pop("golden", None)
    fs = jaxpr_check.check_contract("secret_shiftor.json", c)
    assert fs and {f.rule for f in fs} == {"JAX203"}
    assert any("broadcast_in_dim" in f.message for f in fs)


def test_golden_snapshots_are_current():
    """The checked-in pretty-printed jaxprs match the live lowering —
    a hot-path change must regenerate them (and show up in review)."""
    for name in ("csr_pair_join.json", "csr_pair_join_compact.json",
                 "secret_shiftor.json"):
        c = _contract(name)
        closed = jaxpr_check.trace_contract(c)
        text = jaxpr_check.normalize_jaxpr_text(str(closed))
        with open(os.path.join(REPO, "trivy_tpu", "analysis",
                               "contracts", c["golden"])) as f:
            assert f.read() == text, (
                f"{c['golden']} is stale: run "
                f"python -m trivy_tpu.analysis --update-goldens")


# ---------------------------------------------------------------------------
# cross-checker

def test_crosscheck_clean():
    assert crosscheck.run() == []


def test_crosscheck_catches_report_bit_overlap(monkeypatch):
    from trivy_tpu.ops import constants as C
    monkeypatch.setattr(C, "REPORT_BITS",
                        {"SATISFIED": 1, "NEEDS_RECHECK": 1})
    fs = crosscheck.check_schema()
    assert any("overlaps" in f.message for f in fs)


def test_crosscheck_catches_schema_drift(monkeypatch):
    from trivy_tpu.ops import constants as C
    drifted = dict(C.TABLE_SCHEMA, flags=("int8", 1))
    monkeypatch.setattr(C, "TABLE_SCHEMA", drifted)
    fs = crosscheck.check_schema()
    assert any("table.flags dtype" in f.message for f in fs)


# ---------------------------------------------------------------------------
# CLI: exit codes, --json, --baseline

def _seed_bad_tree(tmp_path):
    pkg = tmp_path / "badpkg"
    pkg.mkdir()
    (pkg / "mod.py").write_text(
        "import jax\n"
        "def _bad_core(x):\n"
        "    return int(x[0])\n"
        "j = jax.jit(_bad_core)\n"
    )
    return str(pkg)


def test_cli_nonzero_on_findings(tmp_path, capsys):
    root = _seed_bad_tree(tmp_path)
    assert cli_main(["--root", root]) == 1
    out = capsys.readouterr().out
    assert "TPU101" in out and "mod.py:3" in out


def test_cli_json_output(tmp_path, capsys):
    root = _seed_bad_tree(tmp_path)
    assert cli_main(["--root", root, "--json"]) == 1
    data = json.loads(capsys.readouterr().out)
    assert data["findings"][0]["rule"] == "TPU101"
    assert data["findings"][0]["line"] == 3
    assert data["findings"][0]["fingerprint"]


def test_cli_baseline_suppresses_explicitly(tmp_path, capsys):
    root = _seed_bad_tree(tmp_path)
    cli_main(["--root", root, "--json"])
    fp = json.loads(capsys.readouterr().out)["findings"][0]["fingerprint"]
    baseline = tmp_path / "baseline.json"
    baseline.write_text(json.dumps({"suppressions": [
        {"fingerprint": fp, "reason": "known: fixture for the docs"},
    ]}))
    assert cli_main(["--root", root, "--baseline", str(baseline)]) == 0
    out = capsys.readouterr().out
    assert "suppressed" in out

    # a reason is mandatory — silent suppression is rejected
    baseline.write_text(json.dumps({"suppressions": [
        {"fingerprint": fp},
    ]}))
    assert cli_main(["--root", root,
                     "--baseline", str(baseline)]) == 2


def test_baseline_fingerprint_is_line_independent(tmp_path):
    root = _seed_bad_tree(tmp_path)
    f1 = astlint.run(root)[0]
    # same finding, shifted by a comment line above
    (tmp_path / "badpkg" / "mod.py").write_text(
        "# moved\nimport jax\n"
        "def _bad_core(x):\n"
        "    return int(x[0])\n"
        "j = jax.jit(_bad_core)\n"
    )
    f2 = astlint.run(root)[0]
    assert f1.line != f2.line
    assert f1.fingerprint() == f2.fingerprint()
    active, hits = apply_baseline([f2], {f1.fingerprint()})
    assert active == [] and len(hits) == 1


def test_list_rules_covers_all_engines(capsys):
    assert cli_main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rid in ("TPU101", "TPU102", "TPU103", "TPU104", "TPU105",
                "TPU106", "TPU107", "TPU108", "JAX201", "JAX204",
                "JAX206", "XCHK301"):
        assert rid in out
    assert set(RULES) >= {"TPU101", "XCHK301"}


def test_cli_subprocess_end_to_end(tmp_path):
    """The real `python -m trivy_tpu.analysis --json` invocation —
    the tier-1 registration of the CLI gate (pays one fresh jax
    import, ~8s, within the <10s tier-1 budget)."""
    import subprocess
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "trivy_tpu.analysis", "--json"],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert json.loads(proc.stdout)["findings"] == []


def test_list_rules_in_fresh_process():
    """The registry must populate on package import — a fresh
    `--list-rules` process (no prior engine imports) sees every rule.
    Cheap: this path never imports jax."""
    import subprocess
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "trivy_tpu.analysis", "--list-rules"],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=60)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    for rid in ("TPU100", "TPU106", "JAX201", "XCHK301"):
        assert rid in proc.stdout


def test_load_baseline_roundtrip(tmp_path):
    p = tmp_path / "b.json"
    p.write_text(json.dumps({"suppressions": [
        {"fingerprint": "abc123", "reason": "r"}]}))
    assert load_baseline(str(p)) == {"abc123"}


# ---------------------------------------------------------------------------
# TPU109 — metric hygiene (graftwatch satellite)

def _metric_catalog():
    from trivy_tpu.analysis import metrics_catalog as mc
    return mc.load_catalog(
        'from trivy_tpu.metrics import METRICS\n'
        'METRICS.declare("t_scans_total", "counter", "Scans.")\n'
        'METRICS.declare("t_lat_seconds", "histogram", "Latency.",\n'
        '                buckets=(0.1, 1.0))\n'
        'METRICS.declare("t_depth", "gauge", "Depth.")\n')


def test_tpu109_catalog_loader_parses_declares():
    cat = _metric_catalog()
    assert {n: s.kind for n, s in cat.items()} == {
        "t_scans_total": "counter", "t_lat_seconds": "histogram",
        "t_depth": "gauge"}
    assert cat["t_scans_total"].help == "Scans."


def test_tpu109_undeclared_series_detected():
    from trivy_tpu.analysis.metrics_catalog import lint_metric_calls
    src = (
        "from ..metrics import METRICS\n"
        "def f():\n"
        "    METRICS.inc('t_scans_total')\n"        # declared: ok
        "    METRICS.inc('t_typo_total')\n"         # undeclared
        "    METRICS.observe('t_nope_seconds', 1)\n"  # undeclared
    )
    fs = list(lint_metric_calls("trivy_tpu/x.py", src,
                                _metric_catalog()))
    assert [(f.rule, f.line) for f in fs] == [("TPU109", 4),
                                              ("TPU109", 5)]
    assert "not declared" in fs[0].message


def test_tpu109_method_type_mismatch_detected():
    from trivy_tpu.analysis.metrics_catalog import lint_metric_calls
    src = (
        "from ..metrics import METRICS\n"
        "METRICS.inc('t_lat_seconds')\n"        # histogram via inc
        "METRICS.observe('t_depth', 2.0)\n"     # gauge via observe
        "METRICS.set_gauge('t_scans_total', 1)\n"  # counter via gauge
        "METRICS.gauge_add('t_depth', 1)\n"     # ok
        "METRICS.observe('t_lat_seconds', 1)\n"  # ok
        "METRICS.get('t_depth')\n"              # read of declared: ok
    )
    fs = list(lint_metric_calls("trivy_tpu/x.py", src,
                                _metric_catalog()))
    assert [(f.rule, f.line) for f in fs] == [("TPU109", 2),
                                              ("TPU109", 3),
                                              ("TPU109", 4)]
    assert "declares histogram" in fs[0].message


def test_tpu109_dynamic_names_and_other_objects_skipped():
    from trivy_tpu.analysis.metrics_catalog import lint_metric_calls
    src = (
        "from ..metrics import METRICS, Registry\n"
        "r = Registry()\n"
        "def f(name):\n"
        "    METRICS.inc(name)\n"            # dynamic: out of reach
        "    METRICS.set_gauge(f'{name}_x', 1)\n"  # dynamic
        "    r.inc('t_not_in_catalog')\n"    # not the METRICS object
    )
    assert list(lint_metric_calls("trivy_tpu/x.py", src,
                                  _metric_catalog())) == []


def test_tpu109_real_catalog_is_complete_and_tree_conforms():
    """The real metrics.py catalog must declare every series with a
    literal type and help, and every literal call site under
    trivy_tpu/ must conform (the rule also runs inside
    test_tree_is_clean; this pins the engine specifically)."""
    from trivy_tpu.analysis.metrics_catalog import (check_metric_hygiene,
                                                    load_catalog)
    cat = load_catalog()
    assert len(cat) >= 25
    assert all(s.kind in ("counter", "gauge", "histogram")
               for s in cat.values())
    assert all(s.help for s in cat.values())
    assert check_metric_hygiene() == []


def test_metrics_reference_in_architecture_is_current():
    """The ARCHITECTURE.md metrics table is GENERATED from the
    catalog: drift fails tier-1, exactly like a golden."""
    from trivy_tpu.analysis import metrics_catalog as mc
    with open(os.path.join(REPO, "ARCHITECTURE.md")) as f:
        doc = f.read()
    assert mc.DOC_BEGIN in doc and mc.DOC_END in doc
    block = doc.split(mc.DOC_BEGIN, 1)[1].split(mc.DOC_END, 1)[0]
    assert block.strip() == mc.render_markdown().strip(), (
        "ARCHITECTURE.md metrics catalog drifted; regenerate with "
        "trivy_tpu.analysis.metrics_catalog.render_markdown()")


def test_storm_is_in_lock_hygiene_scope():
    """Satellite (PR 8): graftstorm (resilience/storm.py) — the
    schedule driver, load workers, and invariant collectors share
    state across threads — is in TPU106 scope like the rest of
    resilience/."""
    src = (
        "import threading\n"
        "class Driver:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._actions = []\n"
        "    def bad(self, a):\n"
        "        self._actions.append(a)\n"
        "    def good(self, a):\n"
        "        with self._lock:\n"
        "            self._actions.append(a)\n"
    )
    fs = _lint("trivy_tpu/resilience/storm.py", src)
    assert [(f.rule, f.line) for f in fs] == [("TPU106", 7)]


def test_storm_no_clocks_or_metrics_in_device_code():
    """Satellite (PR 8): TPU107 — a timed/metered core sneaking into
    storm helper code must be caught (storm is host-side by charter)."""
    src = (
        "import time, jax\n"
        "from trivy_tpu.metrics import METRICS\n"
        "def _storm_core(x):\n"
        "    METRICS.inc('trivy_tpu_oops_total')\n"
        "    return x + time.perf_counter()\n"
        "j = jax.jit(_storm_core)\n"
    )
    fs = _lint("trivy_tpu/resilience/storm.py", src)
    assert [(f.rule, f.line) for f in fs] == [("TPU107", 4),
                                              ("TPU107", 5)]


def test_storm_no_failpoints_in_device_code():
    """Satellite (PR 8): TPU108 — a failpoint probe or breaker read in
    a jitted core inside storm code fires the resilience-in-device-code
    rule."""
    src = (
        "import jax\n"
        "from trivy_tpu.resilience import GUARD, failpoint\n"
        "def _storm_core(x):\n"
        "    failpoint('detect.dispatch')\n"
        "    if GUARD.allow_device():\n"
        "        x = x + 1\n"
        "    return x\n"
        "j = jax.jit(_storm_core)\n"
    )
    fs = _lint("trivy_tpu/resilience/storm.py", src)
    assert [(f.rule, f.line) for f in fs] == [("TPU108", 4),
                                              ("TPU108", 5)]


def test_fanald_pipeline_in_lock_hygiene_scope():
    """Satellite (PR 9): fanald (fanal/pipeline.py) — the ingest
    supervisor, byte budget, and per-layer state are shared across
    walker threads, the analyzer pool, and the watchdog — is in
    TPU106 scope."""
    src = (
        "import threading\n"
        "class Budget:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._counters = {}\n"
        "    def bad(self, k):\n"
        "        self._counters[k] = 1\n"
        "    def good(self, k):\n"
        "        with self._lock:\n"
        "            self._counters[k] = 1\n"
    )
    fs = _lint("trivy_tpu/fanal/pipeline.py", src)
    assert [(f.rule, f.line) for f in fs] == [("TPU106", 7)]
    # v2: the rest of fanal/ is checked too — whole-tree scope
    assert [(f.rule, f.line) for f in
            _lint("trivy_tpu/fanal/walker.py", src)] \
        == [("TPU106", 7)]


def test_fanald_no_clocks_in_device_code():
    """Satellite (PR 9): TPU107 — a timed core sneaking into fanald
    (host-side by charter) must be caught."""
    src = (
        "import time, jax\n"
        "def _walk_core(x):\n"
        "    return x + time.perf_counter()\n"
        "j = jax.jit(_walk_core)\n"
    )
    fs = _lint("trivy_tpu/fanal/pipeline.py", src)
    assert [(f.rule, f.line) for f in fs] == [("TPU107", 3)]


def test_fanald_no_failpoints_in_device_code():
    """Satellite (PR 9): TPU108 — the fanal.walk/fanal.analyze
    failpoint probes and ingest breaker reads belong on the host side
    of fanald; inside a jitted core they run once at trace time."""
    src = (
        "import jax\n"
        "from trivy_tpu.resilience import failpoint\n"
        "def _walk_core(x):\n"
        "    failpoint('fanal.walk')\n"
        "    return x\n"
        "j = jax.jit(_walk_core)\n"
    )
    fs = _lint("trivy_tpu/fanal/pipeline.py", src)
    assert [(f.rule, f.line) for f in fs] == [("TPU108", 4)]


def test_fanal_failpoint_sites_in_catalog():
    """Satellite (PR 9): the fanal.walk / fanal.analyze sites parse
    under the spec grammar and are schedulable."""
    from trivy_tpu.resilience.failpoints import parse_spec
    specs = parse_spec("fanal.walk=hang:100;fanal.analyze=flaky:0.2:7")
    assert set(specs) == {"fanal.walk", "fanal.analyze"}
    try:
        parse_spec("fanal.wlak=error")
    except ValueError:
        pass
    else:
        raise AssertionError("typo'd fanal site must fail at parse")


def test_secret_prefilter_failpoint_site_in_catalog():
    """Satellite (PR 12): the secret.prefilter site parses under the
    spec grammar and is schedulable by storm's ingest menu."""
    from trivy_tpu.resilience.failpoints import parse_spec
    specs = parse_spec("secret.prefilter=hang:100")
    assert set(specs) == {"secret.prefilter"}
    try:
        parse_spec("secret.prefliter=error")
    except ValueError:
        pass
    else:
        raise AssertionError("typo'd secret site must fail at parse")


def test_graftmemo_store_in_lock_hygiene_scope():
    """Satellite (PR 11): fleet/memo.py — one MemoStore is shared
    across server handler threads and the redetectd sweep (known-blob
    registry, per-key stats) — rides the fleet/ TPU106 scope."""
    src = (
        "import threading\n"
        "class Store:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._known = {}\n"
        "    def bad(self, k):\n"
        "        self._known[k] = None\n"
        "    def good(self, k):\n"
        "        with self._lock:\n"
        "            self._known[k] = None\n"
    )
    fs = _lint("trivy_tpu/fleet/memo.py", src)
    assert [(f.rule, f.line) for f in fs] == [("TPU106", 7)]


def test_redetectd_in_lock_hygiene_scope():
    """Satellite (PR 11): detect/redetect.py — the sweep daemon's
    status/thread handoff is shared between handler threads
    (swap_table → schedule), the sweep thread, and the drain path —
    is in TPU106 scope (v2: like everything else)."""
    src = (
        "import threading\n"
        "class Daemon:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._status = {}\n"
        "    def bad(self):\n"
        "        self._status['phase'] = 'idle'\n"
        "    def good(self):\n"
        "        with self._lock:\n"
        "            self._status['phase'] = 'idle'\n"
    )
    fs = _lint("trivy_tpu/detect/redetect.py", src)
    assert [(f.rule, f.line) for f in fs] == [("TPU106", 7)]
    # v2: whole-tree scope — the same class is checked anywhere
    assert [(f.rule, f.line) for f in
            _lint("trivy_tpu/report/fixture.py", src)] \
        == [("TPU106", 7)]


def test_memo_failpoint_sites_in_catalog():
    """Satellite (PR 11): the memo.get / memo.put sites parse under
    the spec grammar and are schedulable."""
    from trivy_tpu.resilience.failpoints import parse_spec
    specs = parse_spec("memo.get=error;memo.put=flaky:0.3:11")
    assert set(specs) == {"memo.get", "memo.put"}
    try:
        parse_spec("memo.gte=error")
    except ValueError:
        pass
    else:
        raise AssertionError("typo'd memo site must fail at parse")


def test_obs_perf_in_lock_hygiene_scope():
    """Satellite (PR 13): graftprof (obs/perf.py) — one LEDGER/PROF
    is shared across every handler thread, the detectd dispatcher,
    and the auto-capture thread — rides obs/'s TPU106 scope."""
    src = (
        "import threading\n"
        "class Ledger:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._shapes = {}\n"
        "    def bad(self, k):\n"
        "        self._shapes[k] = 1\n"
        "    def good(self, k):\n"
        "        with self._lock:\n"
        "            self._shapes[k] = 1\n"
    )
    fs = _lint("trivy_tpu/obs/perf.py", src)
    assert [(f.rule, f.line) for f in fs] == [("TPU106", 7)]
    # v2: whole-tree scope — the same class is checked anywhere
    assert [(f.rule, f.line) for f in
            _lint("trivy_tpu/report/fixture.py", src)] \
        == [("TPU106", 7)]


def test_obs_perf_no_clocks_or_metrics_in_device_code():
    """Satellite (PR 13): TPU107 — graftprof is host orchestration by
    charter; a ledger note's clock read or METRICS write inside a
    jitted core would time the trace and count compilations, so a
    seeded violation in obs/perf.py must be caught."""
    src = (
        "import time, jax\n"
        "from trivy_tpu.metrics import METRICS\n"
        "def _ledger_core(x):\n"
        "    t0 = time.perf_counter()\n"
        "    METRICS.observe('trivy_tpu_device_compile_ms', t0)\n"
        "    return x + 1\n"
        "j = jax.jit(_ledger_core)\n"
    )
    fs = _lint("trivy_tpu/obs/perf.py", src)
    assert [(f.rule, f.line) for f in fs] == [("TPU107", 4),
                                              ("TPU107", 5)]


def test_obs_perf_no_resilience_in_device_code():
    """Satellite (PR 13): TPU108 — the profiler's admission/breaker
    reads stay on the host; a seeded GUARD/failpoint use inside a
    jitted core in obs/perf.py must be caught."""
    src = (
        "import jax\n"
        "from trivy_tpu.resilience import GUARD, failpoint\n"
        "def _prof_core(x):\n"
        "    failpoint('profile.capture')\n"
        "    if GUARD.allow_device():\n"
        "        x = x + 1\n"
        "    return x\n"
        "j = jax.jit(_prof_core)\n"
    )
    fs = _lint("trivy_tpu/obs/perf.py", src)
    assert [(f.rule, f.line) for f in fs] == [("TPU108", 4),
                                              ("TPU108", 5)]


def test_device_series_in_catalog():
    """Satellite (PR 13): every trivy_tpu_device_* series graftprof
    emits is declared in the metrics.py catalog with type + help —
    TPU109 closes the loop from call site to catalog."""
    from trivy_tpu.analysis.metrics_catalog import load_catalog
    cat = load_catalog()
    want = {
        "trivy_tpu_device_dispatches_total": "counter",
        "trivy_tpu_device_padding_waste_ratio": "histogram",
        "trivy_tpu_device_compile_ms": "histogram",
        "trivy_tpu_device_transfer_bytes_total": "counter",
        "trivy_tpu_device_hit_budget_adaptations_total": "counter",
        "trivy_tpu_device_hbm_bytes": "gauge",
        "trivy_tpu_device_resident_bytes": "gauge",
        "trivy_tpu_profile_captures_total": "counter",
    }
    for name, kind in want.items():
        assert name in cat, name
        assert cat[name].kind == kind
        assert cat[name].help


# ---------------------------------------------------------------------------
# graftlint v2: concurrency engine (TPU110-113), planted fixtures


def _conc_tree(tmp_path, files):
    """Write a fixture package and run the concurrency engine over it.
    No lockgraph gate: a fixture tree has no checked-in artifact."""
    from trivy_tpu.analysis import concurrency
    pkg = tmp_path / "fixpkg"
    pkg.mkdir(exist_ok=True)
    for name, src in files.items():
        p = pkg / name
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(src)
    return concurrency.run(root=str(pkg))


def test_lock_order_cycle_detected(tmp_path):
    """Two methods acquiring the same two locks in opposite order is a
    real deadlock: TPU110 names the cycle and both acquisition sites."""
    src = (
        "import threading\n"
        "\n"
        "class Pair:\n"
        "    def __init__(self):\n"
        "        self._a = threading.Lock()\n"
        "        self._b = threading.Lock()\n"
        "\n"
        "    def forward(self):\n"
        "        with self._a:\n"
        "            with self._b:\n"
        "                return 1\n"
        "\n"
        "    def backward(self):\n"
        "        with self._b:\n"
        "            with self._a:\n"
        "                return 2\n"
    )
    fs = _conc_tree(tmp_path, {"pair.py": src})
    cyc = [f for f in fs if f.rule == "TPU110"
           and "lock-order cycle" in f.message]
    assert len(cyc) == 1, "\n".join(f.render() for f in fs)
    assert "Pair._a" in cyc[0].message and "Pair._b" in cyc[0].message
    assert "forward" in cyc[0].message and "backward" in cyc[0].message


def test_double_acquire_detected(tmp_path):
    """Re-entering a non-reentrant Lock self-deadlocks: both the
    direct nested `with` and the one-level interprocedural case
    (method under the lock calls a self-method that takes it again).
    The RLock twin of the interprocedural case is legal and clean."""
    direct = (
        "import threading\n"
        "MU = threading.Lock()\n"
        "\n"
        "def grab():\n"
        "    with MU:\n"
        "        with MU:\n"
        "            return 1\n"
    )
    inter = (
        "import threading\n"
        "\n"
        "class Box:\n"
        "    def __init__(self):\n"
        "        self._mu = threading.Lock()\n"
        "\n"
        "    def put(self):\n"
        "        with self._mu:\n"
        "            self._flush()\n"
        "\n"
        "    def _flush(self):\n"
        "        with self._mu:\n"
        "            pass\n"
    )
    fs = _conc_tree(tmp_path, {"direct.py": direct, "inter.py": inter})
    got = sorted((os.path.basename(f.path), f.line) for f in fs
                 if f.rule == "TPU110")
    assert got == [("direct.py", 6), ("inter.py", 9)], \
        "\n".join(f.render() for f in fs)
    assert any("interprocedural self-deadlock" in f.message for f in fs)
    fs_rlock = _conc_tree(tmp_path, {
        "direct.py": "X = 1\n",
        "inter.py": inter.replace("threading.Lock()",
                                  "threading.RLock()")})
    assert fs_rlock == [], "\n".join(f.render() for f in fs_rlock)


def test_blocking_under_lock_detected(tmp_path):
    """TPU111: a sleep under a held lock directly, and blocking work
    one self-call away (reported at the call site, where the lock is
    actually held)."""
    src = (
        "import threading\n"
        "import time\n"
        "import urllib.request\n"
        "\n"
        "class Slow:\n"
        "    def __init__(self):\n"
        "        self._mu = threading.Lock()\n"
        "\n"
        "    def nap(self):\n"
        "        with self._mu:\n"
        "            time.sleep(0.1)\n"
        "\n"
        "    def fetch(self):\n"
        "        with self._mu:\n"
        "            self._pull()\n"
        "\n"
        "    def _pull(self):\n"
        "        urllib.request.urlopen('http://db')\n"
    )
    fs = _conc_tree(tmp_path, {"slow.py": src})
    got = sorted((f.rule, f.line) for f in fs)
    assert got == [("TPU111", 11), ("TPU111", 15)], \
        "\n".join(f.render() for f in fs)
    assert any("time.sleep" in f.message for f in fs)
    assert any("self._pull()" in f.message and "HTTP request" in f.message
               for f in fs)


def test_blocking_waiver_suppresses_in_place(tmp_path):
    """A reasoned `# lint: allow(TPU111)` pragma on the blocking line
    waives it; the concurrency engine emits no TPU116 hygiene noise of
    its own (that stays with the AST engine, once per pragma)."""
    src = (
        "import threading\n"
        "import time\n"
        "\n"
        "class Slow:\n"
        "    def __init__(self):\n"
        "        self._mu = threading.Lock()\n"
        "\n"
        "    def nap(self):\n"
        "        with self._mu:\n"
        "            # lint: allow(TPU111) reason=bounded 100ms backoff\n"
        "            time.sleep(0.1)\n"
    )
    assert _conc_tree(tmp_path, {"slow.py": src}) == []


def test_condvar_hygiene_detected(tmp_path):
    """TPU113: a bare cv.wait() outside a while-predicate loop, and a
    notify() without holding the owning lock; the canonical
    while-loop wait stays clean (Condition.wait releasing its own
    lock is not 'blocking under a lock')."""
    src = (
        "import threading\n"
        "\n"
        "class Q:\n"
        "    def __init__(self):\n"
        "        self._cv = threading.Condition()\n"
        "        self._items = []\n"
        "\n"
        "    def bad_wait(self):\n"
        "        with self._cv:\n"
        "            if not self._items:\n"
        "                self._cv.wait()\n"
        "            return self._items.pop()\n"
        "\n"
        "    def good_wait(self):\n"
        "        with self._cv:\n"
        "            while not self._items:\n"
        "                self._cv.wait()\n"
        "            return self._items.pop()\n"
        "\n"
        "    def bad_notify(self, item):\n"
        "        self._items.append(item)\n"
        "        self._cv.notify()\n"
    )
    fs = _conc_tree(tmp_path, {"q.py": src})
    got = sorted((f.rule, f.line) for f in fs)
    assert got == [("TPU113", 11), ("TPU113", 22)], \
        "\n".join(f.render() for f in fs)


def test_leaked_executor_and_thread_detected(tmp_path):
    """TPU112 class leg: an owned executor with no shutdown() and an
    owned thread with no join() reachable from any close/stop/drain
    path; the same class with a real close() is clean."""
    leaky = (
        "import threading\n"
        "from concurrent.futures import ThreadPoolExecutor\n"
        "\n"
        "class Leaky:\n"
        "    def __init__(self):\n"
        "        self._pool = ThreadPoolExecutor(max_workers=2)\n"
        "        self._worker = threading.Thread(target=self._run)\n"
        "        self._worker.start()\n"
        "\n"
        "    def _run(self):\n"
        "        pass\n"
        "\n"
        "    def close(self):\n"
        "        pass\n"
    )
    fs = _conc_tree(tmp_path, {"leaky.py": leaky})
    got = sorted((f.rule, f.line) for f in fs)
    assert got == [("TPU112", 6), ("TPU112", 7)], \
        "\n".join(f.render() for f in fs)
    assert any("no shutdown() reachable" in f.message for f in fs)
    assert any("no join() reachable" in f.message for f in fs)
    fixed = leaky.replace(
        "    def close(self):\n        pass\n",
        "    def close(self):\n"
        "        self._pool.shutdown()\n"
        "        self._worker.join()\n")
    assert _conc_tree(tmp_path, {"leaky.py": fixed}) == []


def test_local_and_fire_and_forget_thread_leaks(tmp_path):
    """TPU112 local leg: a local thread that is neither joined nor
    escapes the function, and the bare `Thread(...).start()`
    fire-and-forget form; handing the thread out (return/arg/attr)
    is an escape, not a leak."""
    src = (
        "import threading\n"
        "\n"
        "def leak(job):\n"
        "    t = threading.Thread(target=job)\n"
        "    t.start()\n"
        "\n"
        "def fire(job):\n"
        "    threading.Thread(target=job).start()\n"
        "\n"
        "def handed(job, sink):\n"
        "    t = threading.Thread(target=job)\n"
        "    t.start()\n"
        "    sink.append(t)\n"
        "\n"
        "def joined(job):\n"
        "    t = threading.Thread(target=job)\n"
        "    t.start()\n"
        "    t.join()\n"
    )
    fs = _conc_tree(tmp_path, {"spawn.py": src})
    got = sorted((f.rule, f.line) for f in fs)
    assert got == [("TPU112", 4), ("TPU112", 8)], \
        "\n".join(f.render() for f in fs)
    assert any("fire-and-forget" in f.message for f in fs)


def test_listener_without_remove_detected(tmp_path):
    """TPU112 listener leg: registering a bound method on an external
    object with no remove counterpart on the close path leaks the
    subscriber (meshguard/recovery-listener shape); the symmetric
    register/remove pair is clean."""
    leaky = (
        "class Sub:\n"
        "    def __init__(self, bus):\n"
        "        self._bus = bus\n"
        "        bus.on_status(self._tick)\n"
        "\n"
        "    def _tick(self, ev):\n"
        "        pass\n"
        "\n"
        "    def close(self):\n"
        "        pass\n"
    )
    fs = _conc_tree(tmp_path, {"sub.py": leaky})
    got = [(f.rule, f.line) for f in fs]
    assert got == [("TPU112", 4)], "\n".join(f.render() for f in fs)
    assert "remove_status()" in fs[0].message
    fixed = leaky.replace(
        "    def close(self):\n        pass\n",
        "    def close(self):\n"
        "        self._bus.remove_status(self._tick)\n")
    assert _conc_tree(tmp_path, {"sub.py": fixed}) == []


def test_lockgraph_staleness_gate(tmp_path):
    """The checked-in lockgraph artifact is a golden: missing →
    finding, current → clean, edge set changed → stale finding until
    --update-lockgraph rewrites it."""
    from trivy_tpu.analysis import concurrency
    src = (
        "import threading\n"
        "\n"
        "class Ordered:\n"
        "    def __init__(self):\n"
        "        self._a = threading.Lock()\n"
        "        self._b = threading.Lock()\n"
        "\n"
        "    def step(self):\n"
        "        with self._a:\n"
        "            with self._b:\n"
        "                return 1\n"
    )
    pkg = tmp_path / "gpkg"
    pkg.mkdir()
    (pkg / "mod.py").write_text(src)
    art = tmp_path / "lockgraph.json"

    fs = concurrency.run(root=str(pkg), lockgraph_path=str(art))
    assert [f.rule for f in fs] == ["TPU110"]
    assert "missing" in fs[0].message

    concurrency.update_lockgraph(root=str(pkg), path=str(art))
    graph = json.loads(art.read_text())
    assert graph["schema"] == "trivy-tpu-lockgraph/1"
    assert len(graph["edges"]) == 1
    assert graph["edges"][0]["held"].endswith("Ordered._a")
    assert graph["edges"][0]["acquires"].endswith("Ordered._b")
    assert concurrency.run(root=str(pkg),
                           lockgraph_path=str(art)) == []

    (pkg / "mod.py").write_text(src.replace(
        "        self._b = threading.Lock()\n",
        "        self._b = threading.Lock()\n"
        "        self._c = threading.Lock()\n") + (
        "\n"
        "    def hop(self):\n"
        "        with self._b:\n"
        "            with self._c:\n"
        "                return 2\n"))
    fs = concurrency.run(root=str(pkg), lockgraph_path=str(art))
    assert [f.rule for f in fs] == ["TPU110"]
    assert "stale" in fs[0].message


def test_tree_lockgraph_artifact_exists():
    """The real artifact is checked in next to the engine (its
    currency against the tree is asserted by test_tree_is_clean)."""
    from trivy_tpu.analysis import concurrency
    with open(concurrency.LOCKGRAPH_PATH) as f:
        graph = json.load(f)
    assert graph["schema"] == "trivy-tpu-lockgraph/1"
    assert len(graph["locks"]) >= 20


def test_lock_scope_allowlist_is_gone():
    """v2 acceptance: the v1 `_LOCK_SCOPE` module allowlist is deleted
    — every rule runs whole-tree, intent is expressed by pragma."""
    assert not hasattr(astlint, "_LOCK_SCOPE")


# ---------------------------------------------------------------------------
# waiver grammar (TPU116)


def test_waiver_with_reason_suppresses():
    """A reasoned pragma on (or directly above) the flagged line
    suppresses exactly the named rules, nothing else."""
    src = (
        "import threading\n"
        "\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._cache = {}\n"
        "\n"
        "    def put(self, k, v):\n"
        "        # lint: allow(TPU106) reason=rebuilt under query lock\n"
        "        self._cache[k] = v\n"
    )
    assert _lint("trivy_tpu/iac/fixture.py", src) == []


def test_waiver_without_reason_is_hygiene_finding():
    """A reason-less pragma suppresses NOTHING and is itself flagged
    (TPU116): silent waivers are how allowlists rot."""
    src = (
        "import threading\n"
        "\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._cache = {}\n"
        "\n"
        "    def put(self, k, v):\n"
        "        # lint: allow(TPU106)\n"
        "        self._cache[k] = v\n"
    )
    fs = _lint("trivy_tpu/iac/fixture.py", src)
    got = sorted((f.rule, f.line) for f in fs)
    assert got == [("TPU106", 10), ("TPU116", 9)], \
        "\n".join(f.render() for f in fs)
    assert "reason=" in [f for f in fs if f.rule == "TPU116"][0].message


# ---------------------------------------------------------------------------
# cross-checks: contract coverage (TPU114) + failpoint catalog (TPU115)


def test_jit_entry_discovery_forms():
    """TPU114's discovery sees all three jit-entry spellings:
    decorator, partial-decorator, and assignment."""
    from trivy_tpu.analysis import contract_coverage as cc
    src = (
        "import functools\n"
        "import jax\n"
        "\n"
        "@jax.jit\n"
        "def fused_scan(x):\n"
        "    return x\n"
        "\n"
        "@functools.partial(jax.jit, static_argnames=('n',))\n"
        "def windowed(x, n=1):\n"
        "    return x\n"
        "\n"
        "def _core(x):\n"
        "    return x\n"
        "\n"
        "packed = jax.jit(_core)\n"
    )
    got = cc.jit_entries("trivy_tpu/ops/fix.py", src)
    assert got == [("fused_scan", 5), ("windowed", 9), ("packed", 15)]


def test_contract_coverage_seed_violation(monkeypatch):
    """With the contract set emptied, every real kernel entry under
    ops/ and parallel/ is flagged — the mesh-static entries stay
    quiet because their waivers are in the source, not the contracts."""
    from trivy_tpu.analysis import contract_coverage as cc
    assert cc.check_contract_coverage() == []
    monkeypatch.setattr(cc, "load_contracts", lambda: [])
    fs = cc.check_contract_coverage()
    assert fs, "emptied contract set must un-cover the kernel entries"
    assert all(f.rule == "TPU114" for f in fs)
    specs = {f.context for f in fs}
    assert any(s.startswith("trivy_tpu.ops.") for s in specs)
    assert "trivy_tpu.ops.ac:shiftor_scan" in specs


def test_failpoint_probe_discovery_forms():
    """TPU115's probe scan sees failpoint()/._failpoint()/
    FAILPOINTS.fire()/GUARD.watch(), resolves module-level string
    constants, and skips dynamic sites (validated at arm time)."""
    from trivy_tpu.analysis import failpoint_catalog as fc
    src = (
        'WALK_SITE = "fanal.walk"\n'
        "\n"
        "class H:\n"
        "    def scan(self, site):\n"
        '        failpoint("detect.dispatch")\n'
        '        self._failpoint("rpc.scan")\n'
        "        FAILPOINTS.fire(WALK_SITE)\n"
        '        _GUARD.watch("detect.mesh:0")\n'
        "        failpoint(site)\n"
    )
    got = fc.probe_sites("x.py", src)
    assert got == [("detect.dispatch", 5), ("rpc.scan", 6),
                   ("fanal.walk", 7), ("detect.mesh:0", 8)]
    menu = fc.storm_menu_entries(
        '_X_FAULTS = (("rpc.scan", "error"), ("detect.mesh", "hang"))\n')
    assert menu == [("rpc.scan", "error", 1), ("detect.mesh", "hang", 1)]


def test_failpoint_catalog_seed_violation(monkeypatch):
    """Shrinking the catalog makes the real tree's rpc.scan probe an
    unknown site, and a grafted-in entry nobody probes is flagged as
    dead — both ends of the closed-catalog invariant."""
    from trivy_tpu.analysis import failpoint_catalog as fc
    from trivy_tpu.resilience import failpoints
    assert fc.check_failpoint_catalog() == []
    trimmed = tuple(s for s in failpoints.SITES
                    if s != "rpc.scan") + ("zombie.site",)
    monkeypatch.setattr(failpoints, "SITES", trimmed)
    fs = fc.check_failpoint_catalog()
    assert all(f.rule == "TPU115" for f in fs)
    assert any("rpc.scan" in f.message and "not in the failpoint"
               in f.message for f in fs), \
        "\n".join(f.render() for f in fs)
    assert any("zombie.site" in f.message and "dead entry" in f.message
               for f in fs)


# ---------------------------------------------------------------------------
# CLI: SARIF output + generated rule reference


def test_sarif_output(tmp_path):
    """--sarif writes a SARIF 2.1.0 doc: rule metadata from the
    registry, one result per finding with a stable partialFingerprint
    (CI annotation format; exit code still reflects the findings)."""
    src = (
        "import threading\n"
        "import time\n"
        "\n"
        "class S:\n"
        "    def __init__(self):\n"
        "        self._mu = threading.Lock()\n"
        "\n"
        "    def nap(self):\n"
        "        with self._mu:\n"
        "            time.sleep(1)\n"
    )
    pkg = tmp_path / "spkg"
    pkg.mkdir()
    (pkg / "mod.py").write_text(src)
    out = tmp_path / "out.sarif"
    assert cli_main(["--root", str(pkg), "--sarif", str(out),
                     "--json"]) == 1
    doc = json.loads(out.read_text())
    assert doc["version"] == "2.1.0"
    run = doc["runs"][0]
    assert run["tool"]["driver"]["name"] == "graftlint"
    results = run["results"]
    assert [r["ruleId"] for r in results] == ["TPU111"]
    assert results[0]["locations"][0]["physicalLocation"][
        "region"]["startLine"] == 10
    assert results[0]["partialFingerprints"]["graftlint/v1"]
    rule_ids = [r["id"] for r in run["tool"]["driver"]["rules"]]
    assert rule_ids == ["TPU111"]


def test_rules_reference_in_architecture_is_current():
    """The ARCHITECTURE.md rule-reference table is GENERATED from the
    registry (--update-docs): drift fails tier-1, exactly like the
    metrics table."""
    from trivy_tpu.analysis import registry
    with open(os.path.join(REPO, "ARCHITECTURE.md")) as f:
        doc = f.read()
    assert registry.RULES_DOC_BEGIN in doc
    assert registry.RULES_DOC_END in doc
    block = doc.split(registry.RULES_DOC_BEGIN)[1]
    block = block.split(registry.RULES_DOC_END)[0]
    assert block.strip("\n") == \
        registry.render_rules_markdown().strip("\n")
    for rid in ("TPU110", "TPU111", "TPU112", "TPU113",
                "TPU114", "TPU115", "TPU116"):
        assert f"`{rid}`" in block, rid


def test_full_tree_pass_wall_clock_budget():
    """The source-level engines (AST + concurrency, whole tree) must
    stay cheap enough to run on every tier-1 invocation — the v2
    interprocedural pass cannot cost what the jaxpr traces cost."""
    import time
    from trivy_tpu.analysis import concurrency
    t0 = time.monotonic()
    astlint.run(None)
    concurrency.run(None)
    assert time.monotonic() - t0 < 30.0


# graftfair: seed-violation regressions — the lint rules must keep
# firing on the exact concurrency shapes the multi-tenant QoS code
# introduces (per-tenant state dicts, the fair-queue lock + DRR sweep,
# and the admission condition-variable), so a future refactor of those
# subsystems cannot silently fall out of lint scope.


def test_fair_tenant_state_mutation_outside_lock_detected():
    """TPU106 on the AdmissionQueue/DispatchScheduler shape: per-tenant
    quota dicts guarded by self._lock, with one mutation planted
    outside the lock (the exact bug class graftfair's fold-to-'other'
    path would hit)."""
    src = (
        "import threading\n"
        "class Quota:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._tenants = {}\n"
        "        self._deficit = {}\n"
        "    def shed(self, t):\n"
        "        self._tenants.pop(t, None)\n"
        "    def admit(self, t):\n"
        "        with self._lock:\n"
        "            self._tenants[t] = 1\n"
        "            self._deficit[t] = 0.0\n"
    )
    fs = _lint("trivy_tpu/resilience/fixture.py", src)
    assert [(f.rule, f.line) for f in fs] == [("TPU106", 8)]


def test_fair_sweep_lock_order_cycle_detected(tmp_path):
    """TPU110 on the graftfair sweep shape: a dispatcher that takes
    the fair-queue lock then a tenant-state lock, and a quota updater
    that nests them the other way round — the deadlock the 'all
    _locked helpers require self._lock' contract in detect/sched.py
    exists to prevent."""
    src = (
        "import threading\n"
        "\n"
        "class Sweep:\n"
        "    def __init__(self):\n"
        "        self._fair_lock = threading.Lock()\n"
        "        self._tenant_lock = threading.Lock()\n"
        "\n"
        "    def take_round(self):\n"
        "        with self._fair_lock:\n"
        "            with self._tenant_lock:\n"
        "                return 1\n"
        "\n"
        "    def update_quota(self):\n"
        "        with self._tenant_lock:\n"
        "            with self._fair_lock:\n"
        "                return 2\n"
    )
    fs = _conc_tree(tmp_path, {"sweep.py": src})
    cyc = [f for f in fs if f.rule == "TPU110"
           and "lock-order cycle" in f.message]
    assert len(cyc) == 1, "\n".join(f.render() for f in fs)
    assert "Sweep._fair_lock" in cyc[0].message
    assert "Sweep._tenant_lock" in cyc[0].message


def test_fair_admission_wait_without_predicate_detected(tmp_path):
    """TPU113 on the admission cv shape: the per-tenant admit path
    waiting on the condition with `if` instead of the canonical
    `while` predicate loop (spurious wakeups would admit a tenant past
    its active cap); the real while-loop twin stays clean."""
    src = (
        "import threading\n"
        "\n"
        "class Admit:\n"
        "    def __init__(self):\n"
        "        self._cv = threading.Condition()\n"
        "        self._active = 0\n"
        "        self._cap = 2\n"
        "\n"
        "    def bad_admit(self):\n"
        "        with self._cv:\n"
        "            if self._active >= self._cap:\n"
        "                self._cv.wait()\n"
        "            self._active += 1\n"
        "\n"
        "    def good_admit(self):\n"
        "        with self._cv:\n"
        "            while self._active >= self._cap:\n"
        "                self._cv.wait()\n"
        "            self._active += 1\n"
    )
    fs = _conc_tree(tmp_path, {"admit.py": src})
    got = [(f.rule, f.line) for f in fs]
    assert got == [("TPU113", 12)], "\n".join(f.render() for f in fs)
