"""Engine 1: AST lint over trivy_tpu/ for TPU hot-path invariants.

Device code is identified three ways (union):
  * functions wrapped by `jax.jit` — decorator form (`@jax.jit`,
    `@functools.partial(jax.jit, ...)`) or assignment form
    (`g = jax.jit(f, static_argnums=...)`);
  * functions handed to `pl.pallas_call` as the kernel;
  * the naming convention for jit-core bodies: `_*_core` / `_kernel*`.

For each device function the linter resolves its *static* parameters
(from `static_argnums`/`static_argnames` at the jit site); every other
parameter is a traced value, and rules about host syncs and
data-dependent control flow key off that set. Expressions that only
touch shape metadata (`x.shape`, `x.ndim`, `x.size`, `x.dtype`,
`len(x)`) are static under tracing and never flagged.
"""

from __future__ import annotations

import ast
import os
from dataclasses import dataclass, field

from .registry import Finding, register

# parameter annotations accepted for static jit arguments: hashable
# primitives plus jax.sharding.Mesh (hashable by design, used as the
# shard_map static)
_HASHABLE_STATIC_ANNOTATIONS = {
    "int", "bool", "str", "float", "bytes", "tuple", "frozenset", "Mesh",
}

# attribute accesses that are static under tracing (safe inside int()
# etc. and as Python control-flow conditions)
_SHAPE_ATTRS = {"shape", "ndim", "size", "dtype"}

# methods that mutate a container in place (lock-hygiene rule)
_MUTATORS = {
    "append", "extend", "insert", "add", "discard", "remove", "pop",
    "popitem", "clear", "update", "setdefault",
}

@dataclass
class DeviceFn:
    node: ast.FunctionDef
    statics: set[str]
    reason: str     # "jit" | "pallas" | "core-name" | "shard_map"


@dataclass
class ModuleInfo:
    relpath: str
    tree: ast.Module
    device_fns: list[DeviceFn] = field(default_factory=list)

    @property
    def is_constants_module(self) -> bool:
        return self.relpath.replace(os.sep, "/").endswith(
            "trivy_tpu/ops/constants.py")


# ---------------------------------------------------------------------------
# module scanning / device-function discovery

def _dotted(node: ast.AST) -> str:
    """'jax.jit' for Attribute/Name chains, '' otherwise."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def _is_jax_jit(node: ast.AST) -> bool:
    return _dotted(node) in ("jax.jit", "jit")


def _is_partial(node: ast.AST) -> bool:
    return _dotted(node) in ("functools.partial", "partial")


def _literal_names(node: ast.AST) -> list | None:
    """Tuple/list/single literal of constants → list of values;
    None when any element is not a plain literal."""
    if isinstance(node, ast.Constant):
        return [node.value]
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for elt in node.elts:
            if not isinstance(elt, ast.Constant):
                return None
            out.append(elt.value)
        return out
    return None


@dataclass
class _JitSite:
    """One jax.jit(...) occurrence: the wrapped function name (or def),
    and its static_argnums/static_argnames values (None = non-literal)."""
    target: str | ast.FunctionDef
    line: int
    static_argnums: list | None
    static_argnames: list | None
    has_nonliteral: bool


def _jit_kwargs(call: ast.Call) -> tuple[list | None, list | None, bool]:
    nums = names = None
    nonlit = False
    for kw in call.keywords:
        if kw.arg == "static_argnums":
            nums = _literal_names(kw.value)
            nonlit |= nums is None
        elif kw.arg == "static_argnames":
            names = _literal_names(kw.value)
            nonlit |= names is None
    return nums, names, nonlit


def _collect_jit_sites(tree: ast.Module) -> list[_JitSite]:
    sites: list[_JitSite] = []
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                if _is_jax_jit(dec):
                    sites.append(_JitSite(node, dec.lineno, None, None,
                                          False))
                elif isinstance(dec, ast.Call) and _is_jax_jit(dec.func):
                    nums, names, nonlit = _jit_kwargs(dec)
                    sites.append(_JitSite(node, dec.lineno, nums, names,
                                          nonlit))
                elif (isinstance(dec, ast.Call) and _is_partial(dec.func)
                        and dec.args and _is_jax_jit(dec.args[0])):
                    nums, names, nonlit = _jit_kwargs(dec)
                    sites.append(_JitSite(node, dec.lineno, nums, names,
                                          nonlit))
        elif isinstance(node, ast.Call) and _is_jax_jit(node.func) \
                and node.args and isinstance(node.args[0], ast.Name):
            nums, names, nonlit = _jit_kwargs(node)
            sites.append(_JitSite(node.args[0].id, node.lineno, nums,
                                  names, nonlit))
    return sites


def _param_names(fn: ast.FunctionDef) -> list[str]:
    a = fn.args
    return ([p.arg for p in a.posonlyargs] + [p.arg for p in a.args]
            + [p.arg for p in a.kwonlyargs])


def _positional_params(fn: ast.FunctionDef) -> list[str]:
    a = fn.args
    return [p.arg for p in a.posonlyargs] + [p.arg for p in a.args]


def scan_module(relpath: str, source: str) -> ModuleInfo | None:
    try:
        tree = ast.parse(source, filename=relpath)
    except SyntaxError:
        return None
    info = ModuleInfo(relpath, tree)

    defs: dict[str, ast.FunctionDef] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.FunctionDef):
            defs.setdefault(node.name, node)

    jit_sites = _collect_jit_sites(tree)
    seen: dict[int, DeviceFn] = {}

    def add(fn: ast.FunctionDef, statics: set[str], reason: str):
        d = seen.get(id(fn))
        if d is None:
            d = DeviceFn(fn, set(statics), reason)
            seen[id(fn)] = d
            info.device_fns.append(d)
        else:
            d.statics |= statics

    for site in jit_sites:
        fn = site.target if isinstance(site.target, ast.FunctionDef) \
            else defs.get(site.target)
        if fn is None:
            continue
        statics: set[str] = set(site.static_argnames or [])
        pos = _positional_params(fn)
        for i in site.static_argnums or []:
            if isinstance(i, int) and 0 <= i < len(pos):
                statics.add(pos[i])
        add(fn, statics, "jit")

    # pallas kernels: first positional arg of pl.pallas_call
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) \
                and _dotted(node.func).endswith("pallas_call") \
                and node.args and isinstance(node.args[0], ast.Name):
            fn = defs.get(node.args[0].id)
            if fn is not None:
                add(fn, set(), "pallas")

    # shard_map bodies: the per-device local function is device code
    # exactly like a jitted core — failpoint probes, breaker reads, and
    # clocks in there run once at trace time (TPU107/TPU108 must see
    # inside the mesh path's collective launches)
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) \
                and _dotted(node.func).split(".")[-1] == "shard_map" \
                and node.args and isinstance(node.args[0], ast.Name):
            fn = defs.get(node.args[0].id)
            if fn is not None:
                add(fn, set(), "shard_map")

    # naming convention: _*_core / _kernel*
    for name, fn in defs.items():
        if (name.startswith("_") and name.endswith("_core")) \
                or name.startswith("_kernel"):
            add(fn, set(), "core-name")

    return info


# ---------------------------------------------------------------------------
# shared helpers for rules

def _refs_traced(node: ast.AST, traced: set[str]) -> bool:
    """True if the expression references a traced name as a *value*
    (shape/dtype metadata and len() are static under tracing)."""
    if isinstance(node, ast.Attribute) and node.attr in _SHAPE_ATTRS:
        return False
    if isinstance(node, ast.Call) and isinstance(node.func, ast.Name) \
            and node.func.id == "len":
        return False
    if isinstance(node, ast.Name):
        return node.id in traced
    return any(_refs_traced(c, traced) for c in ast.iter_child_nodes(node))


def _device_walk(dev: DeviceFn):
    """Yield (node, traced_names) over a device function's body; nested
    function defs contribute their own parameters as traced (they close
    over the outer tracer scope)."""
    def walk(fn: ast.AST, traced: set[str]):
        for child in ast.iter_child_nodes(fn):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                inner = traced | set(_param_names(child))
                yield child, inner
                yield from walk(child, inner)
            elif isinstance(child, ast.Lambda):
                inner = traced | {p.arg for p in child.args.args}
                yield child, inner
                yield from walk(child, inner)
            else:
                yield child, traced
                yield from walk(child, traced)

    traced = set(_param_names(dev.node)) - dev.statics
    yield from walk(dev.node, traced)


def _ctx(dev: DeviceFn) -> str:
    return dev.node.name


# ---------------------------------------------------------------------------
# rules

@register("TPU100", "module-parses", "ast")
def rule_syntax(info: ModuleInfo):
    """A module that does not parse cannot be linted; emitted by the
    driver when ast.parse fails (never by this stub — linting stops at
    the syntax error)."""
    return []


@register("TPU101", "host-transfer-in-device-code", "ast")
def rule_host_transfer(info: ModuleInfo):
    """Inside jitted cores and pallas kernels, operations that force a
    host sync (or a tracer error at runtime) are forbidden: `.item()`,
    `.tolist()`, `int()/float()/bool()/complex()` applied to traced
    values, any `np.*`/`numpy.*` call, and
    `jax.device_get`/`jax.device_put`."""
    for dev in info.device_fns:
        for node, traced in _device_walk(dev):
            if not isinstance(node, ast.Call):
                continue
            fname = _dotted(node.func)
            if isinstance(node.func, ast.Attribute) \
                    and node.func.attr in ("item", "tolist") \
                    and _refs_traced(node.func.value, traced | {"self"}):
                yield Finding(
                    "TPU101", info.relpath, node.lineno,
                    f".{node.func.attr}() in device code forces a host "
                    f"sync", _ctx(dev))
            elif isinstance(node.func, ast.Name) \
                    and node.func.id in ("int", "float", "bool",
                                         "complex") \
                    and any(_refs_traced(a, traced) for a in node.args):
                yield Finding(
                    "TPU101", info.relpath, node.lineno,
                    f"{node.func.id}() on a traced value concretizes it "
                    f"(host sync / TracerConversionError)", _ctx(dev))
            elif fname.split(".", 1)[0] in ("np", "numpy") and fname:
                yield Finding(
                    "TPU101", info.relpath, node.lineno,
                    f"numpy call {fname}() inside device code pulls "
                    f"traced values to host — use jnp", _ctx(dev))
            elif fname in ("jax.device_get", "jax.device_put"):
                yield Finding(
                    "TPU101", info.relpath, node.lineno,
                    f"{fname} inside device code is a host round-trip",
                    _ctx(dev))


@register("TPU102", "data-dependent-control-flow", "ast")
def rule_data_dependent_cf(info: ModuleInfo):
    """Python `if`/`while`/`for`/comprehensions inside a device function
    must not branch or iterate on traced values — that either fails at
    trace time or bakes one trace per value (recompile hazard). Shape
    metadata and static arguments are fine; use `jnp.where`/`lax.cond`/
    `lax.fori_loop` for value-dependent control."""
    for dev in info.device_fns:
        for node, traced in _device_walk(dev):
            if isinstance(node, (ast.If, ast.While)) \
                    and _refs_traced(node.test, traced):
                yield Finding(
                    "TPU102", info.relpath, node.lineno,
                    "Python branch on a traced value in device code "
                    "(use jnp.where / lax.cond)", _ctx(dev))
            elif isinstance(node, ast.For) \
                    and _refs_traced(node.iter, traced):
                yield Finding(
                    "TPU102", info.relpath, node.lineno,
                    "Python loop over a traced value in device code "
                    "(use lax.fori_loop / lax.scan)", _ctx(dev))
            elif isinstance(node, ast.comprehension) \
                    and _refs_traced(node.iter, traced):
                yield Finding(
                    "TPU102", info.relpath, node.lineno,
                    "comprehension over a traced value in device code",
                    _ctx(dev))


@register("TPU103", "contract-constant-drift", "ast")
def rule_constant_drift(info: ModuleInfo):
    """The interval flag bits and report bits are defined once, in
    `trivy_tpu/ops/constants.py`. Any other module binding one of those
    names to an integer literal is a drifted copy of the contract —
    exactly the "must match" comment-coupling this package exists to
    kill. Import the constant instead."""
    if info.is_constants_module:
        return
    from ..ops.constants import CONTRACT_CONSTANT_NAMES

    def _int_bindings(node):
        """(name, lineno) pairs bound to int literals by an assignment,
        including tuple unpacking (`HAS_LO, HAS_HI = 1, 4`)."""
        if isinstance(node, ast.Assign):
            targets, value = node.targets, node.value
        elif isinstance(node, ast.AnnAssign) and node.value is not None:
            targets, value = [node.target], node.value
        else:
            return
        for t in targets:
            if isinstance(t, (ast.Tuple, ast.List)) \
                    and isinstance(value, (ast.Tuple, ast.List)) \
                    and len(t.elts) == len(value.elts):
                for te, ve in zip(t.elts, value.elts):
                    if isinstance(te, ast.Name) \
                            and isinstance(ve, ast.Constant) \
                            and isinstance(ve.value, int):
                        yield te.id, node.lineno
            elif isinstance(t, ast.Name) \
                    and isinstance(value, ast.Constant) \
                    and isinstance(value.value, int):
                yield t.id, node.lineno

    for node in ast.walk(info.tree):
        for name, lineno in _int_bindings(node):
            if name in CONTRACT_CONSTANT_NAMES:
                yield Finding(
                    "TPU103", info.relpath, lineno,
                    f"local redefinition of contract constant {name} "
                    f"(import it from trivy_tpu.ops.constants)", name)


@register("TPU104", "static-argument-hygiene", "ast")
def rule_static_hygiene(info: ModuleInfo):
    """`static_argnums`/`static_argnames` at jit sites must be literal
    tuples (a computed static list defeats review and the linter), and
    every static parameter must be annotated with a hashable primitive
    (`int`, `bool`, `str`, `float`, `bytes`, `tuple`, `frozenset`, or
    `Mesh`) — unhashable or un-annotated statics are where silent
    recompile storms start."""
    defs: dict[str, ast.FunctionDef] = {}
    for node in ast.walk(info.tree):
        if isinstance(node, ast.FunctionDef):
            defs.setdefault(node.name, node)
    for site in _collect_jit_sites(info.tree):
        if site.has_nonliteral:
            yield Finding(
                "TPU104", info.relpath, site.line,
                "static_argnums/static_argnames must be literal "
                "constants at the jit site", "")
        fn = site.target if isinstance(site.target, ast.FunctionDef) \
            else defs.get(site.target)
        if fn is None:
            continue
        statics = list(site.static_argnames or [])
        pos = _positional_params(fn)
        for i in site.static_argnums or []:
            if isinstance(i, int) and 0 <= i < len(pos):
                statics.append(pos[i])
        ann = {}
        a = fn.args
        for p in a.posonlyargs + a.args + a.kwonlyargs:
            ann[p.arg] = p.annotation
        for name in statics:
            an = ann.get(name)
            if an is None:
                yield Finding(
                    "TPU104", info.relpath, fn.lineno,
                    f"static argument '{name}' of {fn.name}() has no "
                    f"type annotation (annotate with a hashable "
                    f"primitive)", fn.name)
                continue
            leaf = _dotted(an).rsplit(".", 1)[-1]
            if leaf not in _HASHABLE_STATIC_ANNOTATIONS:
                yield Finding(
                    "TPU104", info.relpath, fn.lineno,
                    f"static argument '{name}' of {fn.name}() is "
                    f"annotated '{leaf or ast.dump(an)}' — not a "
                    f"hashable primitive", fn.name)


@register("TPU105", "debug-in-device-code", "ast")
def rule_debug(info: ModuleInfo):
    """No `print`, `breakpoint`, `pdb.set_trace`, `jax.debug.print` or
    `jax.debug.breakpoint` may ship inside device code: the jax.debug
    hooks insert host callbacks into the lowered program (a sync per
    batch that stalls the device), the rest fail or spam at trace time."""
    banned_exact = {"jax.debug.print", "jax.debug.breakpoint",
                    "jax.debug.callback", "pdb.set_trace",
                    "ipdb.set_trace"}
    for dev in info.device_fns:
        for node, _traced in _device_walk(dev):
            if not isinstance(node, ast.Call):
                continue
            fname = _dotted(node.func)
            if fname in banned_exact or fname in ("print", "breakpoint"):
                yield Finding(
                    "TPU105", info.relpath, node.lineno,
                    f"{fname}() left in device code", _ctx(dev))


@register("TPU107", "instrumentation-in-device-code", "ast")
def rule_instrumentation(info: ModuleInfo):
    """Observability belongs to the host orchestration layer. Inside
    jitted cores and pallas kernels, clock reads (`time.perf_counter()`
    and friends), graftscope span entry (`span(...)` / `obs.span` /
    `trace.span`), and `METRICS.<anything>()` calls are forbidden:
    under jit tracing they run ONCE at trace time — timing the trace
    and counting compilations, not executions — and silently vanish
    from the compiled program, so the instrumentation lies."""
    clock_names = {"perf_counter", "process_time", "monotonic", "time",
                   "perf_counter_ns", "monotonic_ns", "time_ns"}
    for dev in info.device_fns:
        for node, _traced in _device_walk(dev):
            if not isinstance(node, ast.Call):
                continue
            fname = _dotted(node.func)
            head, _, tail = fname.rpartition(".")
            if head == "time" and tail in clock_names:
                yield Finding(
                    "TPU107", info.relpath, node.lineno,
                    f"{fname}() in device code measures trace time, "
                    f"not device time", _ctx(dev))
            elif fname in ("span", "obs.span", "trace.span"):
                yield Finding(
                    "TPU107", info.relpath, node.lineno,
                    f"{fname}() span entered inside device code "
                    f"(instrument the host call site instead)",
                    _ctx(dev))
            elif head in ("METRICS", "metrics.METRICS") and tail:
                yield Finding(
                    "TPU107", info.relpath, node.lineno,
                    f"{fname}() inside device code runs once at trace "
                    f"time — move it to the host orchestration",
                    _ctx(dev))


@register("TPU108", "resilience-in-device-code", "ast")
def rule_resilience(info: ModuleInfo):
    """graftguard belongs to the host orchestration layer, like
    graftscope (TPU107). Inside jitted cores and pallas kernels,
    failpoint probes (`failpoint(...)` / `FAILPOINTS.fire(...)`),
    breaker reads (`GUARD.*`, `.allow()` / `.record_success()` /
    `.record_failure()` / `.trip()` on anything breaker-named), and
    deadline clocks (`Deadline(...)`, `.remaining()` / `.expired()` on
    deadline-named values) are forbidden: under jit tracing they run
    ONCE at trace time — arming a fault or reading a breaker during
    compilation, never during execution — and vanish from the compiled
    program, so the fault injection and supervision silently lie."""
    breaker_methods = {"allow", "allow_device", "record_success",
                       "record_failure", "trip"}
    deadline_methods = {"remaining", "expired"}
    for dev in info.device_fns:
        for node, _traced in _device_walk(dev):
            if not isinstance(node, ast.Call):
                continue
            fname = _dotted(node.func)
            head = fname.split(".", 1)[0]
            _, _, tail = fname.rpartition(".")
            if fname in ("failpoint", "resilience.failpoint",
                         "failpoints.failpoint") \
                    or (head == "FAILPOINTS" and tail):
                yield Finding(
                    "TPU108", info.relpath, node.lineno,
                    f"failpoint probe {fname}() in device code fires "
                    f"once at trace time, not per execution", _ctx(dev))
            elif head == "GUARD" and tail:
                yield Finding(
                    "TPU108", info.relpath, node.lineno,
                    f"breaker/supervisor call {fname}() in device code "
                    f"reads host state at trace time — supervise the "
                    f"host call site instead", _ctx(dev))
            elif tail in breaker_methods and "breaker" in head.lower():
                yield Finding(
                    "TPU108", info.relpath, node.lineno,
                    f"breaker call {fname}() in device code runs once "
                    f"at trace time", _ctx(dev))
            elif fname == "Deadline" or fname.endswith(".Deadline"):
                yield Finding(
                    "TPU108", info.relpath, node.lineno,
                    "Deadline() in device code captures the trace-time "
                    "clock", _ctx(dev))
            elif tail in deadline_methods and "deadline" in head.lower():
                yield Finding(
                    "TPU108", info.relpath, node.lineno,
                    f"deadline clock {fname}() in device code reads "
                    f"trace time, not request time", _ctx(dev))


@register("TPU106", "lock-hygiene", "ast")
def rule_lock_hygiene(info: ModuleInfo):
    """A class that owns a `threading.Lock` must mutate its shared
    state only while holding it. Guarded state = attributes
    initialized to container literals in `__init__` or mutated under
    the lock anywhere in the class; any mutation of those outside a
    `with self.<lock>:` block (including through a local alias) is a
    race. Runs over the WHOLE tree (v2 retired the `_LOCK_SCOPE` path
    list); intentional interprocedural patterns are waived in place
    with `# lint: allow(TPU106) reason=...`."""
    for node in ast.walk(info.tree):
        if isinstance(node, ast.ClassDef):
            yield from _check_class_locks(info, node)


def _self_attr(node: ast.AST) -> str | None:
    if isinstance(node, ast.Attribute) \
            and isinstance(node.value, ast.Name) \
            and node.value.id == "self":
        return node.attr
    return None


def _resolve_attr(expr: ast.AST, aliases: dict[str, str]) -> str | None:
    attr = _self_attr(expr)
    if attr is not None:
        return attr
    if isinstance(expr, ast.Name):
        return aliases.get(expr.id)
    return None


def _header_exprs(st: ast.stmt) -> list[ast.expr]:
    """The expressions evaluated by the statement itself — for compound
    statements, only the header (bodies are walked separately so each
    inner statement carries its own lock state)."""
    if isinstance(st, ast.Assign):
        return [st.value]
    if isinstance(st, (ast.AugAssign, ast.AnnAssign)):
        return [st.value] if st.value is not None else []
    if isinstance(st, ast.Expr):
        return [st.value]
    if isinstance(st, ast.Return):
        return [st.value] if st.value is not None else []
    if isinstance(st, (ast.If, ast.While)):
        return [st.test]
    if isinstance(st, ast.For):
        return [st.iter]
    if isinstance(st, ast.With):
        return [i.context_expr for i in st.items]
    if isinstance(st, ast.Raise):
        return [e for e in (st.exc, st.cause) if e is not None]
    if isinstance(st, ast.Assert):
        return [e for e in (st.test, st.msg) if e is not None]
    if isinstance(st, ast.Match):
        return [st.subject]
    return []


def _mutation_target(node: ast.AST, aliases: dict[str, str]) -> str | None:
    """If the statement mutates a self attribute — through an
    assignment target, a del, or a mutator-method call anywhere in its
    evaluated expressions (including `x = self._vals.pop(k)`) — return
    the attribute name."""
    if isinstance(node, (ast.Assign, ast.AugAssign)):
        targets = node.targets if isinstance(node, ast.Assign) \
            else [node.target]
        for t in targets:
            if isinstance(t, ast.Subscript):
                attr = _resolve_attr(t.value, aliases)
                if attr:
                    return attr
            else:
                attr = _resolve_attr(t, aliases) \
                    if isinstance(node, ast.AugAssign) else _self_attr(t)
                if attr:
                    return attr
    elif isinstance(node, ast.Delete):
        for t in node.targets:
            if isinstance(t, ast.Subscript):
                attr = _resolve_attr(t.value, aliases)
                if attr:
                    return attr
    if isinstance(node, ast.stmt):
        for expr in _header_exprs(node):
            for sub in ast.walk(expr):
                if isinstance(sub, ast.Call) \
                        and isinstance(sub.func, ast.Attribute) \
                        and sub.func.attr in _MUTATORS:
                    attr = _resolve_attr(sub.func.value, aliases)
                    if attr:
                        return attr
    return None


def _walk_method(method: ast.FunctionDef, locks: set[str]):
    """Yield (stmt, under_lock, aliases) for each statement, tracking
    `with self.<lock>:` nesting and local aliases of self attributes."""
    aliases: dict[str, str] = {}

    def visit(stmts, under):
        for st in stmts:
            if isinstance(st, ast.Assign) and len(st.targets) == 1 \
                    and isinstance(st.targets[0], ast.Name):
                src = _self_attr(st.value)
                if src is not None:
                    aliases[st.targets[0].id] = src
            yield st, under, aliases
            if isinstance(st, ast.With):
                locked = under or any(
                    (_self_attr(item.context_expr) or "") in locks
                    for item in st.items)
                yield from visit(st.body, locked)
            elif isinstance(st, (ast.If,)):
                yield from visit(st.body, under)
                yield from visit(st.orelse, under)
            elif isinstance(st, (ast.For, ast.While)):
                yield from visit(st.body, under)
                yield from visit(st.orelse, under)
            elif isinstance(st, ast.Try):
                yield from visit(st.body, under)
                for h in st.handlers:
                    yield from visit(h.body, under)
                yield from visit(st.orelse, under)
                yield from visit(st.finalbody, under)
            elif isinstance(st, ast.Match):
                for case in st.cases:
                    yield from visit(case.body, under)
            elif isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef)):
                # closures inherit the lock state of their definition
                # site (heuristic: a helper defined under the lock is
                # assumed to run under it, and vice versa)
                yield from visit(st.body, under)

    yield from visit(method.body, False)


def _check_class_locks(info: ModuleInfo, cls: ast.ClassDef):
    methods = [n for n in cls.body if isinstance(n, ast.FunctionDef)]
    locks: set[str] = set()
    for m in methods:
        for node in ast.walk(m):
            if isinstance(node, ast.Assign):
                attr = _self_attr(node.targets[0]) \
                    if node.targets else None
                if attr and isinstance(node.value, ast.Call) \
                        and _dotted(node.value.func).rsplit(".", 1)[-1] \
                        in ("Lock", "RLock"):
                    locks.add(attr)
    if not locks:
        return

    # pass 1: guarded attributes
    guarded: set[str] = set()
    for m in methods:
        if m.name == "__init__":
            for node in ast.walk(m):
                if isinstance(node, ast.Assign) and node.targets:
                    target, value = node.targets[0], node.value
                elif isinstance(node, ast.AnnAssign) \
                        and node.value is not None:
                    target, value = node.target, node.value
                else:
                    continue
                attr = _self_attr(target)
                if attr and isinstance(
                        value, (ast.Dict, ast.List, ast.Set)):
                    guarded.add(attr)
        for st, under, aliases in _walk_method(m, locks):
            if under:
                attr = _mutation_target(st, aliases)
                if attr:
                    guarded.add(attr)
    guarded -= locks

    # pass 2: mutations outside the lock
    for m in methods:
        if m.name == "__init__":
            continue
        for st, under, aliases in _walk_method(m, locks):
            if under:
                continue
            attr = _mutation_target(st, aliases)
            if attr in guarded:
                yield Finding(
                    "TPU106", info.relpath, st.lineno,
                    f"mutation of shared '{cls.name}.{attr}' outside "
                    f"the lock", f"{cls.name}.{m.name}")


# ---------------------------------------------------------------------------
# driver

def iter_python_files(root: str):
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [d for d in dirnames
                       if d not in ("__pycache__", ".git")]
        for fn in sorted(filenames):
            if fn.endswith(".py"):
                yield os.path.join(dirpath, fn)


def lint_source(relpath: str, source: str) -> list[Finding]:
    """Run every AST rule over one module's source (fixture-testable).
    Inline `# lint: allow(...)` pragmas are applied here, so waiver
    behavior is part of what fixtures exercise; reason-less pragmas
    surface as TPU116."""
    from . import waivers
    from .registry import rules_for_engine
    info = scan_module(relpath, source)
    if info is None:
        return [Finding("TPU100", relpath, 0, "syntax error", "")]
    out: list[Finding] = []
    for rule in rules_for_engine("ast"):
        out.extend(rule.func(info))
    return waivers.apply(relpath, source, out)


def run(root: str | None = None) -> list[Finding]:
    if root is None:
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    repo = os.path.dirname(root)
    findings: list[Finding] = []
    for path in iter_python_files(root):
        rel = os.path.relpath(path, repo)
        with open(path, encoding="utf-8") as f:
            source = f.read()
        findings.extend(lint_source(rel, source))
    return findings
