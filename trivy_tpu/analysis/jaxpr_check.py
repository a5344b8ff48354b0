"""Engine 2: jaxpr contract checking of the jitted hot-path entries.

Each JSON file in `contracts/` pins one jitted entry point to a
machine-readable contract. The checker traces the entry with
`jax.make_jaxpr` under the contract's canonical abstract shapes — no
device, no compilation; the Pallas kernel traces in interpret mode —
and verifies:

  * input/output dtypes exactly match the contract;
  * every `convert_element_type` in the (recursively flattened) jaxpr
    is in the contract's allowlist — a new widening, or a narrowing
    other than the int32→int8 report packing, is a finding;
  * no host-callback / infeed primitives anywhere in the lowering;
  * the total primitive count stays under the contract's budget, so an
    accidental O(K) Python unroll regresses loudly instead of shipping
    as a 10× slower compile;
  * optionally, the pretty-printed jaxpr matches a golden snapshot
    checked in next to the contract (regenerate with
    ``python -m trivy_tpu.analysis --update-goldens``).

Contract format (all shapes resolve through "shape_vars"):

    {
      "entry": "trivy_tpu.ops.join:csr_pair_join",
      "shape_vars": {"A": 64, "K": 8},
      "args": [{"shape": ["A", "K"], "dtype": "int32"},
               {"static": "T"}],
      "static_kwargs": {"n_words": 3},
      "out_dtypes": ["int8"],
      "allowed_converts": [["bool", "int8"]],
      "max_primitives": 160,
      "golden": "csr_pair_join.jaxpr.txt"
    }
"""

from __future__ import annotations

import functools
import importlib
import json
import os
import re

from .registry import Finding, register

CONTRACTS_DIR = os.path.join(os.path.dirname(__file__), "contracts")

# primitives that round-trip through the host (or block on it); never
# acceptable inside a scan-server hot path
_FORBIDDEN_SUBSTRINGS = ("callback", "infeed", "outfeed", "debug_print")


def _resolve_entry(spec: str):
    mod_name, _, attr = spec.partition(":")
    mod = importlib.import_module(mod_name)
    return getattr(mod, attr)


def _resolve(val, shape_vars: dict):
    if isinstance(val, str):
        return shape_vars[val]
    return val


def _build_args(contract: dict):
    """→ (positional args incl. static values, static_argnums tuple)."""
    import jax
    import numpy as np
    shape_vars = contract.get("shape_vars", {})
    args, static_nums = [], []
    for i, a in enumerate(contract["args"]):
        if "static" in a:
            args.append(_resolve(a["static"], shape_vars))
            static_nums.append(i)
        else:
            shape = tuple(_resolve(d, shape_vars) for d in a["shape"])
            args.append(jax.ShapeDtypeStruct(shape, np.dtype(a["dtype"])))
    return args, tuple(static_nums)


def trace_contract(contract: dict):
    """Trace the contract's entry → ClosedJaxpr."""
    import jax
    fn = _resolve_entry(contract["entry"])
    static_kwargs = contract.get("static_kwargs") or {}
    if static_kwargs:
        fn = functools.partial(fn, **static_kwargs)
    args, static_nums = _build_args(contract)
    if static_nums:
        return jax.make_jaxpr(fn, static_argnums=static_nums)(*args)
    return jax.make_jaxpr(fn)(*args)


def _iter_eqns(jaxpr):
    """All equations, recursing through pjit/scan/pallas sub-jaxprs —
    including sub-jaxprs held in tuple/list params (lax.cond/switch
    'branches'), so nothing inside a conditional escapes the checks."""
    def sub(v):
        if hasattr(v, "jaxpr"):              # ClosedJaxpr
            yield from _iter_eqns(v.jaxpr)
        elif hasattr(v, "eqns"):             # raw Jaxpr
            yield from _iter_eqns(v)
        elif isinstance(v, (tuple, list)):
            for item in v:
                yield from sub(item)

    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            yield from sub(v)


def normalize_jaxpr_text(text: str) -> str:
    """Pretty-printed jaxpr, made diff-stable: object addresses masked,
    trailing whitespace stripped."""
    text = re.sub(r"0x[0-9a-f]+", "0x…", text)
    return "\n".join(line.rstrip() for line in text.splitlines()) + "\n"


def load_contracts() -> list[tuple[str, dict]]:
    out = []
    for fn in sorted(os.listdir(CONTRACTS_DIR)):
        if fn.endswith(".json"):
            with open(os.path.join(CONTRACTS_DIR, fn)) as f:
                out.append((fn, json.load(f)))
    return out


@register("JAX201", "jaxpr-contract", "jaxpr")
def check_contract(name: str, contract: dict) -> list[Finding]:
    """Verify one traced entry against its contract (dtypes, converts
    allowlist, host-callback ban, primitive budget, golden snapshot)."""
    rel = os.path.join("trivy_tpu", "analysis", "contracts", name)
    entry = contract["entry"]
    try:
        closed = trace_contract(contract)
    except Exception as e:  # noqa: BLE001 — report, don't crash the CLI
        return [Finding("JAX205", rel, 0,
                        f"{entry}: trace failed: "
                        f"{type(e).__name__}: {e}", entry)]
    jaxpr = closed.jaxpr
    findings: list[Finding] = []

    # dtypes at the boundary
    want_in = [a["dtype"] for a in contract["args"] if "static" not in a]
    got_in = [str(v.aval.dtype) for v in jaxpr.invars]
    if got_in != want_in:
        findings.append(Finding(
            "JAX201", rel, 0,
            f"{entry}: input dtypes {got_in} != contract {want_in}",
            entry))
    got_out = [str(v.aval.dtype) for v in jaxpr.outvars]
    if got_out != contract["out_dtypes"]:
        findings.append(Finding(
            "JAX201", rel, 0,
            f"{entry}: output dtypes {got_out} != contract "
            f"{contract['out_dtypes']}", entry))

    allowed = {tuple(p) for p in contract.get("allowed_converts", [])}
    n_prims = 0
    forbidden = set(contract.get("forbidden_primitives", []))
    for eqn in _iter_eqns(jaxpr):
        n_prims += 1
        pname = eqn.primitive.name
        if pname in forbidden or any(s in pname
                                     for s in _FORBIDDEN_SUBSTRINGS):
            findings.append(Finding(
                "JAX203", rel, 0,
                f"{entry}: forbidden primitive '{pname}' in lowering "
                f"(host callback / sync)", entry))
        elif pname == "convert_element_type":
            pair = (str(eqn.invars[0].aval.dtype),
                    str(eqn.params["new_dtype"]))
            if pair not in allowed:
                findings.append(Finding(
                    "JAX202", rel, 0,
                    f"{entry}: convert_element_type {pair[0]}→{pair[1]} "
                    f"not in contract allowlist", entry))

    budget = contract["max_primitives"]
    if n_prims > budget:
        findings.append(Finding(
            "JAX204", rel, 0,
            f"{entry}: {n_prims} primitives exceeds contract budget "
            f"{budget} (accidental unroll?)", entry))

    golden = contract.get("golden")
    if golden:
        gpath = os.path.join(CONTRACTS_DIR, golden)
        grel = os.path.join("trivy_tpu", "analysis", "contracts", golden)
        text = normalize_jaxpr_text(str(closed))
        if not os.path.exists(gpath):
            findings.append(Finding(
                "JAX206", grel, 0,
                f"{entry}: golden jaxpr snapshot missing (run "
                f"python -m trivy_tpu.analysis --update-goldens)", entry))
        else:
            with open(gpath, encoding="utf-8") as f:
                want = f.read()
            if text != want:
                # find the first differing line for an actionable message
                got_l, want_l = text.splitlines(), want.splitlines()
                diff_at = next(
                    (i for i, (a, b) in enumerate(zip(got_l, want_l))
                     if a != b), min(len(got_l), len(want_l)))
                findings.append(Finding(
                    "JAX206", grel, diff_at + 1,
                    f"{entry}: lowering changed — jaxpr differs from "
                    f"golden at line {diff_at + 1} (review, then "
                    f"--update-goldens)", entry))
    return findings


# documentation entries for the sub-checks check_contract emits, so
# --list-rules shows every id a finding can carry
@register("JAX202", "convert-allowlist", "jaxpr")
def _doc_converts(*_a):
    """A convert_element_type not in the contract's allowlist: dtype
    drift across the db→join boundary, or a narrowing other than the
    int32→int8 report packing."""
    return []


@register("JAX203", "no-host-callbacks", "jaxpr")
def _doc_callbacks(*_a):
    """A host-callback/infeed/outfeed primitive in the lowering — a
    per-batch host sync that stalls the device."""
    return []


@register("JAX204", "primitive-budget", "jaxpr")
def _doc_budget(*_a):
    """Primitive count over the contract budget — the accidental O(K)
    Python-unroll detector."""
    return []


@register("JAX205", "entry-traces", "jaxpr")
def _doc_trace(*_a):
    """The entry point failed to trace under the contract's abstract
    shapes (signature or shape-contract break)."""
    return []


@register("JAX206", "golden-jaxpr", "jaxpr")
def _doc_golden(*_a):
    """The pretty-printed jaxpr differs from the checked-in golden
    snapshot — the hot-path lowering changed; review, then
    --update-goldens."""
    return []


def update_goldens() -> list[str]:
    """Re-trace every contract with a golden and rewrite the snapshot.
    Returns the paths written."""
    written = []
    for name, contract in load_contracts():
        golden = contract.get("golden")
        if not golden:
            continue
        closed = trace_contract(contract)
        gpath = os.path.join(CONTRACTS_DIR, golden)
        with open(gpath, "w", encoding="utf-8") as f:
            f.write(normalize_jaxpr_text(str(closed)))
        written.append(gpath)
    return written


def run() -> list[Finding]:
    """Dispatch every registered jaxpr rule over every contract — a
    rule added with @register(..., engine="jaxpr") runs here, same as
    the ast/xcheck engines (the JAX202-206 doc stubs are no-ops; the
    real checks live in check_contract/JAX201)."""
    from .registry import rules_for_engine
    findings: list[Finding] = []
    contracts = load_contracts()
    for rule in rules_for_engine("jaxpr"):
        for name, contract in contracts:
            findings.extend(rule.func(name, contract))
    return findings
