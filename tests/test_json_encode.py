"""The JSON encoder (`JsonMixin.to_json` with `_strip_empty`) against a
copy of the per-call encoder it replaced, which walked
`dataclasses.fields` and rebuilt each JSON name on every call.

Reports, cache entries and server replies are the encoder's bytes, so
`json.dumps` of both must be equal, key order included, for every
`JsonMixin` class in default, populated and edge-case states."""

import dataclasses
import enum
import json
import random
from collections import OrderedDict
from dataclasses import dataclass

import pytest

from trivy_tpu import types as T
from trivy_tpu.fanal.artifact import ImageArchiveArtifact
from trivy_tpu.fanal.cache import MemoryCache, blob_from_json

from helpers import make_image


# ---- the oracle: the previous encoder, copied as it was -----------------

def _oracle_strip_empty(v):
    if dataclasses.is_dataclass(v) and not isinstance(v, type):
        return _oracle_to_json(v)
    if isinstance(v, dict):
        out = {}
        for k, val in v.items():
            sv = _oracle_strip_empty(val)
            if sv not in ("", None, [], {}, 0, False) or sv is True:
                out[k] = sv
        return out
    if isinstance(v, (list, tuple)):
        return [_oracle_strip_empty(x) for x in v]
    if isinstance(v, float) and v.is_integer():
        return int(v)
    return v


def _oracle_fields_to_json(self) -> dict:
    out = {}
    for f in dataclasses.fields(self):
        if f.name in self._json_skip:
            continue
        v = getattr(self, f.name)
        name = self._json_names.get(f.name, T._pascal(f.name))
        sv = v if f.name in self._json_raw else _oracle_strip_empty(v)
        if f.name in self._keep_zero:
            out[name] = sv
            continue
        if sv in ("", None, [], {}, 0, False):
            continue
        out[name] = sv
    return out


def _oracle_to_json(obj) -> dict:
    if not isinstance(obj, T.JsonMixin):
        return obj.to_json()
    out = _oracle_fields_to_json(obj)
    if isinstance(obj, T.DetectedVulnerability):
        emb = out.pop("Vulnerability", None) or {}
        out.update(emb)
    return out


def _assert_same(obj):
    got = json.dumps(obj.to_json())
    want = json.dumps(_oracle_to_json(obj))
    assert got == want


# ---- the classes and generic instances ----------------------------------

MIXIN_CLASSES = sorted(
    (c for c in vars(T).values()
     if isinstance(c, type) and issubclass(c, T.JsonMixin)
     and c is not T.JsonMixin),
    key=lambda c: c.__name__)


def test_every_mixin_class_is_covered():
    assert len(MIXIN_CLASSES) == 28
    assert all(dataclasses.is_dataclass(c) for c in MIXIN_CLASSES)


class Sev(str, enum.Enum):
    HIGH = "HIGH"
    NONE = ""


def _populated(cls, depth=0):
    """An instance with every field set from its annotation: plain
    values, nested mixins, and lists and dicts that mix zero and
    non-zero entries."""
    kw = {}
    for i, f in enumerate(dataclasses.fields(cls)):
        ann = str(f.type).strip("'\"")
        if ann.startswith("Optional["):
            ann = ann[len("Optional["):-1].strip("'\"")
        nested = next((c for c in MIXIN_CLASSES if c.__name__ == ann), None)
        if nested is not None:
            kw[f.name] = (_populated(nested, depth + 1) if depth < 2
                          else nested())
        elif ann == "str":
            kw[f.name] = f"{cls.__name__}-{f.name}"
        elif ann == "int":
            kw[f.name] = i + 1
        elif ann == "bool":
            kw[f.name] = True
        elif ann == "float":
            kw[f.name] = 7.5 if i % 2 else 3.0
        elif f.name in cls._json_raw:  # emitted verbatim: plain JSON only
            kw[f.name] = ({"os": "", "n": 0, "k": [{"z": 0}]}
                          if ann == "dict" else [{"Stage": "", "Kind": 0}])
        elif ann == "list":
            kw[f.name] = ["a", "", 0, 2.0, None, {"k": 0, "v": "x"},
                          T.Layer(diff_id="sha256:ab"), [], ("t", 0)]
        elif ann == "dict":
            kw[f.name] = {"z": "", "one": 1, "f": 4.0,
                          "nested": {"zero": 0, "empty": {}, "s": "s"},
                          "cvss": T.CVSS(v3_score=9.0)}
        else:
            raise AssertionError(f"{cls.__name__}.{f.name}: {ann}")
    return cls(**kw)


@pytest.mark.parametrize("cls", MIXIN_CLASSES, ids=lambda c: c.__name__)
def test_default_instance_matches_oracle(cls):
    _assert_same(cls())


@pytest.mark.parametrize("cls", MIXIN_CLASSES, ids=lambda c: c.__name__)
def test_populated_instance_matches_oracle(cls):
    obj = _populated(cls)
    _assert_same(obj)
    # the plan is reused: a second encode gives the same bytes
    assert json.dumps(obj.to_json()) == json.dumps(_oracle_to_json(obj))


# ---- the cases the server and the caches serialise ----------------------

def _apk_db(seed: int, n: int) -> bytes:
    rng = random.Random(seed)
    rows = []
    for i in range(n):
        name = f"pkg{i}-{rng.randrange(10 ** 6)}"
        ver = (f"{rng.randrange(9)}.{rng.randrange(30)}."
               f"{rng.randrange(9)}-r{rng.randrange(5)}")
        rows.append(f"P:{name}\nV:{ver}\nA:x86_64\no:{name}\nL:MIT\n"
                    f"D:so:libc.musl-x86_64.so.1\n\n")
    return "".join(rows).encode()


@pytest.fixture(scope="module")
def apk_blob(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("img") / "apk.tar")
    make_image(path, [{
        "etc/os-release": b'NAME="Alpine Linux"\nID=alpine\n'
                          b'VERSION_ID=3.19.1\n',
        "lib/apk/db/installed": _apk_db(2028, 95),
    }])
    cache = MemoryCache()
    ImageArchiveArtifact(path, cache, scanners=("vuln",)).inspect()
    (blob_json,) = cache.blobs.values()
    return blob_from_json(blob_json)


def test_seeded_apk_blob_matches_oracle(apk_blob):
    pkgs = [p for pi in apk_blob.package_infos for p in pi.packages]
    assert len(pkgs) == 95
    _assert_same(apk_blob)
    # the cache stores exactly what the oracle would have stored
    cache = MemoryCache()
    cache.put_blob("b", apk_blob)
    assert json.dumps(cache.blobs["b"]) == \
        json.dumps(_oracle_to_json(apk_blob))


def _vuln(i: int, with_source: bool) -> T.DetectedVulnerability:
    return T.DetectedVulnerability(
        vulnerability_id=f"CVE-2024-{1000 + i}", pkg_id=f"p{i}@1.0-r0",
        pkg_name=f"p{i}", installed_version="1.0-r0",
        fixed_version="1.1-r0" if i % 3 else "", status="fixed",
        pkg_identifier=T.PkgIdentifier(purl=f"pkg:apk/alpine/p{i}@1.0-r0",
                                       uid=f"{i:016x}"),
        layer=T.Layer(diff_id="sha256:" + "ab" * 32) if i % 2 else T.Layer(),
        data_source=(T.DataSource(id="alpine", name="Alpine Secdb",
                                  url="https://secdb.alpinelinux.org/")
                     if with_source else None),
        primary_url=f"https://avd.aquasec.com/nvd/cve-2024-{1000 + i}",
        vulnerability=T.Vulnerability(
            title=f"t{i}" if i % 4 else "", severity="HIGH",
            vendor_severity={"nvd": 3, "alpine": 0},
            cvss={"nvd": T.CVSS(v3_vector="AV:N", v3_score=7.0)}
            if i % 2 else {},
            references=[f"https://example.com/{i}"] if i % 5 else []))


@pytest.mark.parametrize("with_source", [True, False],
                         ids=["data_source", "no_data_source"])
def test_scan_result_with_vulnerabilities_matches_oracle(with_source):
    res = T.Result(target="img (alpine 3.19.1)", clazz="os-pkgs",
                   type="alpine",
                   vulnerabilities=[_vuln(i, with_source) for i in range(57)])
    _assert_same(res)
    for v in res.vulnerabilities:
        _assert_same(v)


EDGE_CASES = {
    # _keep_zero fields at zero
    "keep_zero_codeline": T.CodeLine(),
    "keep_zero_package": T.Package(name="musl"),
    "keep_zero_data_source": T.DataSource(),
    "keep_zero_misconf_summary": T.MisconfSummary(),
    "keep_zero_result_target": T.Result(),
    "keep_zero_secret_finding": T.SecretFinding(code=T.Code(lines=[
        T.CodeLine(number=0, content="")])),
    # _json_raw fields holding zeros
    "raw_ingest_errors_blob": T.BlobInfo(ingest_errors=[
        {"Stage": "", "Kind": "bomb", "Layer": 0, "Detail": None}]),
    "raw_ingest_errors_result": T.Result(target="t", ingest_errors=[
        {"Stage": "walk", "Kind": "", "Nested": {"zero": 0}}]),
    "raw_image_config_zero": T.Metadata(image_config=dict(
        T.ZERO_IMAGE_CONFIG)),
    "raw_image_config_empty": T.Metadata(),
    "raw_artifact_detail": T.ArtifactDetail(ingest_errors=[{}, {"a": 0}]),
    # _json_skip fields, set and unset
    "skip_build_info_set": T.Package(
        name="bash", build_info=T.BuildInfo(content_sets=["rhel-8"],
                                            nvr="bash-5", arch="x86_64")),
    "skip_build_info_unset": T.Package(name="bash"),
    # integer floats, at every depth
    "integer_float_cvss": T.CVSS(v2_score=5.0, v3_score=0.0,
                                 v40_score=9.5),
    "integer_float_license": T.DetectedLicense(confidence=1.0),
    "integer_float_license_zero": T.DetectedLicense(confidence=0.0),
    "integer_float_in_dict": T.Vulnerability(
        vendor_severity={"nvd": 3.0, "ghsa": 0.0, "redhat": 2.5}),
    # str-enum values, as a field and inside containers
    "str_enum_field": T.Vulnerability(severity=Sev.HIGH),
    "str_enum_zero": T.Vulnerability(severity=Sev.NONE, title="t"),
    "str_enum_in_list": T.Vulnerability(cwe_ids=[Sev.HIGH, Sev.NONE]),
    # tuples, and dict and list subclasses
    "tuple_references": T.Vulnerability(references=("a", "", "b")),
    "tuple_of_mixins": T.Secret(file_path="f", findings=(
        T.SecretFinding(rule_id="aws"), T.SecretFinding())),
    "ordered_dict": T.Vulnerability(vendor_severity=OrderedDict(
        [("b", 1), ("a", 0), ("c", 2)])),
    # nested dicts with zero values
    "nested_dict_zeros": T.Vulnerability(cvss={
        "nvd": {"V3Score": 0.0, "V3Vector": "", "x": {"y": 0, "z": []}},
        "ghsa": T.CVSS(), "redhat": {"bool": False, "t": True}}),
    "nested_empty_mixins": T.BlobInfo(os=T.OS(), repository=T.Repository(),
                                      build_info=T.BuildInfo()),
    "bool_values": T.OS(family="alpine", eosl=True, extended=False),
    "report": T.Report(created_at="2024-01-01T00:00:00Z", results=[
        T.Result(target="t", vulnerabilities=[_vuln(1, True)]),
        T.Result(target="", misconf_summary=T.MisconfSummary())]),
}


@pytest.mark.parametrize("name", sorted(EDGE_CASES))
def test_edge_case_matches_oracle(name):
    _assert_same(EDGE_CASES[name])


def test_edge_cases_emit_what_omitempty_keeps():
    """Spot checks of the oracle-agreed output itself."""
    assert T.CodeLine().to_json() == {
        "Number": 0, "Content": "", "IsCause": False, "Annotation": "",
        "Truncated": False, "FirstCause": False, "LastCause": False}
    assert T.CVSS(v2_score=5.0).to_json() == {"V2Score": 5}
    assert "BuildInfo" not in EDGE_CASES["skip_build_info_set"].to_json()
    assert T.Metadata().to_json() == {"ImageConfig": {}}
    assert json.dumps(T.Vulnerability(severity=Sev.HIGH).to_json()) == \
        '{"Severity": "HIGH"}'


# ---- each concrete class has its own plan -------------------------------

def _parent_and_child():
    @dataclass
    class Parent(T.JsonMixin):
        diff_id: str = ""
        created_by: str = ""
        size: int = 0
        _json_names = {"diff_id": "DiffID"}

    @dataclass
    class Child(Parent):
        extra: str = ""
        _json_names = {"diff_id": "Diff", "extra": "Extra"}
        _keep_zero = ("size",)

    return Parent, Child


@pytest.mark.parametrize("parent_first", [True, False],
                         ids=["parent_first", "child_first"])
def test_subclass_gets_its_own_plan(parent_first):
    Parent, Child = _parent_and_child()
    p = Parent(diff_id="sha256:1", created_by="")
    c = Child(diff_id="sha256:1", created_by="", extra="e")
    objs = [p, c] if parent_first else [c, p]
    first = {type(o): json.dumps(o.to_json()) for o in objs}
    assert first[Parent] == '{"DiffID": "sha256:1"}'
    assert first[Child] == '{"Diff": "sha256:1", "Size": 0, "Extra": "e"}'
    for o in reversed(objs):
        assert json.dumps(o.to_json()) == first[type(o)]
        assert json.dumps(o.to_json()) == json.dumps(_oracle_to_json(o))


def test_subclass_of_a_report_type_gets_its_own_plan():
    @dataclass
    class TaggedLayer(T.Layer):
        tag: str = ""
        _json_names = {"diff_id": "Diff"}
        _keep_zero = ("created_by",)

    layer = T.Layer(diff_id="d")
    before = json.dumps(layer.to_json())
    tagged = TaggedLayer(diff_id="d", tag="t")
    assert tagged.to_json() == {"Diff": "d", "CreatedBy": "", "Tag": "t"}
    assert json.dumps(layer.to_json()) == before == '{"DiffID": "d"}'
    _assert_same(tagged)
