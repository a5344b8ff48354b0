"""SecretScanner v2: exact multi-pattern keyword matching on device,
regex confirmation on host.

Parity contract with the reference scanner (pkg/fanal/secret/scanner.go
Scan:341-418): per file — global allow paths, per-rule path gates, keyword
prefilter (here: one device shift-or pass over all files × all rules
instead of bytes.Contains per rule per file), regex locations with optional
secret-group submatch, allow regexes, exclude blocks, censoring, line/
context extraction (findLocation:447-504), finding sort.

Engine v1 ran a 4-byte-prefix SUPERSET filter on device and re-confirmed
every candidate with a host substring pass; v2's device bitmask is exact
(ops/ac.py has the shift-or derivation), so the host stage is "run the
regex for gated rules" and nothing else. Three prefilter paths, counted
by `trivy_tpu_secret_prefilter_path_total{path=}`:

  pallas  the ops/shiftor_pallas VMEM kernel (TPU backends)
  jnp     ops/ac.shiftor_scan — the CPU path, and the mesh path via
          parallel.mesh.sharded_shiftor_scan (chunk rows sharded over
          every device, so the secrets lane rides meshguard's fault
          domains exactly like the join). Known cost: the meshed lane
          pays the jnp scan's n_keywords × state_words HBM passes per
          shard — dispatching the Pallas kernel per shard under
          shard_map is the open follow-up, deferred until a live-TPU
          round can validate it
  host    bytes.find per keyword — small batches (the device cannot
          amortize dispatch latency under `small_batch_bytes`), the
          graftguard fallback while the detect breaker is open, and
          the parity oracle tier-1 gates the device paths against

`scan_files_many` is the coalesced entry: fanald's pipelined layer walk
hands EVERY missing layer's secret files to one call, so one device
launch serves many concurrent layers the way detectd coalesces joins —
per-layer calls rarely cross the small-batch floor, coalesced ones do.
"""

from __future__ import annotations

import functools
import re
import threading
import time
from typing import NamedTuple, Optional

import numpy as np

from .. import compile_cache
from .. import types as T
from ..metrics import METRICS
from ..obs import cost as _cost
from ..obs import span
from ..ops import ac
from .anchor import finditer
from .rules import BUILTIN_RULES, GLOBAL_ALLOW_RULES, Rule

CHUNK_LEN = 16384
# Max chunk rows per device call (64 MiB of chunk bytes), a power of
# two; a launch's last call is padded up to a power of two too, so each
# bucket shape compiles exactly once (see _pieces). The Pallas
# path's state-word planes make the top bucket's temporaries ~3.8 GB
# on a v5e (compiled memory_analysis, PR 21).
DEVICE_ROWS = 4096
# Below this many total bytes the device path cannot amortize its
# dispatch+transfer latency and plain bytes.find wins — route small
# batches to the host scan so the default is never slower than host.
SMALL_BATCH_BYTES = 2 << 20


class SecretScanner:
    def __init__(self, rules: Optional[list[Rule]] = None,
                 allow_rules: Optional[list] = None,
                 use_device: bool = True,
                 exclude_regexes: Optional[list] = None,
                 mesh=None,
                 small_batch_bytes: Optional[int] = None):
        self.rules = rules if rules is not None else BUILTIN_RULES
        self.global_allow = (allow_rules if allow_rules is not None
                             else GLOBAL_ALLOW_RULES)
        # global exclude-block regexes (scanner.go:27-41 Config)
        self.global_exclude = exclude_regexes or []
        self.use_device = use_device
        # when set, the keyword prefilter shards chunk rows over every
        # device of the dp×db mesh (parallel.mesh.sharded_shiftor_scan)
        self.mesh = mesh
        # instance knob so coalesced callers (storm drills, bench) can
        # force the device path on small fixtures
        self.small_batch_bytes = SMALL_BATCH_BYTES \
            if small_batch_bytes is None else small_batch_bytes
        # keyword → rule bitset mapping for the shared automaton
        self._keywords: list[bytes] = []
        self._kw_rules: list[list[int]] = []
        kw_index: dict[bytes, int] = {}
        self._no_keyword_rules = []
        for ri, rule in enumerate(self.rules):
            if not rule.keywords:
                self._no_keyword_rules.append(ri)
                continue
            for kw in rule.keywords:
                k = kw.lower().encode()
                if k not in kw_index:
                    kw_index[k] = len(self._keywords)
                    self._keywords.append(k)
                    self._kw_rules.append([])
                self._kw_rules[kw_index[k]].append(ri)
        self._bank = ac.build_literal_bank(self._keywords) \
            if self._keywords else None
        self._device_arrays = None
        self._pallas_arrays = None
        self._lock = threading.Lock()
        # graftprof: jnp-path shapes this scanner has dispatched (a new
        # (rows, chunk_len) bucket is a fresh compile) + the bank's
        # host-resident footprint
        self._seen_shapes: set = set()
        if self._bank is not None:
            from ..obs.perf import LEDGER, ndarray_bytes
            LEDGER.note_resident(
                "secret_bank", ndarray_bytes(self._bank.kw_words,
                                             self._bank.kw_masks))

    # --- device prefilter ---

    def _keyword_masks(self, files: list[bytes]) -> list[set[int]]:
        """→ per-file set of rule indices whose keywords appear (exact:
        the device bitmask IS keyword presence, no host re-confirm).

        graftguard: the device prefilter shares the detect breaker —
        while it is open the host scan runs directly (identical rule
        sets, both paths are exact), and device failures here count
        toward opening it. The whole device pass runs under
        GUARD.watch: its dispatch+gets are synchronous, so a clean
        exit is real execution success, a wedge arms the watchdog
        (trips the breaker for everyone else), and errors are recorded
        exactly once by the watch. The `secret.prefilter` failpoint
        fires inside the watch, so chaos drills exercise exactly the
        degradation a real device fault takes."""
        from ..resilience import GUARD, DeviceError
        from ..resilience.failpoints import failpoint
        if self._bank is None:
            return [set() for _ in files]
        total = sum(len(f) for f in files)
        if self.use_device and total >= self.small_batch_bytes and \
                GUARD.allow_device():
            # packed (and the kernel compiled) outside the watch: a
            # kernel the compiler refuses is a bug in this repo, not a
            # device fault, so it raises out of the scan instead of
            # opening the breaker and reading as host traffic
            packed = self._pack(files)
            try:
                with GUARD.watch("detect.device_get"):
                    failpoint("secret.prefilter")
                    out, path = self._launch(packed)
                self._note_path(path, total)
                return out
            except DeviceError:
                # logged, not just swallowed: a DETERMINISTIC host-side
                # bug landing here would open the shared breaker after
                # fail_threshold scans, and the operator needs the
                # traceback to tell it apart from a real device outage
                from ..log import get as _get_logger
                _get_logger("secret").warning(
                    "device keyword prefilter failed; falling back to "
                    "host scan (counted against the detect breaker)",
                    exc_info=True)
        self._note_path("host", total)
        return self._keyword_masks_host(files)

    @staticmethod
    def _note_path(path: str, n_bytes: int) -> None:
        METRICS.inc("trivy_tpu_secret_prefilter_path_total", path=path)
        METRICS.inc("trivy_tpu_secret_scan_bytes_total", n_bytes,
                    path=path)
        # graftcost: scanned bytes billed to the requesting tenant by
        # the serving path that actually ran them
        _cost.charge_secret_bytes(path, float(n_bytes))

    def _keyword_masks_host(self, files: list[bytes]) -> list[set[int]]:
        out = []
        for data in files:
            low = bytes(ac.lower_bytes(data)) if data else b""
            hit = set()
            for ki, kw in enumerate(self._keywords):
                if kw in low:
                    hit.update(self._kw_rules[ki])
            out.append(hit)
        return out

    def _keyword_masks_device(self, files: list[bytes]
                              ) -> tuple[list[set[int]], str]:
        """Unsupervised device prefilter (warmup and tests): → (per-
        file exact rule sets, the path that served the launch:
        \"pallas\" | \"jnp\")."""
        return self._launch(self._pack(files))

    def _pack(self, files: list[bytes]) -> "_Packed":
        """Host half of a device prefilter: chunk every file into one
        buffer in one pass, dedup its rows exactly, and compile the
        Pallas kernel for every bucket shape the launch will use."""
        bank = self._bank
        with span("secret.pack", files=len(files)) as sp:
            rows, owner, fp = ac.pack_batch(
                files, CHUNK_LEN, bank.max_kw_len - 1,
                capacity=_bucket_rows, fingerprint=True)
            n = int(owner.shape[0])
            # content dedup: container filesystems repeat whole blocks
            # across files and layers (vendored code, copied configs,
            # near-identical images) — each distinct 16 KiB row ships
            # once and its result fans back out. Without a repeat the
            # packed buffer ships as it is.
            remap, uniq = _dedup(rows[:n], fp)
            n_uniq = int(uniq.shape[0])
            if n_uniq < n:
                out = np.zeros((_bucket_rows(n_uniq), CHUNK_LEN), np.uint8)
                np.take(rows, uniq, axis=0, out=out[:n_uniq])
                rows = out
            METRICS.inc("trivy_tpu_secret_prefilter_rows_total", n_uniq,
                        outcome="unique")
            METRICS.inc("trivy_tpu_secret_prefilter_rows_total",
                        n - n_uniq, outcome="duplicate")
            sp.attrs["rows"] = n
            sp.attrs["unique"] = n_uniq
        pieces = [(rows[off:off + b], real)
                  for off, real, b in _pieces(n_uniq)]
        use_pallas = (self.mesh is None and bank.n_keywords <= 128
                      and _tpu_backend())
        if use_pallas:
            for piece, _ in pieces:
                self._pallas_kernel(piece.shape)
        return _Packed(len(files), owner, remap, n_uniq, pieces,
                       use_pallas)

    def _launch(self, packed: "_Packed") -> tuple[list[set[int]], str]:
        """Device half: dispatch every piece, fetch, decode. The path
        rides the return value, not instance state — one scanner serves
        many request threads, and a shared last-path attribute would
        mislabel the per-path counters under concurrency."""
        import jax
        bank = self._bank
        out: list[set[int]] = [set() for _ in range(packed.n_files)]
        path = "pallas" if packed.use_pallas else "jnp"
        if not packed.pieces:
            return out, path
        if self._device_arrays is None:
            if self.mesh is not None:
                # replicate the (tiny) bank across the mesh once
                from jax.sharding import NamedSharding, PartitionSpec
                rep = NamedSharding(self.mesh, PartitionSpec())
                self._device_arrays = (
                    jax.device_put(bank.kw_words, rep),
                    jax.device_put(bank.kw_masks, rep))
            else:
                self._device_arrays = (jax.device_put(bank.kw_words),
                                       jax.device_put(bank.kw_masks))
        kw_words, kw_masks = self._device_arrays
        from ..obs.perf import LEDGER
        from ..resilience import GUARD
        # ledger contract: blameless background work (a redetectd-style
        # sweep) re-tags its launches so it never muddies the live
        # occupancy story
        site = "redetect" if GUARD.blameless_active() else "secret"
        futures = []
        for piece, real_rows in packed.pieces:
            b, row_len = piece.shape
            t0 = time.perf_counter()
            # device_put, not jnp.asarray — the latter is an order of
            # magnitude slower for large host arrays
            if packed.use_pallas:
                kww, kwm, bit = self._pallas_arrays
                futures.append(self._pallas_kernel(piece.shape)(
                    kww, kwm, bit, jax.device_put(piece)))
            else:
                # graftprof: a (rows, chunk_len) bucket this scanner
                # has not dispatched is a fresh trace+compile — the
                # dispatch call below pays it synchronously, so its
                # wall time is the compile estimate the ledger records
                with self._lock:
                    new_shape = piece.shape not in self._seen_shapes
                    self._seen_shapes.add(piece.shape)
                with compile_cache.watch() as got:
                    if self.mesh is not None:
                        from ..parallel.mesh import sharded_shiftor_scan
                        futures.append(sharded_shiftor_scan(
                            self.mesh, kw_words, kw_masks, piece,
                            n_words=bank.words))
                    else:
                        futures.append(ac.shiftor_scan(
                            kw_words, kw_masks, jax.device_put(piece),
                            n_words=bank.words))
                if new_shape:
                    LEDGER.note_compile(
                        site, b, 0, (time.perf_counter() - t0) * 1e3,
                        loaded=got.compiled == 0)
            LEDGER.note_dispatch(site, real_rows, b,
                                 row_bytes=row_len)
            # graftcost: the dispatch call's wall time (compile
            # included on a fresh jnp shape) is this piece's device ms
            _cost.charge_device_ms(site,
                                   (time.perf_counter() - t0) * 1e3)
        fetched = []
        for f in futures:
            t_get = time.perf_counter()
            arr = jax.device_get(f)
            _cost.charge_device_ms(
                site, (time.perf_counter() - t_get) * 1e3)
            _cost.ledgered_transfer("dense", float(arr.nbytes))
            fetched.append(arr)
        masks = np.concatenate(
            fetched, axis=0)[:packed.n_uniq][packed.remap]
        # decode the EXACT bitmask: a set bit means the full keyword
        # occurs in that chunk row, so file hits are direct unions.
        # Bit decode is vectorized (unpackbits + nonzero): the per-word
        # Python bit loop was ~1 s on a 64 MiB corpus.
        u8 = np.ascontiguousarray(
            masks.astype(np.uint32)).view(np.uint8)
        bits = np.unpackbits(u8, axis=1, bitorder="little")
        hit_ci, hit_ki = np.nonzero(bits[:, :bank.n_keywords])
        owner_l = packed.owner.tolist()
        for ci, ki in zip(hit_ci.tolist(), hit_ki.tolist()):
            out[owner_l[ci]].update(self._kw_rules[ki])
        return out, path

    def _pallas_kernel(self, shape: tuple[int, int]):
        """The Pallas TPU kernel (ops.shiftor_pallas) for one padded
        [rows, CHUNK_LEN] bucket, compiled ahead of time."""
        import jax

        from ..ops import shiftor_pallas as sp
        if self._pallas_arrays is None:
            self._pallas_arrays = tuple(
                jax.device_put(a) for a in sp.pack_bank(self._bank))
        # one compile per shape even when several scans meet a new
        # bucket at once (lru_cache alone lets them race)
        with _COMPILE_LOCK:
            return _compiled_shiftor(self._bank.state_words,
                                     self._bank.words, shape)

    # --- host confirmation (exact reference semantics) ---

    def scan_files(self, files: list[tuple[str, bytes]]) -> list[T.Secret]:
        """files: [(path, content)] → per-file Secret results (empty
        findings omitted)."""
        return self.scan_files_many([files])[0]

    def scan_files_many(self, batches: list[list[tuple[str, bytes]]]
                        ) -> list[list[T.Secret]]:
        """Coalesced entry: ONE keyword-prefilter launch over every
        batch's files (fanald hands each missing layer as one batch),
        then per-file regex confirmation. Results are per batch, in
        batch/file order — bit-identical to per-batch scan_files calls
        by construction (the prefilter is exact either way; only the
        device launch is shared)."""
        files = [fc for batch in batches for fc in batch]
        contents = [c for _, c in files]
        with span("secret.prefilter", files=len(files),
                  batches=len(batches),
                  bytes=sum(len(c) for c in contents)) as sp:
            masks = self._keyword_masks(contents)
            flagged = sum(len(m) for m in masks)
            sp.attrs["candidates"] = flagged
        results: list[list[T.Secret]] = []
        confirmed = 0
        it = iter(zip(files, masks))
        with span("secret.confirm", files=len(files)) as sp:
            for batch in batches:
                out = []
                for _ in batch:
                    (path, content), rule_idx = next(it)
                    gated = set(rule_idx)
                    sec = self.scan_file(
                        path, content,
                        candidate_rules=gated
                        | set(self._no_keyword_rules))
                    if sec.findings:
                        out.append(sec)
                        hit_ids = {f.rule_id for f in sec.findings}
                        confirmed += sum(
                            1 for ri in gated
                            if self.rules[ri].id in hit_ids)
                results.append(out)
            sp.attrs["findings"] = sum(len(s.findings)
                                       for out in results for s in out)
        if flagged:
            # regex yield of the keyword gate: how many gated
            # (file, rule) candidates actually produced a finding
            METRICS.observe("trivy_tpu_secret_candidate_precision",
                            confirmed / flagged)
        METRICS.inc("trivy_tpu_secret_files_total", len(files))
        METRICS.inc("trivy_tpu_secret_bytes_total",
                    sum(len(c) for c in contents))
        METRICS.inc("trivy_tpu_secret_findings_total",
                    sum(len(s.findings) for out in results for s in out))
        return results

    def scan_file(self, path: str, content: bytes,
                  candidate_rules: Optional[set] = None) -> T.Secret:
        # no candidate rule, nothing to confirm: the file is not decoded
        if candidate_rules is not None and not candidate_rules:
            return T.Secret(file_path=path)
        if any(a.path and a.path.search(path) for a in self.global_allow):
            return T.Secret(file_path=path)
        text = content.decode("utf-8", errors="surrogateescape")
        matched = []
        global_exb = _blocks(text, self.global_exclude) \
            if self.global_exclude else []
        if candidate_rules is None:
            low = bytes(ac.lower_bytes(content)) if content else b""
        for ri, rule in enumerate(self.rules):
            if candidate_rules is not None and ri not in candidate_rules:
                continue
            if not rule.match_path(path):
                continue
            if rule.allow_path(path):
                continue
            if candidate_rules is None and not rule.match_keywords(low):
                continue
            locs = self._find_locations(rule, text)
            if not locs:
                continue
            exb = _blocks(text, rule.exclude_regexes) + global_exb
            matched.extend((rule, start, end) for start, end in locs
                           if not _in_blocks(start, end, exb))
        if not matched:
            return T.Secret(file_path=path)
        censored_text = _censor(text, [(s, e) for _, s, e in matched])
        findings = [self._to_finding(rule, s, e, censored_text)
                    for rule, s, e in matched]
        findings.sort(key=lambda f: (f.rule_id, f.match))
        return T.Secret(file_path=path, findings=findings)

    def _find_locations(self, rule: Rule, text: str):
        # one confirmation per (file, rule); an anchored rule tries only
        # the starts from which a match can reach one of its literals
        anchor = rule.anchor
        METRICS.inc("trivy_tpu_secret_confirm_total",
                    path="full" if anchor is None else "anchored")
        matches = rule.regex.finditer(text) if anchor is None \
            else finditer(rule.regex, anchor, text)
        locs = []
        if rule.secret_group:
            # a Go regex may bind the group name more than once
            # (renamed name__N at compile); each occurrence is a finding
            groups = (rule.secret_group,) + tuple(
                getattr(rule, "secret_aliases", ()))
            for m in matches:
                if self._allowed(rule, m.group(0)):
                    continue
                for g in groups:
                    try:
                        s, e = m.span(g)
                    except (IndexError, re.error):
                        continue
                    if s >= 0:
                        locs.append((s, e))
        else:
            for m in matches:
                if self._allowed(rule, m.group(0)):
                    continue
                locs.append(m.span())
        return locs

    def _allowed(self, rule: Rule, match: str) -> bool:
        if any(a.regex and a.regex.search(match) for a in self.global_allow):
            return True
        return rule.allow_match(match)

    @staticmethod
    def _to_finding(rule: Rule, start: int, end: int,
                    content: str) -> T.SecretFinding:
        start_line, end_line, code, match_line = _find_location(
            start, end, content)
        return T.SecretFinding(
            rule_id=rule.id,
            category=rule.category,
            severity=rule.severity or "UNKNOWN",
            title=rule.title,
            start_line=start_line,
            end_line=end_line,
            code=code,
            match=match_line,
        )


_COMPILE_LOCK = threading.Lock()


@functools.lru_cache(maxsize=None)
def _compiled_shiftor(state_words: int, n_words: int,
                      shape: tuple[int, int]):
    """Process-wide like jit's own cache (the executable depends on
    shapes only, not on a bank's values); a miss pays the compile and
    records it in the graftprof ledger."""
    import jax

    from ..obs.perf import LEDGER
    from ..ops import shiftor_pallas as sp
    t0 = time.perf_counter()
    kw = jax.ShapeDtypeStruct((state_words, sp.K_LANES), np.int32)
    with compile_cache.watch() as got:
        exe = sp.shiftor.lower(
            kw, kw, jax.ShapeDtypeStruct((1, sp.K_LANES), np.int32),
            jax.ShapeDtypeStruct(shape, np.uint8),
            n_words=n_words).compile()
    LEDGER.note_compile("secret", shape[0], 0,
                        (time.perf_counter() - t0) * 1e3,
                        loaded=got.compiled == 0)
    return exe


def _tpu_backend() -> bool:
    import jax
    return jax.default_backend() == "tpu"


def _pieces(n: int) -> list[tuple[int, int, int]]:
    """→ [(first row, real rows, bucket rows)] of the device calls for
    n packed rows: at most DEVICE_ROWS (a power of two) per call, the
    last padded up to a power of two so each bucket shape compiles
    once. The buckets lie back to back, so a buffer of _bucket_rows(n)
    rows holds every piece as a view."""
    from ..ops import next_pow2
    return [(off, min(DEVICE_ROWS, n - off),
             next_pow2(min(DEVICE_ROWS, n - off), floor=64))
            for off in range(0, n, DEVICE_ROWS)]


def _bucket_rows(n: int) -> int:
    pieces = _pieces(n)
    return pieces[-1][0] + pieces[-1][2] if pieces else 0


def _dedup(rows: np.ndarray, fp: np.ndarray
           ) -> tuple[np.ndarray, np.ndarray]:
    """Exact row dedup → (remap: row → index into uniq, uniq: the first
    row of each distinct content, ascending). Rows are grouped by their
    64-bit fingerprint and only rows that share one are byte-compared,
    so two rows merge only when their bytes are equal."""
    n = fp.shape[0]
    order = np.argsort(fp, kind="stable")
    shared = fp[order[1:]] == fp[order[:-1]]
    if not shared.any():
        return np.arange(n), np.arange(n)
    first = np.arange(n)             # row → first row with equal bytes
    starts = np.flatnonzero(np.r_[True, ~shared])
    ends = np.r_[starts[1:], n]
    multi = ends - starts > 1
    for s, e in zip(starts[multi].tolist(), ends[multi].tolist()):
        group = order[s:e]           # ascending: the sort is stable
        head, rest = group[0], group[1:]
        # compared 256 rows at a time: a file of one repeated block
        # can fill a whole group
        if all((rows[rest[k:k + 256]] == rows[head]).all()
               for k in range(0, rest.shape[0], 256)):
            first[rest] = head
            continue
        # a fingerprint shared by rows that differ: key them by bytes
        seen: dict[bytes, int] = {}
        for i in group.tolist():
            first[i] = seen.setdefault(rows[i].tobytes(), i)
    is_first = first == np.arange(n)
    return (np.cumsum(is_first) - 1)[first], np.flatnonzero(is_first)


class _Packed(NamedTuple):
    """A device prefilter's host-side inputs (see SecretScanner._pack)."""
    n_files: int
    owner: np.ndarray            # chunk row → file index
    remap: np.ndarray            # chunk row → deduplicated row
    n_uniq: int
    pieces: list                 # [(padded uint8[b, L], real rows)]
    use_pallas: bool


def _blocks(text: str, regexes) -> list[tuple[int, int]]:
    out = []
    for rx in regexes:
        for m in rx.finditer(text):
            out.append(m.span())
    return out


def _in_blocks(start: int, end: int, blocks) -> bool:
    return any(bs <= start and end <= be for bs, be in blocks)


def _censor(text: str, spans) -> str:
    """`text` with every character inside a span replaced by "*"."""
    out, at = [], 0
    for start, end in sorted(spans):
        if end <= at:
            continue
        start = max(start, at)
        out += (text[at:start], "*" * (end - start))
        at = end
    out.append(text[at:])
    return "".join(out)


_RADIUS = 2  # context lines above/below (scanner.go secretHighlightRadius)


def _find_location(start: int, end: int, content: str):
    """Line numbers, context code window, and the censored match line —
    reference findLocation (scanner.go:447-504)."""
    start_line_num = content.count("\n", 0, start)
    line_start = content.rfind("\n", 0, start)
    line_start = 0 if line_start == -1 else line_start + 1
    line_end = content.find("\n", start)
    line_end = len(content) if line_end == -1 else line_end
    if line_end - line_start > 100:
        line_start = max(start - 30, 0)
        line_end = min(end + 20, len(content))
    match_line = content[line_start:line_end]
    end_line_num = start_line_num + content.count("\n", start, end)

    lines = content.split("\n")
    code_start = max(start_line_num - _RADIUS, 0)
    code_end = min(end_line_num + _RADIUS, len(lines))
    code_lines = []
    found_first = False
    for i, raw in enumerate(lines[code_start:code_end]):
        real = code_start + i
        in_cause = start_line_num <= real <= end_line_num
        code_lines.append(T.CodeLine(
            number=code_start + i + 1,
            content=raw,
            is_cause=in_cause,
            highlighted=raw,
            first_cause=in_cause and not found_first,
            last_cause=False,
        ))
        found_first = found_first or in_cause
    for cl in reversed(code_lines):
        if cl.is_cause:
            cl.last_cause = True
            break
    return (start_line_num + 1, end_line_num + 1,
            T.Code(lines=code_lines), match_line)
