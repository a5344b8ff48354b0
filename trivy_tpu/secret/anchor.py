"""Where a secret rule's match can start.

Host confirmation runs each candidate rule's regex over a whole file.
Python's `re` skips quickly to a pattern's leading literal, but a
pattern that opens with `["']?` or `(^|\\s+)` is tried at every
position of the text. `derive` reads a compiled pattern once and
returns an `Anchor`: literals such that every match contains one of
them, a bounded number of characters after the match's start, or after
a run of one character class that opens the pattern. `finditer` then
tries `regex.match` only at the starts from which a match can reach an
occurrence of a literal, left to right, and yields exactly the matches
of `regex.finditer(text)`.

A pattern the derivation cannot read with certainty gets no anchor and
keeps the whole-text `finditer`: one with back-references, conditionals
or lookbehinds, one whose alternatives share no literal, or one whose
part before every literal is unbounded in another way.
"""

from __future__ import annotations

import re
from re import _compiler, _parser
from re._constants import (ANY, ASSERT, ASSERT_NOT, ATOMIC_GROUP, BRANCH,
                           GROUPREF, GROUPREF_EXISTS, IN, LITERAL, MAX_REPEAT,
                           MAXREPEAT, MIN_REPEAT, NOT_LITERAL,
                           POSSESSIVE_REPEAT, SUBPATTERN)
from typing import Iterator, NamedTuple, Optional

_REPEATS = (MAX_REPEAT, MIN_REPEAT, POSSESSIVE_REPEAT)
# the flags that decide which characters a literal matches
_LITERAL_FLAGS = re.IGNORECASE | re.ASCII | re.UNICODE
# more alternatives than this and the finder stops paying for itself
_MAX_LITERALS = 64


class Anchor(NamedTuple):
    finder: re.Pattern     # the literals, matched as the rule matches them
    lo: int                # a match starts lo..hi characters before one
    hi: int
    runs: tuple            # one-character classes of a leading run, or ()


def derive(regex: re.Pattern) -> Optional[Anchor]:
    """→ the rule's anchor, or None where the whole-text scan stays."""
    tree = _parser.parse(regex.pattern, regex.flags)
    for op, av in _walk(tree):
        if op in (GROUPREF, GROUPREF_EXISTS) or (
                op in (ASSERT, ASSERT_NOT) and av[0] < 0):
            return None
    atoms = list(_flatten(tree, tree.state.flags))
    head = _runs(*atoms[0]) if atoms else ()
    best = None
    for k in range(len(atoms)):
        # atoms[:k] lie before the literals: bounded, or a leading run
        # of one class followed by a bounded part
        lo, hi = _width(atoms[:k])
        runs = ()
        if hi >= MAXREPEAT:
            runs = head
            lo, hi = _width(atoms[1:k])
            if not runs or hi >= MAXREPEAT:
                break
        lits, flags, _ = _literals(atoms[k:])
        if "" in lits:
            continue
        # longest literals first, then the narrowest window of starts
        rank = (min(map(len, lits)), lo - hi, -len(lits))
        if best is None or rank > best[0]:
            best = rank, lits, flags, lo, hi, runs
    if best is None:
        return None
    _, lits, flags, lo, hi, runs = best
    finder = re.compile("|".join(map(re.escape, sorted(lits))), flags)
    return Anchor(finder, lo, hi, runs)


def finditer(regex: re.Pattern, anchor: Anchor,
             text: str) -> Iterator[re.Match]:
    """The matches of `regex.finditer(text)`, found from the anchor.

    finditer's next match is the least start at or after the previous
    match's end at which `regex.match` succeeds. Every match covers an
    occurrence p of a literal and starts in [p - hi, p - lo], reached
    back over a leading run; starts are tried in ascending order from
    those windows, each at most once. Anchored patterns never match
    the empty string, and no `endpos` is passed, so `^`, `$` and `\\Z`
    keep their whole-text meaning."""
    find = anchor.finder.search
    match = regex.match
    lo, hi, runs = anchor.lo, anchor.hi, anchor.runs
    nxt = 0                     # no match starts below nxt
    hit = find(text, lo)
    while hit is not None:
        p = hit.start()
        s = max(nxt, p - hi)
        if runs:
            s = min(_run_start(cls, text, s, nxt) for cls in runs)
        last = p - lo
        while s <= last:
            m = match(text, s)
            if m is None:
                s += 1
                continue
            yield m
            s = nxt = m.end()
        nxt = max(nxt, last + 1)
        hit = find(text, max(p + 1, nxt + lo))


def _run_start(cls: re.Pattern, text: str, t: int, floor: int) -> int:
    """→ the least r >= floor with text[r:t] all in the class."""
    while t > floor and cls.match(text, t - 1):
        t -= 1
    return t


def _walk(items):
    for op, av in items:
        yield op, av
        if op is SUBPATTERN:
            yield from _walk(av[3])
        elif op is BRANCH:
            for alt in av[1]:
                yield from _walk(alt)
        elif op in _REPEATS:
            yield from _walk(av[2])
        elif op in (ASSERT, ASSERT_NOT):
            yield from _walk(av[1])
        elif op is ATOMIC_GROUP:
            yield from _walk(av)


def _flatten(items, flags):
    """Top-level groups opened: → [(node, flags in effect)]."""
    for op, av in items:
        if op is SUBPATTERN:
            _, add, rem, sub = av
            yield from _flatten(sub, (flags | add) & ~rem)
        else:
            yield (op, av), flags


def _width(atoms) -> tuple[int, int]:
    """(least, most) characters the atoms match; most is MAXREPEAT
    where unbounded."""
    return _parser.SubPattern(_parser.State(),
                              [n for n, _ in atoms]).getwidth()


def _literals(atoms) -> tuple[set, int, bool]:
    """→ (strings one of which starts every match of the atoms in
    sequence, the flags they match under, whether every match is one
    of the strings)."""
    acc, flags = {""}, None
    for node, f in atoms:
        if flags is not None and (f ^ flags) & _LITERAL_FLAGS:
            return acc, flags, False
        flags = f
        got = _alternatives(node, f)
        if got is None:
            return acc, flags, False
        strs, complete = got
        grown = {a + b for a in acc for b in strs}
        if len(grown) > _MAX_LITERALS:
            return acc, flags, False
        acc = grown
        if not complete:
            return acc, flags, False
    return acc, flags, True


def _alternatives(node, flags):
    """→ (strings, complete) of one node as `_literals` gives them for
    a sequence; None where no literal starts its matches."""
    op, av = node
    if op is LITERAL:
        return {chr(av)}, True
    if op is IN and all(o is LITERAL for o, _ in av):
        return {chr(c) for _, c in av}, True
    if op is SUBPATTERN:
        _, add, rem, sub = av
        inner = (flags | add) & ~rem
        if (inner ^ flags) & _LITERAL_FLAGS:
            return None
        strs, _, complete = _literals([(n, inner) for n in sub])
        return strs, complete
    if op is BRANCH:
        out, complete = set(), True
        for alt in av[1]:
            strs, _, done = _literals([(n, flags) for n in alt])
            out |= strs
            complete = complete and done
        return out, complete
    return None


def _runs(node, flags) -> tuple:
    """A leading run: an unbounded repeat of one character class, or
    alternatives each such a repeat or of width 0 → the classes, each
    compiled to match one character as the rule does; else ()."""
    op, av = node
    if op in _REPEATS and av[1] == MAXREPEAT:
        cls = _one_char(list(av[2]), flags)
        return (cls,) if cls is not None else ()
    if op is BRANCH:
        out = []
        for alt in av[1]:
            if alt.getwidth() == (0, 0):
                continue
            atoms = list(_flatten(alt, flags))
            got = _runs(*atoms[0]) if len(atoms) == 1 else ()
            if not got:
                return ()
            out.extend(got)
        return tuple(out)
    return ()


def _one_char(items, flags) -> Optional[re.Pattern]:
    atoms = list(_flatten(items, flags))
    if len(atoms) != 1:
        return None
    (op, av), f = atoms[0]
    if op not in (LITERAL, NOT_LITERAL, ANY, IN):
        return None
    state = _parser.State()
    state.flags = f
    return _compiler.compile(_parser.SubPattern(state, [(op, av)]), f)
