"""The plain reference for query answers: decompress-then-compute over
the raw lines, written here and sharing no code with the program.

A query is a tuple:

- ``("search", pred)`` -> ``[(line_no, line), ...]`` in line order;
- ``("count", pred)`` -> the number of matching lines;
- ``("top_k", field, k)`` -> ``[(value, count), ...]``, most frequent
  first, ties by value;
- ``("time_histogram", field, bucket)`` -> ``{value // bucket: count}``
  over the integer of the value's first digit run, sorted by bucket.

and a predicate ``("substring", s)``, ``("regex", pattern)``,
``("field", name, value)`` or ``("and", pred, pred, ...)``. Header
fields come from the configuration's format: a line whose header does
not parse (a malformed line) has no fields, matches no field predicate
and counts in no aggregation.
"""

from __future__ import annotations

import re
from collections import Counter

_FIELD_RE = re.compile(r"<(\w+)>")
_DIGITS = re.compile(r"[0-9]+")


class Header:
    """Reads the header fields of ``<A> <B> ... <Z>: <Content>`` lines:
    fields are non-empty and free of whitespace, separated by single
    spaces, and ``": "`` follows the last one."""

    def __init__(self, fmt: str):
        names = _FIELD_RE.findall(fmt)
        if fmt != " ".join(f"<{n}>" for n in names[:-1]) + ": <Content>":
            raise ValueError(f"unsupported format {fmt!r}")
        self.names = names[:-1]

    def fields(self, line: str) -> dict | None:
        parts = line.split(" ", len(self.names) - 1)
        if len(parts) != len(self.names):
            return None
        last, sep, _content = parts[-1].partition(": ")
        parts[-1] = last
        if not sep or any(not p or any(c.isspace() for c in p) for p in parts):
            return None
        return dict(zip(self.names, parts))


class Reference:
    """Answers queries over ``lines`` (the archive's raw lines)."""

    def __init__(self, lines: list[str], fmt: str):
        self.lines = lines
        hdr = Header(fmt)
        self.fields = [hdr.fields(line) for line in lines]
        self._cache: dict = {}

    def _match(self, pred) -> list[int]:
        kind = pred[0]
        if kind == "substring":
            s = pred[1]
            return [i for i, line in enumerate(self.lines) if s in line]
        if kind == "regex":
            rx = re.compile(pred[1])
            return [i for i, line in enumerate(self.lines) if rx.search(line)]
        if kind == "field":
            name, value = pred[1], pred[2]
            return [i for i, f in enumerate(self.fields) if f is not None and f[name] == value]
        if kind == "and":
            rows = set(range(len(self.lines)))
            for p in pred[1:]:
                rows &= set(self._match(p))
            return sorted(rows)
        raise ValueError(f"unknown predicate {pred!r}")

    def answer(self, query: tuple):
        if query not in self._cache:
            self._cache[query] = self._answer(query)
        return self._cache[query]

    def _answer(self, query: tuple):
        op = query[0]
        if op == "search":
            return [(i, self.lines[i]) for i in self._match(query[1])]
        if op == "count":
            return len(self._match(query[1]))
        if op == "top_k":
            field, k = query[1], query[2]
            c = Counter(f[field] for f in self.fields if f is not None)
            return sorted(c.items(), key=lambda kv: (-kv[1], kv[0]))[:k]
        if op == "time_histogram":
            field, bucket = query[1], query[2]
            c: Counter = Counter()
            for f in self.fields:
                if f is not None and (m := _DIGITS.search(f[field])):
                    c[int(m.group()) // bucket] += 1
            return dict(sorted(c.items()))
        raise ValueError(f"unknown query {query!r}")
