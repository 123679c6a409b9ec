"""The program's /metrics text, parsed; and differences between two scrapes."""

from __future__ import annotations

import re

_LINE = re.compile(r"^([A-Za-z_:][A-Za-z0-9_:]*)(?:\{(.*)\})?\s+(\S+)$")
_LABEL = re.compile(r'([A-Za-z_][A-Za-z0-9_]*)="((?:[^"\\]|\\.)*)"')


def parse(text: str) -> dict:
    """{(family, frozenset of (label, value) pairs): number}."""
    out = {}
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        m = _LINE.match(line)
        if m is None:
            raise ValueError(f"not a metrics line: {line!r}")
        name, labels, value = m.groups()
        key = frozenset(_LABEL.findall(labels)) if labels else frozenset()
        out[(name, key)] = float(value)
    return out


def total(scrape: dict, family: str, where: dict | None = None) -> float:
    """The sum of a family's series whose labels include `where`. A label's
    wanted value may be a list: any of them."""
    want = {
        k: set(v) if isinstance(v, (list, tuple)) else {v}
        for k, v in (where or {}).items()
    }
    acc = 0.0
    for (name, labels), value in scrape.items():
        if name != family:
            continue
        have = dict(labels)
        if all(have.get(k) in vs for k, vs in want.items()):
            acc += value
    return acc


def delta(before: dict, after: dict, family: str, where: dict | None = None) -> float:
    return total(after, family, where) - total(before, family, where)
