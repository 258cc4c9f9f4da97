"""A configuration that jit can take as a static argument."""
from __future__ import annotations


class Frozen(dict):
    """A read-only dict of plain values (lists become tuples), hashable."""

    def __init__(self, d: dict):
        def fix(v):
            if isinstance(v, (list, tuple)):
                return tuple(fix(x) for x in v)
            if isinstance(v, dict):
                return Frozen(v)
            return v
        super().__init__({k: fix(v) for k, v in d.items()})
        self._key = tuple(sorted(self.items()))

    def __hash__(self):
        return hash(self._key)

    def __eq__(self, other):
        return isinstance(other, Frozen) and self._key == other._key
