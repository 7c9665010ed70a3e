"""The plain reference: NumPy answers over the generated arrays.

Imports nothing of the program and takes nothing the program has made. A
person is known to it by the position of its row; ``src``/``dst`` (person
ids) are turned into positions on first use (a sort of the ids and a search
for every edge), so making the reference before the window costs nothing
and the work is paid after it, outside ``setup_s``.

The controls (``--control NAME``) are the same reference, weakened and put
in the program's place; the comparison has to call each not correct:

``int32``
    the precision below the one the configuration states (64-bit Cypher
    integers): every integer of an answer kept in 32 bits, wrapping as a
    32-bit lane does;
``stale_snapshot``
    a snapshot that lacks the last ``1/lost_tail`` of the loaded KNOWS rows:
    breaks "every loaded edge is visible to every read".
"""

from __future__ import annotations

import numpy as np

# --control NAME: the arguments that make the reference that control
CONTROLS = {"int32": {"bits": 32}, "stale_snapshot": {"lost_tail": 64}}


class Reference:
    def __init__(self, arrays, lost_tail: int = 0, bits: int = 64):
        self.arrays = arrays
        self.ids = arrays["ids"]
        self.n = len(self.ids)
        src, dst = arrays["src"], arrays["dst"]
        if lost_tail:
            keep = len(src) - len(src) // lost_tail
            src, dst = src[:keep], dst[:keep]
        self._src, self._dst = src, dst
        self._bits = bits
        self._rows = None

    def column(self, name: str) -> np.ndarray:
        """A Person column, one value per row."""
        return self.arrays[name]

    def _positions(self):
        if self._rows is None:
            order = np.argsort(self.ids)
            by_id = self.ids[order]
            self._rows = tuple(
                order[np.searchsorted(by_id, x)] for x in (self._src, self._dst)
            )
        return self._rows

    @property
    def s(self) -> np.ndarray:
        """The row of each KNOWS row's source person."""
        return self._positions()[0]

    @property
    def d(self) -> np.ndarray:
        return self._positions()[1]

    @property
    def e(self) -> int:
        return len(self._src)

    @property
    def outdeg(self) -> np.ndarray:
        return np.bincount(self.s, minlength=self.n).astype(np.int64)

    def held(self, rows):
        """An answer as this reference's precision holds it: unchanged in
        64 bits, every integer wrapped into ``bits`` below that."""
        if self._bits >= 64:
            return rows
        half = 1 << (self._bits - 1)

        def wrap(v):
            if isinstance(v, int) and not isinstance(v, bool):
                return (v + half) % (2 * half) - half
            return v

        return [{k: wrap(v) for k, v in row.items()} for row in rows]
