"""Nightly dumps of one table, made from a seed in bulk: a vectorised
copy of ``repro.data.workloads.sql_dump_versions`` with the same
statistics. Its random numbers are drawn in another order, so its bytes
differ from the original's for the same seed;
``tests/bench/test_bench_gen.py`` holds the two to the same record shape,
sizes and edit and append fractions.

Rows are ``INSERT INTO t VALUES (<id>,'<12 letters>','<96 printable>');``
lines of about 148 bytes. Each version edits 0.5% of the rows in place
(rows drawn with replacement) and appends 2% new rows. These are the
repo's synthetic statistics, not figures of a published dataset.

``versions`` is an iterator: ``next()`` gives version 0, 1, ... Nothing
is kept but the current state.
"""
from __future__ import annotations

from typing import Iterator

import numpy as np

_PREFIX = np.frombuffer(b"INSERT INTO t VALUES (", np.uint8)
_RID_DIGITS = 9          # ids are drawn below 10**9
_NAME, _BLOB = 12, 96
_POW10 = 10 ** np.arange(_RID_DIGITS - 1, -1, -1, dtype=np.int64)


def _rng(seed: int, salt: int) -> np.random.Generator:
    return np.random.Generator(np.random.PCG64([int(seed) & (2**64 - 1),
                                                salt]))


class _Rows:
    """Rows as columns: id, name, blob."""

    def __init__(self, rng: np.random.Generator, n: int) -> None:
        self.rid = rng.integers(0, 10**9, size=n)
        self.name = rng.integers(97, 123, size=(n, _NAME), dtype=np.uint8)
        self.blob = rng.integers(32, 127, size=(n, _BLOB), dtype=np.uint8)

    def __len__(self) -> int:
        return len(self.rid)

    def replace(self, idx: np.ndarray, new: "_Rows") -> None:
        self.rid[idx] = new.rid
        self.name[idx] = new.name
        self.blob[idx] = new.blob

    def extend(self, new: "_Rows") -> None:
        self.rid = np.concatenate([self.rid, new.rid])
        self.name = np.concatenate([self.name, new.name])
        self.blob = np.concatenate([self.blob, new.blob])

    def head(self, n: int) -> "_Rows":
        out = object.__new__(_Rows)
        out.rid, out.name, out.blob = (self.rid[:n].copy(),
                                       self.name[:n].copy(),
                                       self.blob[:n].copy())
        return out

    def _ndigits(self) -> np.ndarray:
        # "%d" of the id prints no leading zeros
        return np.searchsorted(_POW10[::-1], self.rid, side="right").clip(1)

    def lengths(self) -> np.ndarray:
        return _FIXED + self._ndigits()

    def render(self) -> bytes:
        n = len(self)
        digits = (self.rid[:, None] // _POW10) % 10
        ndig = self._ndigits()
        cols = [np.broadcast_to(_PREFIX, (n, len(_PREFIX))),
                (digits + 48).astype(np.uint8),
                np.broadcast_to(np.frombuffer(b",'", np.uint8), (n, 2)),
                self.name,
                np.broadcast_to(np.frombuffer(b"','", np.uint8), (n, 3)),
                self.blob,
                np.broadcast_to(np.frombuffer(b"');\n", np.uint8), (n, 4))]
        mat = np.concatenate(cols, axis=1)
        keep = np.ones(mat.shape, bool)
        pos = np.arange(_RID_DIGITS)[None, :]
        keep[:, len(_PREFIX):len(_PREFIX) + _RID_DIGITS] = (
            pos >= _RID_DIGITS - ndig[:, None])
        return mat[keep].tobytes()


_FIXED = len(_PREFIX) + 2 + _NAME + 3 + _BLOB + 4
ROW_BYTES_MEAN = _FIXED + 8.9


def versions(seed: int, size: int, edit_share: float = 0.005,
             append_share: float = 0.02) -> Iterator[bytes]:
    """Nightly dumps of one table that starts at about ``size`` bytes."""
    rng = _rng(seed, 1)
    # rows are drawn until the dump reaches ``size``, as the original
    # generator does, so version 0 is never under it
    rows = _Rows(rng, int(size / ROW_BYTES_MEAN * 1.01) + 16)
    n = int(np.searchsorted(np.cumsum(rows.lengths()), size)) + 1
    rows = rows.head(min(n, len(rows)))
    while True:
        yield rows.render()
        n = len(rows)
        edit = rng.integers(0, n, size=max(1, int(n * edit_share)))
        rows.replace(edit, _Rows(rng, len(edit)))
        rows.extend(_Rows(rng, max(1, int(n * append_share))))
