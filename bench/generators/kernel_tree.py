"""Linux release archives, ``linux-<major>.<minor>.tar`` as kernel.org
publishes them, made from a seed in bulk.

The archives are laid out as ``git archive --prefix=linux-<ver>/ <tag>``
writes them (git-archive(1)): a pax global header whose ``comment`` is
the tagged commit's 40-hex id; then a 512-byte ustar header for the
prefix directory, for each directory and for each file, in tree order,
every one with the commit time as its mtime, ``root``/``root`` as owner
and the modes of git's default umask (0664 files, 0775 directories);
file bodies padded to 512 bytes; two zero blocks at the end and the
whole padded to a 10240-byte record.

The tree's contents follow the repo's own ``kernel_versions`` rule
(``repro.data.workloads``), not figures of a real tree: files of 100 to
500 lines, each line 0 to 11 spaces, 8 to 59 lowercase letters and
``();\\n``; each release edits 10% of the files, each with 1 to 5 line
deletes, inserts or rewrites, which shift the bytes that follow them.
Directories hold ``FILES_PER_DIR`` files. A release advances every
member's mtime by ``RELEASE_STEP`` and the name prefix by one minor
version, so every header in the archive changes, and gets a new commit
id.

``versions`` is an iterator: ``next()`` gives release 0, 1, ... Only the
current tree is kept. ``Tree`` is the same tree with its members
exposed, for the tests.
"""
from __future__ import annotations

from typing import Iterator

import numpy as np

BLOCK = 512
RECORD = 10240                      # git archive's record size
FILES_PER_DIR = 32
RELEASE_STEP = 70 * 86400           # about ten weeks between releases
FIRST_MTIME = 1551657600            # 2019-03-03, the day 5.0 was tagged
FIRST_VERSION = (5, 0)
MINORS = 20                         # 5.0 ... 5.19, then 6.0
FILE_MODE, DIR_MODE, PAX_MODE = 0o664, 0o775, 0o666

_WIDTH = 11 + 59 + 4                # the longest line
_TAIL = np.frombuffer(b"();\n", np.uint8)
_ZERO = bytes(2 * RECORD)
_LINE_BATCH = 1 << 18


def _rng(seed: int, salt: int) -> np.random.Generator:
    return np.random.Generator(np.random.PCG64([int(seed) & (2**64 - 1),
                                                salt]))


def _lines(rng: np.random.Generator, n: int) -> tuple[np.ndarray, np.ndarray]:
    """``n`` lines of the rule as one flat byte array, and their lengths."""
    indent = rng.integers(0, 12, size=n)
    body = rng.integers(8, 60, size=n)
    ends = indent + body
    col = np.arange(_WIDTH)
    out = []
    for lo in range(0, n, _LINE_BATCH):
        hi = min(n, lo + _LINE_BATCH)
        mat = rng.integers(97, 123, size=(hi - lo, _WIDTH), dtype=np.uint8)
        mat[col < indent[lo:hi, None]] = 32
        rows = np.arange(hi - lo)
        for k, c in enumerate(_TAIL):
            mat[rows, ends[lo:hi] + k] = c
        out.append(mat[col < ends[lo:hi, None] + len(_TAIL)])
    return np.concatenate(out) if out else np.empty(0, np.uint8), ends + 4


def _octal(values: np.ndarray, width: int) -> np.ndarray:
    """[n, width] bytes: ``width - 1`` zero-padded octal digits and a NUL,
    as git's ``"%0*o"`` into a ``width``-byte field."""
    shifts = 3 * np.arange(width - 2, -1, -1, dtype=np.uint64)
    digits = (np.asarray(values, np.uint64)[:, None] >> shifts) & np.uint64(7)
    out = np.zeros((len(values), width), np.uint8)
    out[:, :-1] = digits.astype(np.uint8) + 48
    return out


def _field(text: bytes, n: int, width: int) -> np.ndarray:
    return np.broadcast_to(np.frombuffer(text.ljust(width, b"\0"), np.uint8),
                           (n, width))


def _headers(names: list[bytes], modes: np.ndarray, sizes: np.ndarray,
            mtime: int, types: bytes) -> np.ndarray:
    """[n, 512] ustar headers as git archive's ``prepare_header`` fills
    them: octal fields, owner root, ``ustar\\0`` ``00``, and the checksum
    over the header with its own field read as spaces."""
    n = len(names)
    h = np.zeros((n, BLOCK), np.uint8)
    h[:, 0:100] = np.frombuffer(b"".join(x.ljust(100, b"\0") for x in names),
                                np.uint8).reshape(n, 100)
    h[:, 100:108] = _octal(modes, 8)
    h[:, 108:116] = _octal(np.zeros(n), 8)
    h[:, 116:124] = _octal(np.zeros(n), 8)
    h[:, 124:136] = _octal(sizes, 12)
    h[:, 136:148] = _octal(np.full(n, mtime), 12)
    h[:, 148:156] = 32
    h[:, 156] = np.frombuffer(types, np.uint8)
    h[:, 257:265] = _field(b"ustar\x0000", n, 8)
    h[:, 265:297] = _field(b"root", n, 32)
    h[:, 297:329] = _field(b"root", n, 32)
    h[:, 329:337] = _octal(np.zeros(n), 8)
    h[:, 337:345] = _octal(np.zeros(n), 8)
    h[:, 148:156] = _octal(h.sum(axis=1, dtype=np.int64), 8)
    return h


class Tree:
    """The source tree of one release and its archive."""

    def __init__(self, seed: int, size: int, edit_share: float = 0.1):
        self.rng = _rng(seed, 3)
        self.edit_share = edit_share
        self.release = 0
        # files are drawn until the archive reaches ``size``: a file takes
        # 43 B a line on average, 300 lines, plus a header and padding
        guess = int(size / (43 * 300 + BLOCK * 1.5) * 1.1) + 16
        nlines = self.rng.integers(100, 500, size=guess)
        flat, lengths = _lines(self.rng, int(nlines.sum()))
        ends = np.cumsum(lengths)[np.cumsum(nlines) - 1]
        starts = np.concatenate([[0], ends[:-1]])
        entry = BLOCK + -(-(ends - starts) // BLOCK) * BLOCK
        n = min(guess, int(np.searchsorted(np.cumsum(entry), size)) + 1)
        self.bodies = [flat[a:b].tobytes() for a, b in zip(starts[:n],
                                                            ends[:n])]
        self.commit = self._commit_id()

    def _commit_id(self) -> bytes:
        return b"".join(b"%x" % d for d in self.rng.integers(0, 16, size=40))

    @property
    def prefix(self) -> bytes:
        major, minor = divmod(FIRST_VERSION[0] * MINORS + FIRST_VERSION[1]
                              + self.release, MINORS)
        return b"linux-%d.%d/" % (major, minor)

    @property
    def mtime(self) -> int:
        return FIRST_MTIME + self.release * RELEASE_STEP

    def members(self) -> list[tuple[bytes, int, bytes | None]]:
        """(name, mode, body; None for a directory) in archive order,
        after the pax global header."""
        p = self.prefix
        out = [(p, DIR_MODE, None)]
        for d in range(0, len(self.bodies), FILES_PER_DIR):
            sub = p + b"d%04d/" % (d // FILES_PER_DIR)
            out.append((sub, DIR_MODE, None))
            out += [(sub + b"f%05d.c" % i, FILE_MODE, self.bodies[i])
                    for i in range(d, min(d + FILES_PER_DIR,
                                          len(self.bodies)))]
        return out

    def archive(self) -> bytes:
        pax = b"52 comment=" + self.commit + b"\n"
        members = self.members()
        names = [b"pax_global_header"] + [m[0] for m in members]
        sizes = np.array([len(pax)] + [len(b) if b is not None else 0
                                       for _, _, b in members])
        modes = np.array([PAX_MODE] + [m[1] for m in members])
        types = b"g" + b"".join(b"0" if b is not None else b"5"
                                for _, _, b in members)
        hdr = memoryview(_headers(names, modes, sizes, self.mtime,
                                  types).tobytes())
        parts = [hdr[:BLOCK], pax, _ZERO[:-len(pax) % BLOCK]]
        for e, (_, _, body) in enumerate(members, 1):
            parts.append(hdr[e * BLOCK:(e + 1) * BLOCK])
            if body is not None:
                parts += [body, _ZERO[:-len(body) % BLOCK]]
        used = sum(len(x) for x in parts) + 2 * BLOCK
        parts.append(_ZERO[:2 * BLOCK + -used % RECORD])
        return b"".join(parts)

    def advance(self) -> None:
        """The next release: edit ``edit_share`` of the files with 1 to 5
        line operations each, a new commit id, mtime and prefix."""
        rng, n = self.rng, len(self.bodies)
        edit = rng.permutation(n)[:max(1, int(n * self.edit_share))]
        ops = rng.integers(1, 6, size=len(edit))
        flat, lengths = _lines(rng, int(ops.sum()))
        bounds = np.concatenate([[0], np.cumsum(lengths)])
        fresh = (flat[a:b].tobytes() for a, b in zip(bounds[:-1], bounds[1:]))
        for idx, nops in zip(edit, ops):
            f = self.bodies[idx].splitlines(keepends=True)
            for _ in range(nops):
                op = rng.integers(0, 3)
                pos = int(rng.integers(0, max(1, len(f))))
                line = next(fresh)
                if op == 0 and f:             # delete
                    del f[pos % len(f)]
                elif op == 1:                 # insert
                    f.insert(pos, line)
                elif f:                       # rewrite
                    f[pos % len(f)] = line
            self.bodies[idx] = b"".join(f)
        self.release += 1
        self.commit = self._commit_id()


def versions(seed: int, size: int, edit_share: float = 0.1) -> Iterator[bytes]:
    """Release archives of one tree that starts at about ``size`` bytes."""
    tree = Tree(seed, size, edit_share)
    while True:
        yield tree.archive()
        tree.advance()
