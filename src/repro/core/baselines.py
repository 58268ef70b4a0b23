"""The paper's comparison targets: N-transform and Finesse resemblance
detection (super-feature schemes), over windowed Rabin-style
fingerprints (``hashing.rabin_fps_np``, the same linear-window
formulation as CARD's gear scan — DESIGN.md §3).

Both schemes map a chunk to `sf_count` super-features; two chunks are
treated as similar if ANY super-feature matches, and the first match wins
("FirstFit", as in Finesse/FAST'19 and paper §3).
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.core import hashing

_FNV64_OFFSET = 0xCBF29CE484222325
_FNV64_PRIME = 0x100000001B3


def _fnv64(values: np.ndarray) -> int:
    h = _FNV64_OFFSET
    for v in np.asarray(values, dtype=np.uint64):
        h ^= int(v)
        h = (h * _FNV64_PRIME) & 0xFFFFFFFFFFFFFFFF
    return h


@dataclasses.dataclass(frozen=True)
class SuperFeatureConfig:
    features_per_sf: int = 4
    sf_count: int = 3
    window: int = hashing.RABIN_WINDOW

    @property
    def total_features(self) -> int:
        return self.features_per_sf * self.sf_count


class NTransform:
    """Shilane et al.: N linear transforms of all window fingerprints.

    feature_i = max_pos ((m_i * fp_pos + a_i) mod 2^32); super-feature j =
    hash of its group of `features_per_sf` consecutive features.
    """

    def __init__(self, cfg: SuperFeatureConfig | None = None, seed: int = 7):
        self.cfg = cfg or SuperFeatureConfig()
        rng = np.random.Generator(np.random.PCG64(seed))
        n = self.cfg.total_features
        self._m = (rng.integers(1, 2**32, n, dtype=np.uint64) | np.uint64(1))
        self._a = rng.integers(0, 2**32, n, dtype=np.uint64)

    def super_features(self, data: bytes) -> tuple[int, ...]:
        buf = np.frombuffer(data, dtype=np.uint8)
        fps = hashing.rabin_fps_np(buf, self.cfg.window).astype(np.uint64)  # [L]
        # N linear transforms, max over positions: [N]
        t = (fps[None, :] * self._m[:, None] + self._a[:, None]) & np.uint64(0xFFFFFFFF)
        feats = t.max(axis=1)
        g = self.cfg.features_per_sf
        return tuple(_fnv64(feats[j * g:(j + 1) * g])
                     for j in range(self.cfg.sf_count))


class Finesse:
    """Zhang et al. FAST'19: fine-grained feature locality.

    Split the chunk into `total_features` sub-chunks; feature of each =
    max window fingerprint inside it. Group consecutive sub-chunk features
    into `features_per_sf`-sized groups, sort within each group, and build
    SF_j from the j-th ranked value of every group (rank-based grouping,
    paper Fig. 2).
    """

    def __init__(self, cfg: SuperFeatureConfig | None = None):
        self.cfg = cfg or SuperFeatureConfig()

    def super_features(self, data: bytes) -> tuple[int, ...]:
        buf = np.frombuffer(data, dtype=np.uint8)
        n = len(buf)
        fps = hashing.rabin_fps_np(buf, self.cfg.window).astype(np.uint64)
        t = self.cfg.total_features
        bounds = np.linspace(0, n, t + 1).astype(np.int64)
        feats = np.zeros(t, dtype=np.uint64)
        for i in range(t):
            lo, hi = bounds[i], bounds[i + 1]
            feats[i] = fps[lo:hi].max() if hi > lo else 0
        # rank-based grouping: groups of size features_per_sf along the chunk;
        # SF_j collects the j-th smallest of each group.
        g = self.cfg.features_per_sf
        ngroups = self.cfg.sf_count
        grouped = feats[: g * ngroups].reshape(ngroups, g)
        ranked = np.sort(grouped, axis=1)          # [ngroups, g]
        return tuple(_fnv64(ranked[:, j]) for j in range(g))[: self.cfg.sf_count]


class SuperFeatureIndex:
    """FirstFit store: any-SF-match -> similar; first match is the base.

    `query`/`stage` accept an *overlay* (same table-list shape, holding
    staged-but-not-admitted entries) so a batch can be scored as if its
    earlier chunks were already inserted — without mutating the index.
    Persistent tables win over the overlay, matching insert's
    first-writer-wins `setdefault`. The FirstFit ordering lives only
    here; callers never touch the tables directly.
    """

    def __init__(self):
        self._tables: list[dict[int, int]] = []

    def query(self, sfs: tuple[int, ...],
              overlay: list[dict[int, int]] | None = None) -> int | None:
        for j, sf in enumerate(sfs):
            hit = self._tables[j].get(sf) if j < len(self._tables) else None
            if hit is None and overlay is not None and j < len(overlay):
                hit = overlay[j].get(sf)
            if hit is not None:
                return hit
        return None

    def stage(self, sfs: tuple[int, ...], chunk_id: int,
              overlay: list[dict[int, int]]) -> None:
        """Record an insert in `overlay` only (the index is untouched),
        preserving first-writer-wins across persistent + staged entries."""
        while len(overlay) < len(sfs):
            overlay.append({})
        for j, sf in enumerate(sfs):
            if j >= len(self._tables) or sf not in self._tables[j]:
                overlay[j].setdefault(sf, chunk_id)

    def insert(self, sfs: tuple[int, ...], chunk_id: int) -> None:
        while len(self._tables) < len(sfs):
            self._tables.append({})
        for j, sf in enumerate(sfs):
            self._tables[j].setdefault(sf, chunk_id)
