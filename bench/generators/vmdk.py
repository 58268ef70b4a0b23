"""Backups of one VM disk image, made from a seed in bulk: a vectorised
copy of ``repro.data.workloads.vmdk_versions`` with the same statistics
(``tests/bench/test_bench_gen.py`` holds the two together).

The image is 4 KiB blocks, half of them zero and half random. Each
version rewrites 1% of the blocks, drawn without replacement, with
random bytes. These are the repo's synthetic statistics, not figures of
a published dataset.

``versions`` is an iterator: ``next()`` gives version 0, 1, ...
"""
from __future__ import annotations

from typing import Iterator

import numpy as np


def _rng(seed: int, salt: int) -> np.random.Generator:
    return np.random.Generator(np.random.PCG64([int(seed) & (2**64 - 1),
                                                salt]))


BLOCK = 4096


def versions(seed: int, size: int, zero_share: float = 0.5,
             rewrite_share: float = 0.01) -> Iterator[bytes]:
    """Backups of one VM image of ``size`` bytes (whole 4 KiB blocks)."""
    rng = _rng(seed, 2)
    nblocks = max(2, size // BLOCK)
    img = np.zeros((nblocks, BLOCK), np.uint8)
    data = rng.permutation(nblocks)[:nblocks - int(nblocks * zero_share)]
    img[data] = rng.integers(0, 256, size=(len(data), BLOCK), dtype=np.uint8)
    while True:
        yield img.tobytes()
        touch = rng.permutation(nblocks)[:max(1, int(nblocks * rewrite_share))]
        img[touch] = rng.integers(0, 256, size=(len(touch), BLOCK),
                                  dtype=np.uint8)

