"""The benchmark's vectorised generators keep the statistics of the
repo's own (``repro.data.workloads``): record shape, sizes, and the
edit, append and rewrite fractions; and they are deterministic in the
seed."""
import os

import numpy as np
import pytest

from bench import drive
from repro.data import workloads

SIZE = 1 << 20
GENERATORS = sorted(f[:-3] for f in os.listdir(os.path.join(drive.BENCH,
                                                            "generators"))
                    if f.endswith(".py"))


def gen(name):
    return drive.plugin("generators", name)


def take(it, n):
    return [next(it) for _ in range(n)]


@pytest.mark.parametrize("name", GENERATORS)
def test_same_seed_same_bytes(name):
    big = 2**40 + 3      # seeds past 32 bits are taken whole
    a = take(gen(name).versions(big, SIZE), 3)
    b = take(gen(name).versions(big, SIZE), 3)
    c = take(gen(name).versions(big + 1, SIZE), 3)
    assert a == b
    assert a[0] != c[0]


def rows(v: bytes) -> list[bytes]:
    return v.split(b"\n")[:-1]


def test_sql_dump_matches_the_repo_generator():
    ours = take(gen("sql_dump").versions(5, SIZE), 3)
    theirs = workloads.sql_dump_versions(
        workloads.WorkloadConfig(base_size=SIZE, versions=3, seed=5))
    for a, b in zip(ours, theirs):
        assert abs(len(a) - len(b)) / len(b) < 0.01
    assert len(ours[0]) >= SIZE
    r0, r1 = rows(ours[0]), rows(ours[1])
    rt0, rt1 = rows(theirs[0]), rows(theirs[1])
    # record shape: same prefix, lengths in the same range
    for r in r0[:50] + rt0[:50]:
        assert r.startswith(b"INSERT INTO t VALUES (") and r.endswith(b"');")
    assert {len(r) for r in r0} <= set(range(139, 149))
    assert abs(np.mean([len(r) for r in r0])
               - np.mean([len(r) for r in rt0])) < 0.5
    # appends: 2% of the rows; edits: 0.5% of the rows, in place
    n = len(r0)
    assert len(r1) - n == n // 50 == len(rt1) - len(rt0)
    edited = sum(a != b for a, b in zip(r0, r1[:n]))
    edited_theirs = sum(a != b for a, b in zip(rt0, rt1[:len(rt0)]))
    assert 0.8 * (n // 200) <= edited <= n // 200
    assert abs(edited - edited_theirs) <= 0.1 * (n // 200) + 2


def blocks(v: bytes) -> np.ndarray:
    return np.frombuffer(v, np.uint8).reshape(-1, gen("vmdk").BLOCK)


def test_vmdk_matches_the_repo_generator():
    ours = take(gen("vmdk").versions(5, SIZE), 3)
    theirs = workloads.vmdk_versions(
        workloads.WorkloadConfig(base_size=SIZE, versions=3, seed=5))
    assert [len(v) for v in ours] == [len(v) for v in theirs] == [SIZE] * 3
    for vs in (ours, theirs):
        b0, b1 = blocks(vs[0]), blocks(vs[1])
        nblocks = len(b0)
        assert (~b0.any(1)).sum() == nblocks // 2        # half zero
        changed = (b0 != b1).any(1).sum()
        assert nblocks // 100 - 1 <= changed <= nblocks // 100
