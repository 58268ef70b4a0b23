"""The Linux release-archive generator writes what ``git archive`` writes
and edits the tree at the rate of the repo's ``kernel_versions`` rule;
stdlib ``tarfile`` is the plain reference of the format. And the reader
of the intra-stream score's span, ``intra_ms_per_MiB``."""
import difflib
import functools
import io
import re
import tarfile
import types

import numpy as np
import pytest

from bench import drive
from bench import run as bench_run
from repro.data import workloads

SIZE = 2 << 20
SEED = 2**40 + 11
RELEASES = 6                      # five releases' edits


def gen():
    return drive.plugin("generators", "kernel_tree")


@functools.cache
def releases():
    """(archive, members) of each release of one tree."""
    tree = gen().Tree(SEED, SIZE)
    out = []
    for _ in range(RELEASES):
        out.append((tree.archive(), tree.members()))
        tree.advance()
    return out


def opened(archive: bytes) -> tarfile.TarFile:
    return tarfile.open(fileobj=io.BytesIO(archive), mode="r:")


def bodies(members) -> list[bytes]:
    return [body for _, _, body in members if body is not None]


def line_edits(a: bytes, b: bytes) -> int:
    """Line operations that turn file ``a`` into ``b``: a rewrite counts
    once, as do a delete and an insert."""
    sm = difflib.SequenceMatcher(None, a.splitlines(True),
                                 b.splitlines(True), autojunk=False)
    return sum(max(i2 - i1, j2 - j1)
               for tag, i1, i2, j1, j2 in sm.get_opcodes() if tag != "equal")


def edit_statistics(files_by_release) -> tuple[float, float]:
    """(share of files edited, mean line operations an edited file) over
    consecutive releases of one tree."""
    shares, ops = [], []
    for old, new in zip(files_by_release, files_by_release[1:]):
        changed = [line_edits(a, b) for a, b in zip(old, new) if a != b]
        shares.append(len(changed) / len(old))
        ops += changed
    return float(np.mean(shares)), float(np.mean(ops))


def same_seed_same_bytes():
    it = gen().versions(SEED, SIZE)
    first = [next(it) for _ in range(2)]
    assert first == [a for a, _ in releases()[:2]]
    assert next(gen().versions(SEED + 1, SIZE)) != first[0]


def tarfile_opens_every_version():
    for archive, members in releases():
        assert len(archive) >= SIZE
        assert len(opened(archive).getmembers()) == len(members)


def members_are_the_tree():
    for archive, members in releases():
        tf = opened(archive)
        got = tf.getmembers()
        for m, (name, mode, body) in zip(got, members):
            assert m.name == name.decode().rstrip("/")
            assert m.mode == mode and m.uname == m.gname == "root"
            if body is None:
                assert m.isdir() and mode == 0o775
            else:
                assert m.isfile() and mode == 0o664
                assert m.size == len(body)
                assert tf.extractfile(m).read() == body
        prefix = members[0][0].decode()
        assert re.fullmatch(r"linux-\d+\.\d+/", prefix)
        assert all(m.name.startswith(prefix.rstrip("/")) for m in got)


def mtime_advances_by_the_release_step():
    mtimes = [{m.mtime for m in opened(a).getmembers()} for a, _ in releases()]
    assert all(len(t) == 1 for t in mtimes)
    steps = np.diff([t.pop() for t in mtimes])
    assert (steps == gen().RELEASE_STEP).all()
    prefixes = [members[0][0] for _, members in releases()]
    assert len(set(prefixes)) == RELEASES


def pax_comment_changes():
    comments = [opened(a).pax_headers["comment"] for a, _ in releases()]
    assert all(re.fullmatch(r"[0-9a-f]{40}", c) for c in comments)
    assert len(set(comments)) == RELEASES
    # git archive's pax global header comes first, as its own entry
    assert releases()[0][0][:17] == b"pax_global_header"
    assert releases()[0][0][156:157] == b"g"


def ends_on_a_record():
    for archive, _ in releases():
        assert len(archive) % 10240 == 0
        assert archive[-1024:] == bytes(1024)


def edits_follow_the_kernel_versions_rule():
    ours = [bodies(m) for _, m in releases()]
    assert all(len(f) == len(ours[0]) for f in ours)
    theirs = [re.split(rb"==== file \d+ ====\n", v)[1:]
              for v in workloads.kernel_versions(workloads.WorkloadConfig(
                  base_size=SIZE, versions=RELEASES, seed=5))]
    share, ops = edit_statistics(ours)
    share_theirs, ops_theirs = edit_statistics(theirs)
    # 10% of the files a release, each with 1 to 5 line operations
    # (3 on average; an operation that lands beside another can merge)
    assert share == pytest.approx(0.1, abs=0.01)
    assert share_theirs == pytest.approx(0.1, abs=0.01)
    assert abs(ops - ops_theirs) < 0.6 and 2.5 < ops < 3.5
    lines = [len(b.splitlines()) for b in ours[0]]
    assert 100 <= min(lines) and max(lines) < 500
    assert abs(np.mean([len(b) for b in ours[0]])
               - np.mean([len(b) for b in theirs[0]])) < 800


@pytest.mark.parametrize("prop", [
    same_seed_same_bytes, tarfile_opens_every_version, members_are_the_tree,
    mtime_advances_by_the_release_step, pax_comment_changes,
    ends_on_a_record, edits_follow_the_kernel_versions_rule],
    ids=lambda f: f.__name__)
def test_release_archive(prop):
    prop()


def test_intra_reader_is_ms_per_MiB_and_silent_without_the_field():
    read = bench_run.reader("intra_ms_per_MiB")

    def report(**kw):
        return types.SimpleNamespace(bytes_in=2 << 20, **kw)

    run = drive.Run(cell="kernel_tree.ingest", config={}, traffic={},
                    seed=1, seconds=1.0, trace=True, started=0.0)
    run.commits = [(report(intra_seconds=0.01, index_bases=90,
                           stream_bases=1), 1.0),
                   (report(intra_seconds=0.03, index_bases=80,
                           stream_bases=0), 1.0)]
    assert read(run) == pytest.approx(1000.0 * 0.04 / 4.0)
    assert "170 bases from the index, 1 from the stream" in run.notes[-1]
    run.commits = [(report(), 1.0)]
    assert read(run) is None
    run.commits = []
    assert read(run) is None
