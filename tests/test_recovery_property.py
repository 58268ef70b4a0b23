"""Torn-tail recovery under arbitrary *joint* truncation (DESIGN.md
§10.6 / ROADMAP hardening item).

Commits are buffered, not fsync'd, so after a crash the OS may have
persisted any prefix of chunks.log and any *independent* prefix of
recipes.jsonl — including a recipe line whose chunks never reached the
log. The property: for every joint truncation point, reopen succeeds,
every stream still reported live restores byte-identically, streams
whose data was torn are retired (never served short/corrupt), and the
directory accepts and persists fresh appends.

The property runs as a deterministic seeded sweep (always, boundary
cuts included) and additionally under hypothesis when installed
(requirements-dev.txt), matching the repo's guarded-hypothesis idiom."""
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                     # dev-only dep; sweep still runs
    HAVE_HYPOTHESIS = False

from repro import api
from repro.core import delta


def _reference_container(tmp):
    """Small container with raw chunks, cross-stream delta chains, a
    retire tombstone, and per-recipe lengths; returns the two files'
    bytes plus {handle: stream bytes}."""
    rng = np.random.default_rng(0)
    backend = api.FileBackend(tmp)
    expected = {}
    prev = None
    cid = 0
    for _s in range(3):
        ids, lens, datas = [], [], []
        for j in range(4):
            if prev is not None and j < len(prev[0]) and rng.random() < 0.6:
                mix = bytearray(prev[1][j])
                mix[10:20] = rng.integers(0, 256, 10, np.uint8).tobytes()
                data = bytes(mix)
                backend.put_delta(cid, prev[0][j],
                                  delta.encode(data, prev[1][j]), data=data)
            else:
                data = rng.integers(0, 256, int(rng.integers(80, 400)),
                                    np.uint8).tobytes()
                backend.put_raw(cid, data)
            ids.append(cid)
            lens.append(len(data))
            datas.append(data)
            cid += 1
        expected[backend.add_recipe(ids, lens)] = b"".join(datas)
        prev = (ids, datas)
    backend.retire_recipe(1)            # a tombstone line in the journal
    backend.flush()
    backend.close()
    log = (tmp / "chunks.log").read_bytes()
    recipes = (tmp / "recipes.jsonl").read_bytes()
    return log, recipes, expected


@pytest.fixture(scope="module")
def reference(tmp_path_factory):
    log, recipes, expected = _reference_container(
        tmp_path_factory.mktemp("ref"))
    return {"log": log, "recipes": recipes, "expected": expected}


def _check_joint_cut(reference, tmp, cut_log: int, cut_rec: int) -> None:
    """The recovery property for one joint truncation point."""
    log, recipes, expected = (reference["log"], reference["recipes"],
                              reference["expected"])
    tmp.mkdir(parents=True, exist_ok=True)
    (tmp / "chunks.log").write_bytes(log[:cut_log])
    (tmp / "recipes.jsonl").write_bytes(recipes[:cut_rec])

    backend = api.FileBackend(tmp)      # must never raise
    live = backend.live_handles()
    # slots that exist but are not live were retired — by the original
    # tombstone or by torn-tail recovery; they must STAY retired below
    retired = [h for h in range(backend.num_streams()) if h not in live]
    for h in live:
        recipe = backend.recipe(h)
        # hardening invariant: a live recipe's chunks (and their whole
        # base chains) survived the log truncation
        for c in recipe:
            cur = c
            while cur >= 0:
                assert backend.contains(cur)
                cur = backend.base_of(cur)
        # and it serves the exact original bytes
        assert b"".join(backend.get_many(recipe)) == expected[h]
        lens = backend.recipe_lengths(h)
        if lens is not None:
            assert sum(lens) == len(expected[h])
    # a store opens on the recovered directory (refcount rebuild included)
    store = api.DedupStore(
        api.build_detector(api.DedupConfig.from_dict(
            {"detector": "dedup-only"})), backend=backend)
    for h in live:
        assert store.restore(h) == expected[h]
    # the recovered tail is a clean append boundary: new data commits,
    # survives a reopen, and never collides with surviving chunk ids
    fresh = b"fresh-after-recovery" * 4
    nh = store.ingest(fresh) and store.reports[-1].handle
    assert store.restore(nh) == fresh
    store.close()
    again = api.FileBackend(tmp)
    assert b"".join(again.get_many(again.recipe(nh))) == fresh
    # live handles stay live (the post-recovery ingest must not have
    # reused their cids or otherwise disturbed them) and serve the
    # original bytes
    for h in live:
        assert b"".join(again.get_many(again.recipe(h))) == expected[h]
    # retired handles stay retired: without a persisted retire tombstone
    # (and torn cids kept out of reissue), a recovery-retired recipe
    # whose cids were reused by the fresh ingest would resurrect here —
    # live again, serving the fresh stream's bytes under an old handle
    for h in retired:
        with pytest.raises(KeyError):
            again.recipe(h)
    again.close()


def test_joint_truncation_seeded_sweep(reference, tmp_path):
    log, recipes = reference["log"], reference["recipes"]
    rng = np.random.default_rng(42)
    cuts = {(len(log), len(recipes)), (0, 0),
            (len(log), 0), (0, len(recipes))}
    # boundary-biased pairs: record/line edges are where off-by-ones live
    edges_l = [0, 1, 11, 12, 13, 37, len(log) - 1, len(log)]
    edges_r = [0, 1, len(recipes) - 1, len(recipes)]
    for el in edges_l:
        for er in edges_r:
            cuts.add((min(max(el, 0), len(log)),
                      min(max(er, 0), len(recipes))))
    while len(cuts) < 70:
        cuts.add((int(rng.integers(0, len(log) + 1)),
                  int(rng.integers(0, len(recipes) + 1))))
    for i, (cl, cr) in enumerate(sorted(cuts)):
        _check_joint_cut(reference, tmp_path / f"cut{i}", cl, cr)


if HAVE_HYPOTHESIS:
    @given(data=st.data())
    @settings(max_examples=40, deadline=None)
    def test_joint_truncation_property(reference, tmp_path_factory, data):
        cut_log = data.draw(
            st.integers(0, len(reference["log"])), label="cut_log")
        cut_rec = data.draw(
            st.integers(0, len(reference["recipes"])), label="cut_recipes")
        _check_joint_cut(reference, tmp_path_factory.mktemp("hyp"),
                         cut_log, cut_rec)


def test_recipe_surviving_torn_chunks_is_retired(tmp_path):
    """Directed version of the hardening: recipes.jsonl fully intact,
    chunks.log torn before the last stream's records — that stream's
    recipe must be retired on reopen, earlier streams must still serve."""
    log, recipes, expected = _reference_container(tmp_path / "ref")
    tmp = tmp_path / "cut"
    tmp.mkdir()
    # keep exactly stream 0's records (cids 0..3) by scanning a fresh
    # backend for their end offsets
    probe = api.FileBackend(tmp_path / "ref")
    ends = {cid: probe._index[cid][2] + probe._index[cid][3]
            for cid in probe.chunk_ids()}
    probe.close()
    keep_through = max(ends[c] for c in range(4))
    (tmp / "chunks.log").write_bytes(log[:keep_through])
    (tmp / "recipes.jsonl").write_bytes(recipes)
    backend = api.FileBackend(tmp)
    assert backend.live_handles() == [0]    # 1 was deleted, 2 torn away
    assert b"".join(backend.get_many(backend.recipe(0))) == expected[0]
    with pytest.raises(KeyError):
        backend.recipe(2)
    backend.close()


def test_joint_truncation_on_clean_boundaries_keeps_everything(tmp_path):
    log, recipes, expected = _reference_container(tmp_path / "ref")
    tmp = tmp_path / "cut"
    tmp.mkdir()
    (tmp / "chunks.log").write_bytes(log)
    (tmp / "recipes.jsonl").write_bytes(recipes)
    backend = api.FileBackend(tmp)
    assert sorted(backend.live_handles()) == [0, 2]     # 1 was retired
    for h in backend.live_handles():
        assert b"".join(backend.get_many(backend.recipe(h))) == expected[h]
    backend.close()
