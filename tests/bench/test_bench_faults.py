"""The comparison that decides ``correct`` sees each fault the cells can
have, planted in the timed path below an otherwise whole run: an answer
altered where it is produced, a commit whose records are lost, chunk
boundaries, initial features, top-1 answers and base choices gone
wrong. And
its control: the reference computed in bfloat16, or serving a stale
version, in the program's place, fails the limits."""
import numpy as np
import pytest

from bench import control


def flip(data: bytes) -> bytes:
    if not data:
        return data
    b = bytearray(data)
    b[len(b) // 2] ^= 1
    return bytes(b)


def test_altered_delta_fails_ingest(tiny, monkeypatch):
    from repro.core import delta
    encode = delta.encode
    monkeypatch.setattr(delta, "encode",
                        lambda target, base: encode(flip(target), base))
    result, _ = tiny("sql_backup.ingest")
    assert result["correct"] is False
    assert result["checks"]["versions_restored_wrong"]["value"] > 0


def test_lost_records_fail_ingest(tiny, monkeypatch):
    from repro.api import containers
    put_many = containers.FileBackend.put_many
    monkeypatch.setattr(containers.FileBackend, "put_many",
                        lambda self, records: put_many(self, records[:-1]))
    result, _ = tiny("vmdk_backup.ingest")
    assert result["correct"] is False
    assert result["checks"]["versions_restored_wrong"]["value"] > 0


def test_shifted_boundaries_fail_ingest(tiny, monkeypatch):
    from repro.kernels import ingest
    scan = ingest.scan_stream

    def shifted(data, mask_s, mask_l):
        s, cs, cl = scan(data, mask_s, mask_l)
        return s, np.roll(cs, 1), np.roll(cl, 1)
    monkeypatch.setattr(ingest, "scan_stream", shifted)
    result, _ = tiny("sql_backup.ingest")
    assert result["correct"] is False
    assert result["checks"]["versions_chunked_wrong"]["value"] > 0


def test_altered_features_fail_ingest(tiny, monkeypatch):
    from repro.core import features
    call = features.FeatureExtractor.__call__
    monkeypatch.setattr(features.FeatureExtractor, "__call__",
                        lambda self, *a, **kw: call(self, *a, **kw) + 1e-3)
    result, _ = tiny("sql_backup.ingest")
    assert result["correct"] is False
    c = result["checks"]["feature_gap"]
    assert c["value"] > c["limit"]


def test_wrong_top1_fails_ingest(tiny, monkeypatch):
    from repro.kernels import ops
    sim_topk = ops.sim_topk

    def wrong(q, index):
        score, arg = sim_topk(q, index)
        return score, (arg + 1) % index.shape[0]
    monkeypatch.setattr(ops, "sim_topk", wrong)
    result, run = tiny("sql_backup.ingest", size_mib=1.5, kernel=True)
    assert run.topk_shapes, "the kernel path ran"
    assert result["correct"] is False
    c = result["checks"]["top1_gap"]
    assert c["value"] > c["limit"]


def test_dropped_bases_fail_ingest(tiny, monkeypatch):
    from repro.core import pipeline
    score = pipeline.CARDDetector.score

    def dropped(self, feats, batch):
        r = score(self, feats, batch)
        r.base_ids[::2] = -1        # half the chunks stored without a base
        return r
    monkeypatch.setattr(pipeline.CARDDetector, "score", dropped)
    result, _ = tiny("sql_backup.ingest")
    assert result["correct"] is False
    assert result["checks"]["versions_restored_wrong"]["value"] == 0
    c = result["checks"]["base_gap"]
    assert c["value"] > c["limit"]


@pytest.mark.parametrize("cell,method", [
    ("sql_backup.restore_range", "restore_range"),
    ("vmdk_backup.restore_full", "restore")])
def test_altered_answer_fails_restore(tiny, monkeypatch, cell, method):
    from repro.api import store
    serve = getattr(store.DedupStore, method)
    monkeypatch.setattr(store.DedupStore, method,
                        lambda self, *a: flip(serve(self, *a)))
    result, _ = tiny(cell)
    assert result["correct"] is False
    assert result["checks"]["answers_wrong"]["value"] > 0


@pytest.mark.parametrize("cell", ["sql_backup.ingest",
                                  "sql_backup.restore_range"])
def test_control_fails_a_limit(tiny, cell):
    result, run = tiny(cell, size_mib=1.5, kernel=cell.endswith("ingest"))
    assert result["correct"] is True
    readings = control.control_readings(run)
    limits = {k: c["limit"] for k, c in result["checks"].items()}
    assert any(v > limits[k] for k, v in readings.items()), readings


def test_base_control_fails_its_limit(tiny):
    result, run = tiny("sql_backup.ingest", size_mib=1.5, kernel=True)
    assert result["correct"] is True
    reading = control.control_readings(run, ["base_gap"])
    assert set(reading) == {"base_gap"}
    assert reading["base_gap"] > result["checks"]["base_gap"]["limit"]


def test_base_gap_reads_each_kind_of_wrong_choice():
    from bench import reference
    rng = np.random.default_rng(3)
    f = rng.standard_normal((40, 8))
    f /= np.linalg.norm(f, axis=1, keepdims=True)
    index, stream = f[:20].astype(np.float32), f[20:30].astype(np.float32)
    index_ids = np.arange(100, 120)
    stream[5] = index[7]                    # a near copy of a stored row
    ids = np.arange(10)
    is_new = np.ones(10, bool)
    best, score = reference.best_bases(stream, ids, is_new, index,
                                       index_ids, 0.3)
    assert best[5] == 107

    def gap(chosen):
        return reference.base_gap(stream, ids, is_new, index, index_ids,
                                  chosen, 0.3)
    assert gap(best) == pytest.approx(0.0, abs=1e-6)
    missed = best.copy()
    missed[5] = -1                          # a base found and not taken
    assert gap(missed) == pytest.approx(1.0 - 0.3, abs=1e-5)
    stranger = best.copy()
    stranger[5] = 999                       # no candidate at all
    assert gap(stranger) == 2.0
    later = best.copy()
    later[2] = 7                            # a chunk later in the stream
    assert gap(later) == 2.0
