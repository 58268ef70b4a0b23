"""Detectors + the end-to-end dedup/delta pipeline (paper §5 system).

    stream -> FastCDC chunks -> exact dedup (blake2b)
           -> resemblance detection (pluggable: CARD / Finesse / N-transform)
           -> delta-encode against the detected base | store raw
           -> container backend; DCR = bytes_in / bytes_stored

Detectors implement the staged protocol (repro.api.detect, DESIGN.md §2.1):

    fit(training_streams, chunker_cfg)   offline model training
    extract(batch) -> features           pure, batched heavy lifting
    score(features, batch) -> result     pure candidate scoring
    observe(features, batch)             the one index-mutating step

`extract` sees the whole stream at once so feature extraction and index
search batch properly (CARD queries are one matmul, not n python calls);
FirstFit baselines keep their sequential any-SF-match semantics via a
pure overlay in `score`. The v0 single-call `detect(chunks, ids, is_new,
stream_hashes)` surface survives via LegacyDetectMixin, bit-identical.
Detection time (the paper's speed metric) = wall time across the three
stages, excluding chunking and delta I/O, matching the paper's accounting.

The store itself lives in repro.api.store (StreamSession ingestion over a
ContainerBackend); DedupStore/StoreStats are re-exported here for the v0
import surface.
"""
from __future__ import annotations

from typing import Any, Protocol, Sequence

import numpy as np

from repro.api.detect import LegacyDetectMixin
from repro.api.registry import register_detector
from repro.api.store import DedupStore, StreamSession, chunk_with  # noqa: F401  (v0 surface)
from repro.api.types import DetectBatch, DetectResult, IngestReport, StoreStats  # noqa: F401
from repro.core import baselines, chunking, context_model, features


class Detector(Protocol):
    """v0 single-call protocol; still accepted everywhere (run_detect
    falls back to it for detectors that are not staged)."""

    name: str

    def fit(self, training_streams: Sequence[bytes],
            cfg: chunking.ChunkerConfig) -> None: ...

    def detect(self, chunks: list[chunking.Chunk], ids: np.ndarray,
               is_new: np.ndarray, stream_hashes: np.ndarray) -> np.ndarray: ...


class NullDetector(LegacyDetectMixin):
    """Exact dedup only (no delta compression)."""
    name = "dedup-only"

    def fit(self, training_streams, cfg):
        pass

    def extract(self, batch: DetectBatch) -> None:
        return None

    def score(self, feats: None, batch: DetectBatch) -> DetectResult:
        return DetectResult(np.full(len(batch), -1, np.int64))

    def observe(self, feats: None, batch: DetectBatch) -> None:
        pass


class SuperFeatureDetector(LegacyDetectMixin):
    """Shared FirstFit wrapper for N-transform / Finesse.

    FirstFit is inherently sequential (chunk i may delta against chunk
    j < i of the same stream, inserted moments earlier), so `score`
    replays that order against a *pure overlay* of the shared index:
    persistent tables are consulted first (insert is first-writer-wins),
    then same-batch entries. `observe` then admits the batch for real —
    the final index state and every verdict are bit-identical to the v0
    interleaved query/insert loop.
    """

    def __init__(self, scheme, name: str):
        self._scheme = scheme
        self.name = name
        self._index = baselines.SuperFeatureIndex()

    def fit(self, training_streams, cfg):
        pass  # content-only schemes have no training phase

    def extract(self, batch: DetectBatch) -> list[tuple[int, ...]]:
        return [self._scheme.super_features(ck.data) for ck in batch.chunks]

    def score(self, sfs_list: list[tuple[int, ...]],
              batch: DetectBatch) -> DetectResult:
        n = len(batch)
        out = np.full(n, -1, np.int64)
        overlay: list[dict[int, int]] = []
        for i, sfs in enumerate(sfs_list):
            if batch.is_new[i]:
                hit = self._index.query(sfs, overlay=overlay)
                if hit is not None and hit != batch.ids[i]:
                    out[i] = hit
            self._index.stage(sfs, int(batch.ids[i]), overlay)
        return DetectResult(out)

    def observe(self, sfs_list: list[tuple[int, ...]],
                batch: DetectBatch) -> None:
        for sfs, cid in zip(sfs_list, batch.ids):
            self._index.insert(sfs, int(cid))


def ntransform_detector(cfg: baselines.SuperFeatureConfig | None = None):
    return SuperFeatureDetector(baselines.NTransform(cfg), "n-transform")


def finesse_detector(cfg: baselines.SuperFeatureConfig | None = None):
    return SuperFeatureDetector(baselines.Finesse(cfg), "finesse")


class CARDDetector(LegacyDetectMixin):
    """The paper's scheme: initial features -> context model -> cosine index.

    Batch two-phase search: one top-1 query of all new chunks against the
    stored index, plus one intra-stream similarity pass (earlier chunks of
    the same stream are eligible bases), then a single batched insert.

    The resemblance index is a registry knob (`index="exact"` |
    "banded-lsh" | an already-built index object), not a constructor
    branch; `use_lsh_bands` survives as a v0 alias.
    """

    name = "card"

    def __init__(self,
                 feat_cfg: features.FeatureConfig | None = None,
                 model_cfg: context_model.ContextModelConfig | None = None,
                 threshold: float = 0.3,
                 use_lsh_bands: bool = False,
                 use_kernel: bool = True,
                 fused: bool = True,
                 index: str | Any | None = None,
                 index_args: dict | None = None):
        self.feat_cfg = feat_cfg or features.FeatureConfig()
        self.model_cfg = model_cfg or context_model.ContextModelConfig(m=self.feat_cfg.m)
        assert self.model_cfg.m == self.feat_cfg.m
        self.threshold = threshold
        self.fused = fused
        self._lmax_floor = 0            # set from the chunker cfg in fit()
        self.extractor = features.FeatureExtractor(self.feat_cfg,
                                                   use_kernel=use_kernel,
                                                   fused=fused)
        self.model = context_model.ContextModel(self.model_cfg)
        if index is None:
            index = "banded-lsh" if use_lsh_bands else "exact"
        if isinstance(index, str):
            from repro.api.registry import get_index
            kwargs = dict(index_args or {})
            if index == "exact":
                kwargs.setdefault("use_kernel", use_kernel)
            self.index = get_index(index)(self.model_cfg.d,
                                          threshold=threshold, **kwargs)
        else:
            self.index = index

    def fit(self, training_streams, cfg):
        """Training process (paper Fig. 3 left): chunk the training data in
        stream order, extract initial features, train the CBOW model."""
        # pin the fused path's Lmax bucket at the chunker's max chunk
        # size, so steady-state streams of this config never retrace just
        # because their observed longest chunk straddles a pow2 boundary
        self._lmax_floor = int(getattr(cfg, "max_size", 0) or 0)
        feats = []
        for stream in training_streams:
            chunks, h = chunk_with(cfg, stream)
            if chunks:
                offs = np.asarray([c.offset for c in chunks])
                feats.append(self.extractor([c.data for c in chunks], h, offs,
                                            lmax_floor=self._lmax_floor))
        if not feats:
            raise ValueError("CARD needs at least one training stream")
        self.model.fit(np.concatenate(feats, axis=0))

    def extract(self, batch: DetectBatch) -> np.ndarray:
        init = self.extractor([c.data for c in batch.chunks],
                              batch.stream_hashes, batch.offsets,
                              lmax_floor=self._lmax_floor)
        if not self.fused:
            return self.model.transform(init)                 # [n, D]
        # bucket the row count so the jitted projection compiles once per
        # pow2 bucket, not once per stream length (DESIGN.md §8); the
        # transform is row-wise, so padding rows changes nothing
        n = init.shape[0]
        pad = features.bucket_pow2(n, 16) - n
        if pad:
            init = np.pad(init, ((0, pad), (0, 0)))
        return self.model.transform(init)[:n]                 # [n, D]

    def score(self, feats: np.ndarray, batch: DetectBatch) -> DetectResult:
        from repro.api import observe   # off the package-import path
        n = len(batch)
        out = np.full(n, -1, np.int64)

        # phase 1: against the stored index (the index's copy to the
        # device, the sim_topk call and its compile, the answer's fetch)
        with observe.Span("ingest.search"):
            ext_ids, ext_scores = self.index.query(feats)

        # phase 2: intra-stream (earlier chunks of this stream)
        sims = feats @ feats.T
        iu = np.triu_indices(n)
        sims[iu] = -np.inf                                   # j < i only
        intra_j = sims.argmax(axis=1)
        intra_s = sims[np.arange(n), intra_j]

        use_intra = intra_s >= np.maximum(ext_scores, self.threshold)
        best_id = np.where(use_intra, batch.ids[intra_j], ext_ids)
        best_sc = np.where(use_intra, intra_s, ext_scores)
        ok = (best_sc >= self.threshold) & batch.is_new & (best_id != batch.ids)
        out[ok] = best_id[ok]
        return DetectResult(out, scores=np.where(ok, best_sc, 0.0))

    def observe(self, feats: np.ndarray, batch: DetectBatch) -> None:
        new_mask = batch.is_new.astype(bool)
        if new_mask.any():
            self.index.insert_batch(feats[new_mask], batch.ids[new_mask])


# --- registry factories (repro.api.config builds through these) --------------

@register_detector("dedup-only")
def _build_null() -> NullDetector:
    return NullDetector()


@register_detector("finesse")
def _build_finesse(**sf_args) -> SuperFeatureDetector:
    cfg = baselines.SuperFeatureConfig(**sf_args) if sf_args else None
    return finesse_detector(cfg)


@register_detector("n-transform")
def _build_ntransform(**sf_args) -> SuperFeatureDetector:
    cfg = baselines.SuperFeatureConfig(**sf_args) if sf_args else None
    return ntransform_detector(cfg)


@register_detector("card")
def _build_card(*, feat: dict | None = None, model: dict | None = None,
                threshold: float = 0.3, index: str | None = None,
                index_args: dict | None = None,
                use_kernel: bool = True, fused: bool = True) -> CARDDetector:
    feat_cfg = features.FeatureConfig(**(feat or {}))
    model_kw = dict(model or {})
    model_kw.setdefault("m", feat_cfg.m)
    model_cfg = context_model.ContextModelConfig(**model_kw)
    return CARDDetector(feat_cfg=feat_cfg, model_cfg=model_cfg,
                        threshold=threshold, index=index,
                        index_args=index_args, use_kernel=use_kernel,
                        fused=fused)


def run_workload(detector: Detector, versions: Sequence[bytes],
                 cfg: chunking.ChunkerConfig | None = None,
                 train_on: int = 1) -> StoreStats:
    """Paper experiment harness: fit on the first `train_on` versions, then
    ingest every version through the store; returns final stats."""
    store = DedupStore(detector, cfg)
    store.fit(list(versions[:train_on]))
    for v in versions:
        store.ingest(v)
    return store.stats
