"""Compile the store's device programs for one described TPU v5e chip.

Nothing runs: the TPU compiler installed with jaxlib compiles for a chip
that is described, not attached, and refuses what the chip would refuse
(unaligned kernel blocks, too much fast memory, programs that do not fit
in HBM). Interpret-mode tests cannot see those faults.

Widths are the ones ``chip_smoke.py`` drives at its defaults: 128 MiB
streams land in the 256 MiB scan bucket, ~8k chunks of 16 KiB average
pad to B = 16384 rows, and the longest-chunk extent is the chunker's
max size (64 KiB). The scan is one Pallas kernel, ``gear_scan``.

The programs' named scopes (segment max, unique, embed) and kernel
names are asserted in the lowered text, so that a device trace can
split each program's time by step.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and test workers import every file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import chunking, features
from repro.kernels import ingest as kingest
from repro.kernels import ops
from repro.kernels import shingle_embed as _shingle
from repro.kernels import sim_topk as _topk

SPAD = 256 << 20                    # scan bucket of a 128 MiB stream
BPAD = 16384                        # chunk-count bucket of ~8k chunks
LMAX = chunking.ChunkerConfig().max_size
FEAT = features.FeatureConfig()     # k=32, m=64, n=2
D = 50                              # context-model output width
INDEX_ROWS = 1 << 20


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _scopes(lowered) -> str:
    """The lowered program with its op locations, where named scopes
    and kernel names show."""
    return lowered.as_text(debug_info=True)


# temp_size_in_bytes of the 256 MiB-bucket scan before it was one kernel
# (a gather of GEAR_TABLE, five shifted copies and jnp.packbits)
SCAN_TEMP_BEFORE = 4831934976


def test_scan_compiles(one_chip, monkeypatch):
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    cfg = chunking.ChunkerConfig()
    lowered = kingest._scan_fused.lower(
        _spec(one_chip, (SPAD,), jnp.uint8),
        mask_s=cfg.mask_s, mask_l=cfg.mask_l)
    assert 'kernel_name = "gear_scan"' in _scopes(lowered)
    compiled = lowered.compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the table lookup is a lane permute inside the kernel, never an
    # XLA gather of one index per stream byte
    assert "gather" not in text
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes == SPAD
    assert mem.temp_size_in_bytes <= SCAN_TEMP_BEFORE
    # everything the program holds must fit one 16 GB chip
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < 16e9


def test_extract_with_kernel_compiles(one_chip, monkeypatch):
    # jax.default_backend() is "cpu" here, so steer the kernel wrapper to
    # the compiled (Mosaic) mode the chip host selects
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    lowered = kingest._extract_fused.lower(
        _spec(one_chip, (SPAD,), jnp.uint32),
        _spec(one_chip, (BPAD,), jnp.int32),
        _spec(one_chip, (BPAD,), jnp.int32),
        _spec(one_chip, (FEAT.m,), jnp.uint32),
        _spec(one_chip, (FEAT.m,), jnp.uint32),
        k=FEAT.k, n=FEAT.n, lmax=LMAX, normalize=True,
        use_kernel=True)
    text = _scopes(lowered)
    for name in ("segment_max", "unique", "embed",
                 'kernel_name = "shingle_embed"'):
        assert name in text, name
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sim_topk_compiles(one_chip):
    lowered = _topk.sim_topk.lower(
        _spec(one_chip, (8192, D), jnp.float32),
        _spec(one_chip, (INDEX_ROWS, D), jnp.float32),
        interpret=False)
    assert 'kernel_name = "sim_topk"' in _scopes(lowered)
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_shingle_embed_compiles(one_chip):
    s = FEAT.num_shingles
    lowered = _shingle.shingle_embed_sum.lower(
        _spec(one_chip, (BPAD, s), jnp.uint32),
        _spec(one_chip, (BPAD, s), jnp.bool_),
        _spec(one_chip, (1, FEAT.m), jnp.uint32),
        _spec(one_chip, (1, FEAT.m), jnp.uint32),
        interpret=False)
    assert 'kernel_name = "shingle_embed"' in _scopes(lowered)
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()
