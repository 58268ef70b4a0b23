"""Pallas TPU kernels for CARD's compute hot spots + the prefill fast path.

Each kernel: <name>.py (pl.pallas_call + explicit BlockSpec VMEM tiling),
jit'd public wrappers in ops.py (compiled on TPU, interpret=True on CPU),
pure-jnp oracles in ref.py. tests/test_kernels.py sweeps shapes/dtypes and
asserts equality/allclose against the oracles; tests/test_chip_compile.py
compiles the store's kernels for a described v5e chip.

  gear_hash      the stream scan: gear table lookup by lane permute,
                 32-byte window by doubling (the rolling hash is linear,
                 so every position is a 32-tap correlation evaluated in
                 parallel, DESIGN.md §3) and the FastCDC candidate bits,
                 packed on the MXU, in one pass (kernels/ingest._scan_fused)
  shingle_embed  multiply-shift M-hash feature accumulation (Algorithm 1)
  sim_topk       tiled cosine top-1 with running (max, argmax) — the
                 flash-attention trick applied to resemblance search
  flash_attn     blockwise online-softmax attention with GQA-by-indexing
"""
from repro.kernels import ops  # noqa: F401
