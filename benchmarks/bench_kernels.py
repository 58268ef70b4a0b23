"""Per-Pallas-kernel microbenchmark: interpret-mode kernel vs pure-jnp ref
(correctness is asserted; on-CPU wall time is for the ref path, which is
the deployable CPU fallback — TPU timing requires hardware)."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import hashing
from repro.kernels import gear_hash, ref, shingle_embed, sim_topk


def _t(fn, *args, reps=5):
    fn(*args)  # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / reps * 1e6


def run() -> list[dict]:
    rng = np.random.Generator(np.random.PCG64(0))
    rows = []

    data = rng.integers(0, 256, size=1 << 19, dtype=np.uint8)
    ref_us = _t(lambda x: hashing.gear_hashes_np(x), data)
    kern, _, _ = gear_hash.gear_scan(jnp.asarray(data), mask_s=0xFF,
                                     mask_l=0xF, interpret=True)
    rows.append({"bench": "kernels", "name": "gear_hash.gear_scan",
                 "shape": "524288", "us_per_call_ref": round(ref_us, 1),
                 "allclose": bool(np.array_equal(
                     np.asarray(kern), hashing.gear_hashes_np(data)))})

    ids = jnp.asarray(rng.integers(0, 2**32, size=(256, 61), dtype=np.uint32))
    mask = jnp.ones((256, 61), jnp.float32)
    a, b = hashing.multiply_shift_params(64)
    aj, bj = jnp.asarray(a), jnp.asarray(b)
    ref_us = _t(lambda i, m: ref.shingle_embed_ref(i, m > 0, aj, bj), ids, mask)
    kern = shingle_embed.shingle_embed_sum(ids, mask, aj.reshape(1, -1),
                                           bj.reshape(1, -1), interpret=True)
    oracle = ref.shingle_embed_ref(ids, mask > 0, aj, bj) * 61
    rows.append({"bench": "kernels", "name": "shingle_embed",
                 "shape": "256x61x64", "us_per_call_ref": round(ref_us, 1),
                 "allclose": bool(np.allclose(np.asarray(kern), np.asarray(oracle),
                                              atol=1e-4))})

    q = jnp.asarray(rng.standard_normal((64, 50)).astype(np.float32))
    idx = jnp.asarray(rng.standard_normal((16384, 50)).astype(np.float32))
    ref_us = _t(lambda a_, b_: ref.sim_topk_ref(a_, b_), q, idx)
    ks, ka = sim_topk.sim_topk(q, idx, interpret=True)
    rs, ra = ref.sim_topk_ref(q, idx)
    rows.append({"bench": "kernels", "name": "sim_topk",
                 "shape": "64x16384x50", "us_per_call_ref": round(ref_us, 1),
                 "allclose": bool(np.allclose(np.asarray(ks), np.asarray(rs),
                                              atol=1e-4)
                                  and np.array_equal(np.asarray(ka), np.asarray(ra)))})
    return rows


def main():
    from benchmarks import common
    common.emit(run(), "kernels")


if __name__ == "__main__":
    main()
