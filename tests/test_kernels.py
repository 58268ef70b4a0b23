"""Per-kernel validation: interpret-mode Pallas vs pure-jnp oracle,
swept over shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import hashing
from repro.kernels import gear_hash, ops, ref, shingle_embed, sim_topk


def _stream(kind: str, n: int, seed: int) -> np.ndarray:
    rng = np.random.Generator(np.random.PCG64(seed))
    if kind == "random":
        return rng.integers(0, 256, size=n, dtype=np.uint8)
    if kind == "zeros":
        return np.zeros(n, np.uint8)
    if kind == "ramp":
        return (np.arange(n) % 256).astype(np.uint8)
    # long runs of one byte, the shape of zero-filled VM image blocks
    return np.repeat(rng.integers(0, 256, size=n // 300 + 1,
                                  dtype=np.uint8), 300)[:n]


class TestGearScan:
    """The scan kernel against numpy, on one block and across blocks,
    where the 31-byte halo comes from the block before."""

    @pytest.mark.parametrize("blocks", [0.25, 1, 2, 4])
    @pytest.mark.parametrize("kind", ["random", "zeros", "ramp", "runs"])
    def test_vs_numpy(self, blocks, kind):
        n = int(blocks * gear_hash.BLOCK_ROWS) * gear_hash.LANES
        data = _stream(kind, n, n + len(kind))
        h, cs, cl = gear_hash.gear_scan(jnp.asarray(data), mask_s=0xFF,
                                        mask_l=0x7, interpret=True)
        want = hashing.gear_hashes_np(data)
        assert np.array_equal(np.asarray(h), want)
        assert np.array_equal(np.unpackbits(np.asarray(cs)),
                              (want & np.uint32(0xFF)) == 0)
        assert np.array_equal(np.unpackbits(np.asarray(cl)),
                              (want & np.uint32(0x7)) == 0)

    def test_serial_recurrence(self):
        # shifts of 32 and more vanish, so the serial FastCDC hash with
        # zeros before the stream's head is the windowed one everywhere
        data = _stream("random", 1 << 16, 5)
        h, _, _ = gear_hash.gear_scan(jnp.asarray(data), mask_s=1, mask_l=1,
                                      interpret=True)
        assert np.array_equal(np.asarray(h),
                              hashing.gear_hashes_serial_np(data))


class TestShingleEmbed:
    @pytest.mark.parametrize("b,s,m", [(1, 61, 64), (8, 61, 64), (13, 61, 50),
                                       (32, 200, 80), (7, 130, 40)])
    def test_vs_ref(self, b, s, m):
        rng = np.random.Generator(np.random.PCG64(b * 100 + s + m))
        ids = rng.integers(0, 2**32, size=(b, s), dtype=np.uint32)
        mask = rng.random((b, s)) < 0.8
        a_np, b_np = hashing.multiply_shift_params(m)
        a, bb = jnp.asarray(a_np), jnp.asarray(b_np)
        got = shingle_embed.shingle_embed_sum(
            jnp.asarray(ids), jnp.asarray(mask.astype(np.float32)),
            a.reshape(1, -1), bb.reshape(1, -1), interpret=True)
        want = ref.shingle_embed_ref(jnp.asarray(ids), jnp.asarray(mask), a, bb)
        # ref divides by count; kernel returns raw sum
        cnt = np.maximum(mask.sum(-1, keepdims=True), 1)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want) * cnt,
                                   rtol=1e-5, atol=1e-5)

    def test_all_masked(self):
        ids = jnp.zeros((4, 61), jnp.uint32)
        mask = jnp.zeros((4, 61), jnp.float32)
        a_np, b_np = hashing.multiply_shift_params(64)
        out = ops.shingle_embed(ids, mask, jnp.asarray(a_np), jnp.asarray(b_np),
                                normalize=False)
        assert np.allclose(np.asarray(out), 0.0)


class TestSimTopk:
    @pytest.mark.parametrize("b,n,d", [(1, 100, 50), (8, 1024, 50), (5, 3000, 64),
                                       (16, 257, 80), (9, 5000, 40)])
    def test_vs_ref(self, b, n, d):
        rng = np.random.Generator(np.random.PCG64(b * 7 + n + d))
        q = rng.standard_normal((b, d)).astype(np.float32)
        idx = rng.standard_normal((n, d)).astype(np.float32)
        s, a = sim_topk.sim_topk(jnp.asarray(q), jnp.asarray(idx), interpret=True)
        sr, ar = ref.sim_topk_ref(jnp.asarray(q), jnp.asarray(idx))
        np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-4, atol=1e-5)
        assert np.array_equal(np.asarray(a), np.asarray(ar))

    def test_padding_never_wins(self):
        """All-negative scores: padded -inf rows must not be selected."""
        q = -np.eye(4, 16, dtype=np.float32)
        idx = np.eye(3, 16, dtype=np.float32)  # pads to 128+
        s, a = sim_topk.sim_topk(jnp.asarray(q), jnp.asarray(idx), interpret=True)
        assert (np.asarray(a) < 3).all()


class TestFlashAttention:
    @pytest.mark.parametrize("b,h,kv,tq,tk,hd,causal", [
        (2, 8, 4, 300, 300, 32, True),
        (1, 4, 4, 512, 512, 64, True),
        (2, 8, 2, 128, 640, 32, False),
        (1, 6, 3, 257, 257, 16, True),   # ragged vs block size
    ])
    def test_vs_ref(self, b, h, kv, tq, tk, hd, causal):
        from repro.kernels import flash_attn
        rng = np.random.Generator(np.random.PCG64(b * h + tq))
        q = jnp.asarray(rng.standard_normal((b, h, tq, hd)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((b, kv, tk, hd)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((b, kv, tk, hd)), jnp.float32)
        got = flash_attn.flash_attention(q, k, v, causal=causal,
                                         block_q=128, block_k=128,
                                         interpret=True)
        want = ref.flash_attention_ref(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_bf16(self):
        from repro.kernels import flash_attn
        rng = np.random.Generator(np.random.PCG64(9))
        q = jnp.asarray(rng.standard_normal((1, 8, 256, 64)), jnp.bfloat16)
        k = jnp.asarray(rng.standard_normal((1, 4, 256, 64)), jnp.bfloat16)
        v = jnp.asarray(rng.standard_normal((1, 4, 256, 64)), jnp.bfloat16)
        got = flash_attn.flash_attention(q, k, v, interpret=True)
        want = ref.flash_attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=2e-2, atol=2e-2)
