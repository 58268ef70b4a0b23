"""The stream scan kernel: gear lookup, 32-byte window and FastCDC
candidate bits in one pass over the bytes (DESIGN.md §3, §8).

The gear hash is linear, so every position's windowed hash is a 32-tap
correlation of the byte's table words (``hashing.gear_hashes_np``):

    h_i = sum_{k<32} GEAR_TABLE[b_{i-k}] << k        (uint32 wraparound)

The bucket-padded [Spad] byte stream is laid out as [R, 128] rows, row
r continuing row r-1. That is the chip's own tiling of a flat array, so
the reshapes in and out are free and the hashes leave as the flat
[Spad] uint32 array the extract program reads. The grid walks blocks of
rows; each step also reads the last rows of the block before it, which
hold the window's 31-byte halo.

* Lookup: GEAR_TABLE's 256 words are two 128-lane rows. A byte's word is
  a lane permute (``take_along_axis`` along lanes) of the row its top
  bit picks, one vector register at a time: no gather from memory.
* Window: five doubling steps, ``h_2w(i) = h_w(i) + h_w(i-w) << w``.
  ``i - w`` is a lane roll by w, with the row above (a sublane roll) for
  the lanes below w and the halo's last row for the block's first row;
  positions before the stream's head read zero.
* Candidate bits: a mask's 0/1 map, 8 rows side by side, times a
  [1024, 128] matrix of bit weights on the MXU packs 8 lanes into one
  byte, exactly (a sum of distinct powers of two below 256). The bytes
  come out in ``np.packbits`` order, so the host unpacks them flat.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import hashing

LANES = 128
BLOCK_ROWS = 2048           # 256 KiB of stream a grid step
_SLAB = 32                  # rows looked up at a time (the u8 tile)


def _tables() -> np.ndarray:
    """GEAR_TABLE as two lane rows (bytes < 128, bytes >= 128) of one
    [8, 128] tile."""
    t = np.zeros((8, LANES), np.uint32)
    t[0] = hashing.GEAR_TABLE[:LANES]
    t[1] = hashing.GEAR_TABLE[LANES:]
    return t


def _bit_weights() -> np.ndarray:
    """[1024, 128] map from 1024 consecutive 0/1 positions to their 128
    packed bytes, first position in the top bit (``np.packbits``)."""
    k = np.arange(8 * LANES)
    w = np.zeros((8 * LANES, LANES), np.float32)
    w[k, k // 8] = 2.0 ** (7 - k % 8)
    return w


def _lookup(tab: jax.Array, b: jax.Array) -> jax.Array:
    """[s, 128] int32 bytes -> their GEAR_TABLE words."""
    idx = b & (LANES - 1)
    mode = lax.GatherScatterMode.PROMISE_IN_BOUNDS
    lo = jnp.take_along_axis(jnp.broadcast_to(tab[0:1], b.shape), idx,
                             axis=1, mode=mode)
    hi = jnp.take_along_axis(jnp.broadcast_to(tab[1:2], b.shape), idx,
                             axis=1, mode=mode)
    return jnp.where(b >= LANES, hi, lo)


def _scan_kernel(tab_ref, bits_ref, halo_ref, x_ref, h_ref, cs_ref, cl_ref,
                 m_scr, *, mask_s: int, mask_l: int):
    rows = h_ref.shape[0]
    tab = tab_ref[...]

    def slab(k, carry):
        r0 = pl.multiple_of(k * _SLAB, _SLAB)
        h_ref[pl.ds(r0, _SLAB), :] = _lookup(
            tab, x_ref[pl.ds(r0, _SLAB), :].astype(jnp.int32))
        return carry
    lax.fori_loop(0, rows // _SLAB, slab, 0)
    # the previous block's last rows; the stream's head has none
    halo = _lookup(tab, halo_ref[_SLAB - 8:, :].astype(jnp.int32))
    halo = jnp.where(pl.program_id(0) == 0, jnp.uint32(0), halo)

    h = h_ref[...]
    lane = lax.broadcasted_iota(jnp.int32, h.shape, 1)
    row = lax.broadcasted_iota(jnp.int32, h.shape, 0)
    w = 1
    while w < hashing.GEAR_WINDOW:
        back = pltpu.roll(h, w, 1)                      # h[r, c - w]
        halo_back = pltpu.roll(halo, w, 1)
        above = jnp.where(row == 0,
                          jnp.broadcast_to(halo_back[7:8], h.shape),
                          pltpu.roll(back, 1, 0))       # h[r - 1, c - w]
        h = h + (jnp.where(lane < w, above, back) << w)
        # the halo's last row is exact in the lanes the block reads
        # (>= 128 - 16) without a carry of its own
        halo = halo + (halo_back << w)
        w *= 2
    h_ref[...] = h

    bits = bits_ref[...]

    def pack(mask: int) -> jax.Array:
        m_scr[...] = jnp.where((h & jnp.uint32(mask)) == 0, 1.0, 0.0)
        side = jnp.concatenate(                         # [rows/8, 1024]
            [m_scr[pl.ds(j, rows // 8, stride=8), :] for j in range(8)],
            axis=1)
        packed = jnp.dot(side.astype(jnp.bfloat16), bits,
                         preferred_element_type=jnp.float32)
        return packed.astype(jnp.int32).astype(jnp.uint8)
    cs_ref[...] = pack(mask_s)
    cl_ref[...] = pack(mask_l)


@functools.partial(jax.jit, static_argnames=("mask_s", "mask_l", "interpret"))
def gear_scan(data: jax.Array, *, mask_s: int, mask_l: int,
              interpret: bool = False
              ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """[Spad] uint8 -> (gear hashes [Spad] uint32, packed candidate maps
    of ``mask_s`` and ``mask_l`` [Spad/8] uint8 each). ``Spad`` is a
    power of two of at least 128 * 32 bytes, as every stream bucket is."""
    x = data.reshape(-1, LANES)
    r = x.shape[0]
    br = min(BLOCK_ROWS, r)
    assert r % br == 0 and br % _SLAB == 0, (r, br)
    halo_block = br // _SLAB
    kernel = functools.partial(_scan_kernel, mask_s=mask_s, mask_l=mask_l)
    h, cs, cl = pl.pallas_call(
        kernel,
        grid=(r // br,),
        in_specs=[
            pl.BlockSpec((8, LANES), lambda i: (0, 0)),
            pl.BlockSpec((8 * LANES, LANES), lambda i: (0, 0)),
            # the previous block's last _SLAB rows (clamped at block 0,
            # where the kernel zeroes them)
            pl.BlockSpec((_SLAB, LANES),
                         lambda i: (jnp.maximum(i * halo_block - 1, 0), 0)),
            pl.BlockSpec((br, LANES), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, LANES), lambda i: (i, 0)),
            pl.BlockSpec((br // 8, LANES), lambda i: (i, 0)),
            pl.BlockSpec((br // 8, LANES), lambda i: (i, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((r, LANES), jnp.uint32),
                   jax.ShapeDtypeStruct((r // 8, LANES), jnp.uint8),
                   jax.ShapeDtypeStruct((r // 8, LANES), jnp.uint8)],
        scratch_shapes=[pltpu.VMEM((br, LANES), jnp.float32)],
        interpret=interpret,
        name="gear_scan",
    )(jnp.asarray(_tables()), jnp.asarray(_bit_weights(), jnp.bfloat16), x, x)
    return h.reshape(-1), cs.reshape(-1), cl.reshape(-1)
