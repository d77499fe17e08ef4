"""Packed 4-bit LUT-Q decode GEMV Pallas kernel (the decode-serving win).

Decode at batch B is HBM-bandwidth-bound: wall time ~ weight bytes / HBM
bw. LUT-Q with K <= 16 stores 4 bits/weight; this kernel keeps the
assignment matrix PACKED in HBM (two indices per byte, the serve layout
of ``ref.pack4_kin``: row pairs, even row in the low nibble) and decodes
it in VMEM — weight traffic is Kin*N/2 bytes vs 2*Kin*N for bf16.

Decode, per 128-lane column block of a (bk/2, bn) packed tile:

* the two nibble planes are decoded apart, never interleaved back into
  rows: ``y += x_even @ d[lo] + x_odd @ d[hi]``, with x's even and odd
  columns split once outside the kernel (XLA shares the split among the
  dots that read one activation);
* each plane is decoded by a 4-level bit tree (:func:`tree_decode`):
  four bit tests, then 8 + 4 + 2 + 1 selects between dictionary
  scalars read from SMEM.

That is about 27 vector ops per 1024 decoded weights, against about 96
for a compare-and-select chain over the interleaved tile. A lane gather
(``jnp.take_along_axis`` from a 128-lane dictionary row) takes about 7,
but ran 2-10% slower on a v5e at every tile timed: at decode widths
the MXU's weight loads, not the VPU, seem to bound the kernel
(``docs/kernels.md`` has the counts and times). The decoded values are
exactly ``d[a]``, cast to x's dtype before the MXU.

Grid: (B/bm, N/bn, Kin/bk) with k innermost. At decode time B is small
and one bm block holds it whole; a long prefill tiles B, so x and the
f32 output stay inside scoped VMEM. The column loop is rolled, so a wide
tile does not grow the code.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.lutq_matmul import SMEM_WHOLE, smem_row

LANES = 128


def tree_decode(idx: jax.Array, d_ref) -> jax.Array:
    """``d[idx]`` for an int32 tile of 4-bit indices, from a (1, 16)
    SMEM dictionary: each level halves the candidates by one bit."""
    cand = [d_ref[0, k] for k in range(16)]
    for bit in range(4):
        take_odd = (idx & (1 << bit)) != 0
        cand = [jnp.where(take_odd, hi, lo)
                for lo, hi in zip(cand[0::2], cand[1::2])]
    return cand[0]


def _dot(x, w):
    return jax.lax.dot_general(
        x, w.astype(x.dtype),
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _kernel(xe_ref, xo_ref, p_ref, d_ref, o_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    bn = p_ref.shape[1]
    width = LANES if bn % LANES == 0 else bn   # interpret mode: any bn
    xe, xo = xe_ref[...], xo_ref[...]

    def column(j, carry):
        c = pl.ds(pl.multiple_of(j * width, width), width)
        # widen before the nibble ops (Mosaic has no 8-bit vector
        # shifts); the zero-extended byte's high nibble needs no mask
        packed = p_ref[:, c].astype(jnp.int32)
        o_ref[:, c] += _dot(xe, tree_decode(packed & 0xF, d_ref)) + \
            _dot(xo, tree_decode(packed >> 4, d_ref))
        return carry

    jax.lax.fori_loop(0, bn // width, column, 0)


def lutq_gemv_packed(
    x: jax.Array,        # (B, Kin)
    packed: jax.Array,   # (Kin/2, N) uint8 — two 4-bit indices per byte
    d: jax.Array,        # (K,) float32, K <= 16
    *,
    bm: int = 256,
    bn: int = 256,
    bk: int = 512,
    interpret: bool = False,
) -> jax.Array:
    B, Kin = x.shape
    Kin2, N = packed.shape
    assert Kin == Kin2 * 2
    assert d.shape[0] <= 16, "packed layout is 4-bit (K <= 16)"
    bm, bn, bk = min(bm, B), min(bn, N), min(bk, Kin)
    assert B % bm == 0 and N % bn == 0 and Kin % bk == 0 and bk % 2 == 0

    x_even = jax.lax.slice(x, (0, 0), x.shape, (1, 2))
    x_odd = jax.lax.slice(x, (0, 1), x.shape, (1, 2))
    d16 = jnp.pad(d, (0, 16 - d.shape[0]))   # padded entries never indexed
    x_spec = pl.BlockSpec((bm, bk // 2), lambda i, j, k: (i, k))
    return pl.pallas_call(
        _kernel,
        grid=(B // bm, N // bn, Kin // bk),
        in_specs=[
            x_spec,
            x_spec,
            pl.BlockSpec((bk // 2, bn), lambda i, j, k: (k, j)),
            SMEM_WHOLE,
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((B, N), jnp.float32),
        interpret=interpret,
    )(x_even, x_odd, packed, smem_row(d16))
