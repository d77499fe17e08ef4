"""Packed 4-bit LUT-Q decode GEMV Pallas kernel (the decode-serving win).

Decode at batch B is HBM-bandwidth-bound: wall time ~ weight bytes / HBM
bw. LUT-Q with K=16 stores 4 bits/weight; this kernel keeps the
assignment matrix PACKED in HBM (two indices per byte), unpacks nibbles
in VMEM, decodes against the SMEM dictionary (compare-and-select, see
``lutq_matmul.select_decode``) and runs the (small-M) matmul — weight
traffic is Kin*N/2 bytes vs 2*Kin*N for bf16: a 4x reduction of the
dominant roofline term for decode.

Grid: (B/bm, N/bn, Kin/bk) with k innermost. At decode time B is small
and one bm block holds it whole; a long prefill tiles B, so x and the
f32 output stay inside scoped VMEM.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.lutq_matmul import SMEM_WHOLE, select_decode, smem_row


def _kernel(x_ref, p_ref, d_ref, o_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    # widen before the nibble ops: Mosaic has no 8-bit vector shifts
    packed = p_ref[...].astype(jnp.int32)   # (bk/2, bn)
    lo = packed & 0xF                       # even rows
    hi = (packed >> 4) & 0xF
    bk2, bn = packed.shape
    idx = jnp.stack([lo, hi], axis=1).reshape(bk2 * 2, bn)
    w = select_decode(idx, d_ref)
    x = x_ref[...]                          # (bm, bk)
    o_ref[...] += jax.lax.dot_general(
        x, w.astype(x.dtype),
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def lutq_gemv_packed(
    x: jax.Array,        # (B, Kin)
    packed: jax.Array,   # (Kin/2, N) uint8 — two 4-bit indices per byte
    d: jax.Array,        # (16,) float32
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

    grid = (B // bm, N // bn, Kin // bk)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk // 2, bn), lambda i, j, k: (k, j)),
            SMEM_WHOLE,
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((B, N), jnp.float32),
        interpret=interpret,
    )(x, packed, smem_row(d))
