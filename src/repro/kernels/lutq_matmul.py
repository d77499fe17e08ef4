"""Fused LUT-decode + matmul Pallas TPU kernel.

Computes ``y = x @ d[A]`` without ever materializing the decoded weight
matrix in HBM: the int8 assignment block (bk x bn) streams HBM->VMEM
(1 byte/weight instead of 2-4 for bf16/f32), is decoded against the
(<=256-entry, SMEM-resident) dictionary, and feeds the MXU.

TPU adaptation of the paper's "K multiplications per output" claim: on
TPU the win is *memory traffic*, not multiplier count — weight bytes
drop 2-4x (4x more with the packed 4-bit variant in lutq_gemv_packed),
which moves the decode-phase memory roofline term directly.

Decode is a compare-and-select over the K dictionary scalars on the
(bk, bn) tile (:func:`select_decode`). Mosaic lowers neither a 1-D
gather nor the ``(bk*bn, 1)`` reshape a one-hot matmul needs; the
select chain yields exactly ``d[a]``.

Grid: (M/bm, N/bn, Kin/bk), k innermost so the f32 output block stays
resident across the accumulation.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def select_decode(a: jax.Array, d_ref) -> jax.Array:
    """``d[a]`` for an int32 index tile: one compare-and-select per
    dictionary entry, the entries read as scalars from a (1, K) SMEM
    dictionary (:func:`smem_row`).

    Indices are taken mod 256, so int8-stored assignments of a K=256
    dictionary decode like ``jnp.take``'s negative-index wrap."""
    n = d_ref.shape[1]
    a = a & 0xFF
    w = jnp.full(a.shape, d_ref[0, 0], d_ref.dtype)
    return jax.lax.fori_loop(
        1, n, lambda k, w: jnp.where(a == k, d_ref[0, k], w), w,
        unroll=unroll_small(n - 1))


def unroll_small(n: int):
    """``fori_loop`` unroll for an n-step loop in a Mosaic kernel, which
    takes a full unroll or none: unrolled up to 16 steps (4-bit
    dictionaries), rolled beyond."""
    return max(1, n) if n <= 16 else 1


#: BlockSpec of a small table held whole in SMEM (read as scalars).
SMEM_WHOLE = pl.BlockSpec(memory_space=pltpu.SMEM)


def smem_row(t: jax.Array, dtype=jnp.float32) -> jax.Array:
    """A (K,) table as the (1, K) 32-bit row the kernels keep in SMEM.
    Two dims, so that ``vmap`` over a stack of tables yields blocks that
    still span their whole last two dims."""
    return t.astype(dtype).reshape(1, -1)


def _kernel(x_ref, a_ref, d_ref, o_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...]
    w = select_decode(a_ref[...].astype(jnp.int32), d_ref)
    o_ref[...] += jax.lax.dot_general(
        x, w.astype(x.dtype),
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def lutq_matmul(
    x: jax.Array,       # (M, Kin)
    a: jax.Array,       # (Kin, N) int8
    d: jax.Array,       # (K,) float32
    *,
    bm: int = 256,
    bn: int = 256,
    bk: int = 512,
    interpret: bool = False,
) -> jax.Array:
    M, Kin = x.shape
    Kin2, N = a.shape
    assert Kin == Kin2
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, Kin)
    assert M % bm == 0 and N % bn == 0 and Kin % bk == 0, (M, N, Kin, bm, bn, bk)

    grid = (M // bm, N // bn, Kin // bk)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            SMEM_WHOLE,
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        interpret=interpret,
    )(x, a, smem_row(d))
