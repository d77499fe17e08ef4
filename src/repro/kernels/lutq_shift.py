"""Shift-and-add LUT matmul Pallas TPU kernel (multiplier-less path).

Computes the int32 accumulator ``acc = xq @ wsh[A]`` for pow2-constrained
dictionaries: ``xq`` are int8-quantized activations, ``A`` streams
HBM->VMEM as int8 assignments (1 byte/weight), and ``wsh`` is the
(<=256-entry, SMEM-resident) *shifted-integer* dictionary — each pow2
entry pre-lowered to ``sign * (1 << (exponent - min_exponent))`` by
``kernels.ref.pow2_shift_weights``, an O(K) exponent-add outside the hot
loop. The kernel therefore performs only integer adds/shifted adds (the
paper's multiplier-less claim; on the MXU as int8 x int8 contractions
of 7-bit weight planes); the caller applies the single fp
multiply — ``acc * (act_scale * 2^(min_exponent - 1 + POW2_MIN_EXP))`` —
at the O(M·N) epilogue.

Because accumulation is exact int32, the result is bit-identical to the
``kernels.ref.lutq_shift_ref`` oracle under ANY tile shape and any
K-shard/psum order — unlike the f32 fused kernel, no single-k-step
pinning is needed for interpret-mode bit-identity.

Overflow bound (checked at encode time in ``core.policy.serve_view``):
|acc| <= 127 * 2^span * Kin, so 7 + span + ceil(log2 Kin) <= 31 bits
must hold, where span = max-min nonzero exponent of the dictionary.

Grid: (M/bm, N/bn, Kin/bk), k innermost so the int32 output block stays
resident across the accumulation.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.lutq_matmul import (SMEM_WHOLE, select_decode, smem_row,
                                       unroll_small)

#: 7-bit planes that cover any shift a 31-bit accumulator admits.
_MAX_PLANES = 5


def _kernel(x_ref, a_ref, w_ref, o_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    xq = x_ref[...]                                   # (bm, bk) int8
    wt = select_decode(a_ref[...].astype(jnp.int32), w_ref)  # ±2^j or 0
    mag = jnp.where(wt < 0, -wt, wt)
    top = jax.lax.fori_loop(
        1, w_ref.shape[1],
        lambda k, m: jnp.maximum(m, jnp.maximum(w_ref[0, k], -w_ref[0, k])),
        jnp.maximum(w_ref[0, 0], -w_ref[0, 0]), unroll=unroll_small(
            w_ref.shape[1] - 1))

    # The MXU has no int32 x int32 matmul. Split each shifted weight into
    # 7-bit planes (exactly one is nonzero per weight), contract each as
    # int8 x int8 -> int32 and shift the partial back: every partial and
    # every running sum stays within the overflow guard, so the sum is
    # the exact int32 accumulator. Planes above the dictionary's largest
    # shift are skipped at run time.
    for p in range(_MAX_PLANES):
        def plane(p=p):
            c = (mag >> (7 * p)) & 0x7F
            c = jnp.where(wt < 0, -c, c).astype(jnp.int8)
            part = jax.lax.dot_general(
                xq, c, dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32)
            o_ref[...] += part << (7 * p) if p else part

        if p == 0:
            plane()
        else:
            pl.when(top >= (1 << (7 * p)))(plane)


def lutq_shift(
    xq: jax.Array,      # (M, Kin) int8 quantized activations
    a: jax.Array,       # (Kin, N) int8 assignments
    wsh: jax.Array,     # (K,) int32 shifted-integer dictionary
    *,
    bm: int = 256,
    bn: int = 256,
    bk: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """int32 accumulator of the shift-add matmul (see module docstring)."""
    M, Kin = xq.shape
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
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.int32),
        interpret=interpret,
    )(xq.astype(jnp.int8), a, smem_row(wsh, jnp.int32))
