"""Tiled k-means assignment + statistics Pallas kernel (paper step 4).

Step 4 runs over EVERY weight of EVERY layer each minibatch — the
training-time hot spot LUT-Q adds. For a sorted dictionary the nearest
entry of w is ``sum(mid < w)`` over the K-1 interval midpoints: one
compare-and-add per midpoint on the VPU, no gather. Per-entry sums and
counts are masked reductions over each (rows, 128) tile, kept as
128-lane partials that accumulate across the sequential grid; the
wrapper folds the lanes. One pass of the kernel yields everything the
centroid recenter step needs.

The flat weights are viewed as (N/128, 128) so every block is a legal
TPU tile, including under ``vmap`` over a layer stack, where a 1-D
block would not be.

HBM traffic: one read of w + one write of a (int8) per iteration —
the same arrays the training step already touches.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.lutq_matmul import unroll_small

_LANES = 128


def _kernel(mid_ref, w_ref, a_ref, sums_ref, counts_ref, *, n_dict: int,
            bn: int, n_valid: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        sums_ref[...] = jnp.zeros_like(sums_ref)
        counts_ref[...] = jnp.zeros_like(counts_ref)

    w = w_ref[...].astype(jnp.float32)          # (rows, 128)
    # assignment = number of midpoints strictly below w (ties -> lower)
    a = jax.lax.fori_loop(
        0, n_dict - 1,
        lambda k, a: a + (mid_ref[0, k] < w).astype(jnp.int32),
        jnp.zeros(w.shape, jnp.int32), unroll=unroll_small(n_dict - 1))
    a_ref[...] = a.astype(jnp.int8)
    if n_valid % bn:
        # ragged tail: zero-pad entries (global index >= n_valid) must not
        # enter the statistics. n_valid is trace-static, so full blocks
        # compile with no masking at all.
        row = jax.lax.broadcasted_iota(jnp.int32, w.shape, 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, w.shape, 1)
        a = jnp.where(i * bn + row * _LANES + lane < n_valid, a, n_dict)

    def stat(k, carry):
        m = a == k
        sums_ref[pl.ds(k, 1), :] += jnp.sum(jnp.where(m, w, 0.0), axis=0,
                                            keepdims=True)
        counts_ref[pl.ds(k, 1), :] += jnp.sum(m.astype(jnp.int32), axis=0,
                                              keepdims=True)
        return carry

    jax.lax.fori_loop(0, n_dict, stat, 0, unroll=unroll_small(n_dict))


def kmeans_stats(
    w: jax.Array,   # (N,) flat weights
    d: jax.Array,   # (K,) sorted dictionary
    *,
    bn: int = 4096,
    interpret: bool = False,
):
    """Returns (assignments int8 (N,), sums f32 (K,), counts f32 (K,)).

    Any N is accepted: the flat weights are zero-padded onto the block
    grid and the tail block masks padded entries out of the sums/counts
    (assignments for the pad are computed but sliced off), so the result
    is element-exact with the unpadded kernel. ``bn`` must be a multiple
    of 128 (of 4096 when compiled: the int8 assignment tile is 32 x 128).
    Counts accumulate in int32, exact past 2^24 members.
    """
    N = w.shape[0]
    n_dict = d.shape[0]
    assert bn % _LANES == 0, bn
    bn = min(bn, -(-N // _LANES) * _LANES)
    Np = -(-N // bn) * bn
    if Np != N:
        w = jnp.pad(w, (0, Np - N))
    rows = bn // _LANES
    d = d.astype(jnp.float32)
    # at least one midpoint, so the SMEM operand is never empty; with a
    # one-entry dictionary it sits at +inf and every weight assigns to 0
    mid = ((d[:-1] + d[1:]) * 0.5 if n_dict > 1
           else jnp.full((1,), jnp.inf, jnp.float32))
    a, sums, counts = pl.pallas_call(
        functools.partial(_kernel, n_dict=n_dict, bn=bn, n_valid=N),
        grid=(Np // bn,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((rows, _LANES), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((rows, _LANES), lambda i: (i, 0)),
            pl.BlockSpec((n_dict, _LANES), lambda i: (0, 0)),
            pl.BlockSpec((n_dict, _LANES), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Np // _LANES, _LANES), jnp.int8),
            jax.ShapeDtypeStruct((n_dict, _LANES), jnp.float32),
            jax.ShapeDtypeStruct((n_dict, _LANES), jnp.int32),
        ],
        interpret=interpret,
    )(mid.reshape(1, -1), w.reshape(Np // _LANES, _LANES))
    a = a.reshape(Np)
    return ((a[:N] if Np != N else a), jnp.sum(sums, axis=1),
            jnp.sum(counts, axis=1).astype(jnp.float32))
