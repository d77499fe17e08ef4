"""Pallas paged-attention decode kernel: walk the block table, not a gather.

The paged serving path (runtime/paged_kv.py) keeps the KV cache in
fixed-size physical pages behind per-slot block tables. Before this
kernel, every decode step gathered the whole table into a contiguous
``(B, NB*page, Hkv, dh)`` tensor — and for int8 KV dequantized *all* of
it to bf16 — per layer, per step. At decode batch sizes the KV stream
dominates the byte traffic, so that materialization was pure waste:
``NB*page`` positions read regardless of how many are live.

This kernel walks the block table directly. The grid is ``(B, NB)``
with the page dimension innermost; the block table and ``cache_len``
ride as scalar-prefetch operands so each step's BlockSpec index_map can
resolve ``logical page j of row b`` to a physical page and DMA exactly
that ``(page, Hkv, dh)`` tile — every KV head of the page at once, the
only block of the ``(P, page, Hkv, dh)`` pool that meets the TPU's
last-two-dims tiling rule (full ``Hkv`` and full ``dh``; head_dim 80 is
not a lane multiple). Pages with no live positions — beyond
``cache_len``, or wholly behind the sliding window — are redirected to
the trash page (physical page 0) in the index_map, so consecutive dead
steps re-request the same block and the pipeline never streams them
from HBM. int8 pages dequantize in-kernel from the per-token scale
planes, page by page; the full dequantized cache never exists.

Single-pass numerics: **bit-identical to the gather oracle**
(``ops.paged_attention(..., backend="gather")``, i.e. ``gather_pages``
+ ``nn.attention.decode_attention``) in interpret mode. Decode has a
single query row, so instead of the multi-block online-softmax
rescaling of ``flash_attn.py`` the finalize step replays
``decode_attention``'s op sequence per KV head on a VMEM scratch row —
same contraction (batch-1 einsums with an f32 accumulator, the form
Mosaic lowers), same ``NEG_INF`` masking of positions ``>= cache_len``
and behind-window positions, same f32 softmax and f32 V accumulation.
Masked columns contribute exactly ``exp(NEG_INF - m) = 0.0`` and dead
pages are zero-filled in scratch, so skipped pages are exact no-ops.

VMEM: the single-pass scratch holds every head's dequantized K and V
row (``2 * Hkv * NB*page * dh`` entries). When that outgrows the budget
(``vmem_budget_bytes``, default 16 MiB) ``paged_attention_tpu`` runs
the **two-pass** kernel (``vmem_plan`` decides): pass A streams K page
by page into an f32 score scratch and softmaxes the whole row once the
last page is in; pass B streams V page by page and accumulates
``p_page @ v_page`` into an f32 output. The softmax is exact (not
online), but summing the output over pages reorders the V reduction,
so the two-pass result matches the oracle to f32 rounding, not bit for
bit.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.nn.attention import NEG_INF

TRASH_PAGE = 0

#: default per-core VMEM budget for the decode kernel's scratch (bytes).
DEFAULT_VMEM_BUDGET = 16 * 2**20

#: VMEM the kernel may use on top of its scratch: double-buffered page,
#: scale, query and output blocks, plus the compiler's own temporaries.
_VMEM_HEADROOM = 8 * 2**20


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def vmem_plan(nb: int, page: int, dh: int, g: int, *, quant: bool,
              kv_itemsize: int, hkv: int = 1,
              budget_bytes: Optional[int] = None, tiled: bool = False
              ) -> dict:
    """Pick single-pass or two-pass scratch for one batch row.

    Single-pass scratch is every head's dequantized K and V row,
    ``2 * hkv * nb*page * dh`` entries (bf16 under int8 quant, else the
    pool dtype). When that exceeds ``budget_bytes`` the plan switches to
    the two-pass kernel: an f32 score scratch ``(hkv, nb, g, page)``
    plus an f32 output accumulator ``(hkv, g, dh)``. ``tiled=True``
    (compiled for the TPU) counts what VMEM really holds: the last two
    dims of every buffer padded to (8 sublanes, 128 lanes).

    Pure host arithmetic so tests can probe the decision without
    running the kernel."""
    budget = DEFAULT_VMEM_BUDGET if budget_bytes is None else int(budget_bytes)
    sub, lane = (8, 128) if tiled else (1, 1)
    scr_item = 2 if quant else kv_itemsize
    lanes_dh = _round_up(dh, lane)
    single = 2 * hkv * _round_up(nb * page, sub) * lanes_dh * scr_item
    multi = 4 * hkv * (nb * _round_up(g, sub) * _round_up(page, lane)
                       + _round_up(g, sub) * lanes_dh)
    return {"multipass": single > budget, "single_bytes": single,
            "multi_bytes": multi}


def _page_live(start: jax.Array, page: int, cl: jax.Array,
               window: Optional[int]) -> jax.Array:
    """Does logical page [start, start+page) hold any attended position?"""
    live = start < cl
    if window is not None:
        live = jnp.logical_and(live, start + page > cl - window)
    return live


def _valid(pos: jax.Array, cl: jax.Array, window: Optional[int]):
    valid = pos < cl
    if window is not None:
        valid &= pos >= cl - window
    return valid


def _head(ref, scale_ref, h: int, oracle_shapes: bool) -> jax.Array:
    """One KV head of the current page, ``(page, dh)``, dequantized the
    way the oracle does it: int8 -> bf16, times the bf16 per-token
    scale (promotion to f32 happens inside the score einsum). The
    interpreter rounds the product to bf16 explicitly: XLA may keep it
    at f32 when it fuses it into its consumer (Mosaic has no
    ``reduce_precision``, and rounds a bf16 product anyway)."""
    x = ref[0, :, h, :]
    if scale_ref is not None:
        x = x.astype(jnp.bfloat16) * scale_ref[0, :, h][:, None]
        if oracle_shapes:
            x = jax.lax.reduce_precision(
                x.astype(jnp.float32), exponent_bits=8,
                mantissa_bits=7).astype(jnp.bfloat16)
    return x


def _scores(q: jax.Array, k: jax.Array, oracle_shapes: bool) -> jax.Array:
    """``decode_attention``'s score einsum for one head: q (G, dh),
    k (W, dh) -> f32 (1, G, W), rounded to the einsum's result dtype
    first like the oracle's ``einsum(...).astype(f32)``.

    Mosaic lowers one batch dim at most, with a 32-bit accumulator. The
    interpreter instead replays the oracle's two singleton batch dims
    (b, h) — scores (1, 1, G, W) — which keep XLA's CPU contraction and
    softmax order identical to the batched oracle; a one-batch-dim
    einsum drifts by an ulp there."""
    if oracle_shapes:
        s = jnp.einsum("bhgd,bkhd->bhgk", q[None, None], k[None, :, None])
        return s.astype(jnp.float32)
    s = jnp.einsum("bgd,bkd->bgk", q[None], k[None],
                   preferred_element_type=jnp.float32)
    return s.astype(jnp.result_type(q.dtype, k.dtype)).astype(jnp.float32)


def _pv(p: jax.Array, v: jax.Array, oracle_shapes: bool) -> jax.Array:
    """f32 probabilities (G, W), with any leading singleton dims, times
    (W, dh) values -> f32 (G, dh) (``oracle_shapes`` as in
    :func:`_scores`)."""
    v = v.astype(jnp.float32)
    if oracle_shapes:
        p = p.reshape((1, 1) + p.shape[-2:])
        return jnp.einsum("bhgk,bkhd->bhgd", p, v[None, :, None])[0, 0]
    p = p.reshape((1,) + p.shape[-2:])
    return jnp.einsum("bgk,bkd->bgd", p, v[None],
                      preferred_element_type=jnp.float32)[0]


def _decode_kernel(blk_ref, cl_ref, q_ref, k_ref, v_ref, *rest, page, nb,
                   hkv, window, scale, quant, oracle_shapes):
    if quant:
        ks_ref, vs_ref, o_ref, k_scr, v_scr = rest
    else:
        (o_ref, k_scr, v_scr), ks_ref, vs_ref = rest, None, None
    b = pl.program_id(0)
    j = pl.program_id(1)
    cl = cl_ref[b]
    start = pl.multiple_of(j * page, page)
    live = _page_live(start, page, cl, window)

    @pl.when(live)
    def _copy_page():
        for h in range(hkv):
            k_scr[h, pl.ds(start, page), :] = _head(
                k_ref, ks_ref, h, oracle_shapes).astype(k_scr.dtype)
            v_scr[h, pl.ds(start, page), :] = _head(
                v_ref, vs_ref, h, oracle_shapes).astype(v_scr.dtype)

    @pl.when(jnp.logical_not(live))
    def _zero_page():
        # dead pages must be *finite* in scratch: their softmax weight is
        # exactly 0.0 and 0.0 * finite == 0.0 matches the oracle's masked
        # gather contribution bit-for-bit (0.0 * NaN would not)
        z = jnp.zeros((hkv, page, k_scr.shape[2]), k_scr.dtype)
        k_scr[:, pl.ds(start, page), :] = z
        v_scr[:, pl.ds(start, page), :] = z

    @pl.when(j == nb - 1)
    def _finalize():
        pos = jax.lax.broadcasted_iota(jnp.int32, (1, 1, nb * page), 2)
        valid = _valid(pos, cl, window)

        # a loop, not an unrolled Python for: each head's contraction
        # stays a computation of its own, as in the per-head oracle
        def one_head(h, carry):
            s = _scores(q_ref[0, h] * scale, k_scr[h], oracle_shapes)
            p = jax.nn.softmax(jnp.where(valid, s, NEG_INF), axis=-1)
            o_ref[0, h] = _pv(p, v_scr[h], oracle_shapes).astype(o_ref.dtype)
            return carry

        jax.lax.fori_loop(0, hkv, one_head, 0)


def _decode_kernel_twopass(blk_ref, cl_ref, q_ref, k_ref, v_ref, *rest,
                           page, nb, hkv, window, scale, quant,
                           oracle_shapes):
    """VMEM-bounded twin of ``_decode_kernel``: grid steps j < nb are
    pass A (scores of K page j; exact softmax over the whole row on the
    last page), steps j >= nb are pass B (accumulate probabilities of
    page ``j - nb`` times its V page)."""
    if quant:
        ks_ref, vs_ref, o_ref, s_scr, acc = rest
    else:
        (o_ref, s_scr, acc), ks_ref, vs_ref = rest, None, None
    b = pl.program_id(0)
    j = pl.program_id(1)
    cl = cl_ref[b]
    in_a = j < nb
    jb = jnp.maximum(j - nb, 0)
    live_a = jnp.logical_and(in_a, _page_live(j * page, page, cl, window))
    live_b = jnp.logical_and(jnp.logical_not(in_a),
                             _page_live(jb * page, page, cl, window))

    @pl.when(live_a)
    def _score_page():
        for h in range(hkv):
            s = _scores(q_ref[0, h] * scale,
                        _head(k_ref, ks_ref, h, oracle_shapes), oracle_shapes)
            s_scr[h, j] = s.reshape(s.shape[-2:])

    @pl.when(jnp.logical_and(in_a, jnp.logical_not(live_a)))
    def _zero_score_page():
        # finite filler: these columns are NEG_INF-masked before softmax
        s_scr[:, j] = jnp.zeros(s_scr.shape[:1] + s_scr.shape[2:],
                                jnp.float32)

    @pl.when(j == nb - 1)
    def _softmax():
        jj = jax.lax.broadcasted_iota(jnp.int32, (nb, 1, page), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (nb, 1, page), 2)
        valid = _valid(jj * page + col, cl, window)
        for h in range(hkv):
            s = jnp.where(valid, s_scr[h], NEG_INF)         # (nb, G, page)
            m = jnp.max(jnp.max(s, axis=2, keepdims=True), axis=0,
                        keepdims=True)
            e = jnp.exp(s - m)
            den = jnp.sum(jnp.sum(e, axis=2, keepdims=True), axis=0,
                          keepdims=True)
            s_scr[h] = e / den
        acc[...] = jnp.zeros_like(acc)

    @pl.when(live_b)
    def _accumulate_page():
        for h in range(hkv):
            acc[h] += _pv(s_scr[h, jb],
                          _head(v_ref, vs_ref, h, oracle_shapes),
                          oracle_shapes)

    @pl.when(j == 2 * nb - 1)
    def _emit():
        o_ref[0] = acc[...].astype(o_ref.dtype)


def paged_attention_tpu(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    block: jax.Array,
    cache_len: jax.Array,
    *,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    interpret: bool = False,
    vmem_budget_bytes: Optional[int] = None,
) -> jax.Array:
    """One-token paged decode attention through the block table.

    q: (B, 1, H, dh); k_pool/v_pool: (P, page, Hkv, dh) physical pages
    (int8 when ``k_scale``/``v_scale`` planes (P, page, Hkv) are given);
    block: (B, NB) int32 block table; cache_len: (B,) or scalar int32.
    Returns (B, 1, H, dh) in q.dtype; single-pass results are
    bit-identical to ``decode_attention(q, gather_pages(...), ...)`` in
    interpret mode.

    ``vmem_budget_bytes`` bounds the single-pass scratch (default
    ``DEFAULT_VMEM_BUDGET``); rows whose scratch outgrows it run the
    two-pass kernel picked by :func:`vmem_plan`.
    """
    B, _, H, dh = q.shape
    _, page, Hkv, _ = k_pool.shape
    NB = block.shape[1]
    G = H // Hkv
    if scale is None:
        scale = dh ** -0.5
    quant = k_scale is not None
    cl = jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32).reshape(-1), (B,))
    qr = q.reshape(B, Hkv, G, dh)
    plan = vmem_plan(NB, page, dh, G, quant=quant, hkv=Hkv,
                     kv_itemsize=k_pool.dtype.itemsize,
                     budget_bytes=vmem_budget_bytes, tiled=not interpret)
    twopass = plan["multipass"]

    def live_page(b, j, blk, cln):
        live = _page_live(j * page, page, cln[b], window)
        return jnp.where(live, blk[b, j], TRASH_PAGE)

    if twopass:
        def k_page(b, j, blk, cln):
            return live_page(b, jnp.minimum(j, NB - 1), blk, cln) * (j < NB)

        def v_page(b, j, blk, cln):
            return live_page(b, jnp.maximum(j - NB, 0), blk, cln) * (j >= NB)
        steps = 2 * NB
        kernel, scratch = _decode_kernel_twopass, [
            pltpu.VMEM((Hkv, NB, G, page), jnp.float32),
            pltpu.VMEM((Hkv, G, dh), jnp.float32)]
    else:
        k_page = v_page = live_page
        steps = NB
        scr_dtype = jnp.bfloat16 if quant else k_pool.dtype
        kernel, scratch = _decode_kernel, [
            pltpu.VMEM((Hkv, NB * page, dh), scr_dtype),
            pltpu.VMEM((Hkv, NB * page, dh), scr_dtype)]

    def spec(page_of, ndim):
        return pl.BlockSpec(
            (1, page, Hkv, dh)[:ndim],
            lambda b, j, blk, cln: (page_of(b, j, blk, cln),) + (0,) * (ndim - 1))

    head = pl.BlockSpec((1, Hkv, G, dh), lambda b, j, blk, cln: (b, 0, 0, 0))
    in_specs = [head, spec(k_page, 4), spec(v_page, 4)]
    operands = [qr, k_pool, v_pool]
    if quant:
        in_specs += [spec(k_page, 3), spec(v_page, 3)]
        operands += [k_scale, v_scale]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, steps),
        in_specs=in_specs,
        out_specs=head,
        scratch_shapes=scratch,
    )
    scratch_bytes = plan["multi_bytes" if twopass else "single_bytes"]
    out = pl.pallas_call(
        functools.partial(kernel, page=page, nb=NB, hkv=Hkv, window=window,
                          scale=scale, quant=quant, oracle_shapes=interpret),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=scratch_bytes + _VMEM_HEADROOM),
        interpret=interpret,
        name="paged_attn",
    )(block, cl, *operands)
    return out.reshape(B, 1, H, dh)


def pages_read_per_step(cache_len: int, page: int, nb: int,
                        window: Optional[int] = None) -> int:
    """Modeled distinct KV pages the kernel streams for one row's decode
    step (the gather oracle always reads ``nb``). Dead/out-of-window
    pages collapse onto the trash page, which the pipeline requests but
    never re-streams between consecutive grid steps; count it as one
    page when any step is dead."""
    if cache_len <= 0:
        return 1
    first = 0 if window is None else max(0, (cache_len - window) // page)
    last = (min(cache_len, nb * page) - 1) // page
    live = max(0, last - first + 1)
    dead = nb - live
    return live + (1 if dead else 0)
