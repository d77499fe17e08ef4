"""LUT-Q: dictionary + assignment quantization with iterative k-means.

Implements the paper's Table 1 algorithm as pure JAX:

  step 1   Q = d[A]                      -> :func:`decode`
  step 2/3 STE forward + master update   -> :func:`quantize_ste`
  step 4   M k-means iterations on (A,d) -> :func:`kmeans_update`

Production note (TPU adaptation): the assignment step is 1-D nearest-
neighbour search. For a *sorted* dictionary the nearest entry is found by
bucketizing against the K-1 midpoints (``searchsorted``), which is
O(N log K) time and O(N) memory instead of the naive N x K distance
matrix. 1-D k-means preserves dictionary order across recenter steps, so
sortedness is an invariant we establish at init and keep thereafter.
Centroid recentering uses one-hot segment sums.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.spec import QuantSpec


class LutqState(NamedTuple):
    """Quantization state for one weight tensor (a pytree node).

    w: full-precision master weights (paper's W), any shape.
    d: dictionary, shape (K,), sorted ascending.
    a: assignments, int8, same shape as w (values in [0, K)).
    sid: resolved QuantPolicy rule id (int32) recording which rule
      produced this state, or None (legacy trees). Shaped like d's
      stack dims — d.shape[:-1] — so scan-over-layers slicing and vmap
      see a consistent leading axis; scalar for unstacked tensors. None
      flattens away as an empty pytree, so 3-field construction and old
      checkpoints keep working unchanged.
    act: frozen activation-quant record for this tensor's matmul
      boundary, or None (dynamic / fp activations). Shape
      d.shape[:-1] + (2,) = per-stack-slice [scale, qmax]: the
      calibration-frozen scale s and clip bound so the kernel boundary
      computes clip(round(x/s), -qmax, qmax). Trailing-axis layout keeps
      scan-over-layers slicing consistent with d (see sid).

    Serve-form convention: ``d.dtype == int8`` means the dictionary is a
    pow2 sign+exponent *plane* (see :func:`pow2_encode`), exactly as
    ``a.dtype == uint8`` means packed assignments.
    """

    w: jax.Array
    d: jax.Array
    a: jax.Array
    sid: Optional[jax.Array] = None
    act: Optional[jax.Array] = None


# ---------------------------------------------------------------------------
# step 1: decode tied weights
# ---------------------------------------------------------------------------

def decode(d: jax.Array, a: jax.Array) -> jax.Array:
    """Q = d[A] (paper step 1)."""
    return jnp.take(d, a.astype(jnp.int32), axis=0)


# ---------------------------------------------------------------------------
# steps 2/3: straight-through estimator
# ---------------------------------------------------------------------------

@jax.custom_vjp
def _ste_exact(w: jax.Array, q: jax.Array) -> jax.Array:
    return q


def _ste_fwd(w, q):
    return q, None


def _ste_bwd(_, g):
    # dC/dW = dC/dQ (paper step 3); q carries no gradient of its own.
    return g, jnp.zeros_like(g)


_ste_exact.defvjp(_ste_fwd, _ste_bwd)


def decode_any(d: jax.Array, a: jax.Array) -> jax.Array:
    """decode() for stacked dictionaries: d (..., K), a (..., *w_shape).

    Leading axes of d index independent tensors (scan-over-layers stacks,
    MoE experts) each with its own dictionary. An int8 ``d`` is a pow2
    sign+exponent plane (serve-form convention) and is decoded to exact
    ±2^k floats first.
    """
    if d.dtype == jnp.int8:
        d = pow2_decode(d)
    nstack = d.ndim - 1
    f = decode
    for _ in range(nstack):
        f = jax.vmap(f)
    return f(d, a)


def quantize_ste_any(w: jax.Array, d: jax.Array, a: jax.Array) -> jax.Array:
    """Stack-aware quantize_ste (see decode_any)."""
    q = decode_any(d, a).astype(w.dtype)
    return _ste_exact(w, q)


def quantize_ste(w: jax.Array, d: jax.Array, a: jax.Array) -> jax.Array:
    """Forward value is *exactly* Q = d[A]; gradient flows straight to w.

    This realizes the paper's split between step 2 (gradients w.r.t. Q)
    and step 3 (applying them to the full-precision W): autodiff through
    this function gives dC/dW = dC/dQ. Bit-exactness of the forward value
    matters for the multiplier-less claims (decoded weights must be exact
    dictionary entries), hence custom_vjp instead of the
    ``w + stop_grad(q - w)`` trick which reintroduces rounding.
    """
    q = decode(d, a).astype(w.dtype)
    return _ste_exact(w, q)


# ---------------------------------------------------------------------------
# dictionary constraints
# ---------------------------------------------------------------------------

def pow2_round(x: jax.Array, min_exp: int = -14, max_exp: int = 15) -> jax.Array:
    """Round magnitudes to the nearest power of two, keep sign.

    Entries exactly 0 stay 0 (used by the pruning constraint). Exponents
    are clamped so decoded bf16/f16 values stay representable.
    """
    mag = jnp.abs(x)
    safe = jnp.where(mag > 0, mag, 1.0)
    # Round in log-space: nearest power of two of m is 2^round(log2 m).
    e = jnp.clip(jnp.round(jnp.log2(safe)), min_exp, max_exp)
    p = jnp.exp2(e)
    return jnp.where(mag > 0, jnp.sign(x) * p, 0.0).astype(x.dtype)


# Exponent window shared by pow2_round and the sign+exponent plane
# encoding below. 30 = (POW2_MAX_EXP - POW2_MIN_EXP + 1) codes fit int8.
POW2_MIN_EXP = -14
POW2_MAX_EXP = 15


def pow2_encode(d: jax.Array) -> jax.Array:
    """Encode a pow2-constrained dictionary as an int8 sign+exponent plane.

    Per entry: code 0 for an exact zero (pruning slot); otherwise
    ``sign(entry) * (exponent - POW2_MIN_EXP + 1)`` with the exponent in
    [POW2_MIN_EXP, POW2_MAX_EXP], so |code| ∈ [1, 30]. Same shape as the
    input (stack axes pass through), and the serve tree stores *only*
    this plane — 1 byte/entry instead of 4 — which is what the shift-add
    kernel consumes. Inverse: :func:`pow2_decode` (exact round-trip for
    in-range pow2 entries).
    """
    mag = jnp.abs(d).astype(jnp.float32)
    safe = jnp.where(mag > 0, mag, 1.0)
    e = jnp.clip(jnp.round(jnp.log2(safe)), POW2_MIN_EXP, POW2_MAX_EXP)
    code = jnp.sign(d).astype(jnp.int32) * (e.astype(jnp.int32)
                                            - POW2_MIN_EXP + 1)
    return jnp.where(mag > 0, code, 0).astype(jnp.int8)


def pow2_decode(code: jax.Array, dtype=jnp.float32) -> jax.Array:
    """Decode an int8 sign+exponent plane back to exact ±2^k / 0 floats.

    2^e for integer e ∈ [-14, 15] is exact in f32, so every decoded
    entry is bit-exactly a power of two (the multiplier-less invariant).
    """
    mag = jnp.abs(code.astype(jnp.int32))
    val = (jnp.exp2((mag - 1 + POW2_MIN_EXP).astype(jnp.float32))
           * jnp.sign(code).astype(jnp.float32))
    return jnp.where(mag > 0, val, 0.0).astype(dtype)


def _fixed_dictionary(spec: QuantSpec, dtype=jnp.float32) -> jax.Array:
    if spec.constraint == "binary":
        return jnp.array([-1.0, 1.0], dtype=dtype)
    if spec.constraint == "ternary":
        return jnp.array([-1.0, 0.0, 1.0], dtype=dtype)
    raise ValueError(spec.constraint)


def apply_constraint(d: jax.Array, spec: QuantSpec) -> jax.Array:
    """Project a (sorted) dictionary onto the spec's constraint set."""
    if spec.constraint == "pow2":
        d = pow2_round(d)
    elif spec.fixed_dictionary:
        d = _fixed_dictionary(spec, d.dtype)
    if spec.prune_frac > 0.0:
        # Pin the entry nearest zero to exactly zero.
        zi = jnp.argmin(jnp.abs(d))
        d = d.at[zi].set(0.0)
    # Constraints (esp. pow2 rounding) can produce duplicates but are
    # monotone, so sortedness is preserved; enforce it defensively.
    return jnp.sort(d)


# ---------------------------------------------------------------------------
# assignment: 1-D nearest neighbour over a sorted dictionary
# ---------------------------------------------------------------------------

def assign(w: jax.Array, d: jax.Array) -> jax.Array:
    """A_ij = argmin_k |W_ij - d_k| for sorted d. Returns int8.

    Bucketize against midpoints between consecutive dictionary entries:
    entry k owns the interval (m_{k-1}, m_k]. Ties at an exact midpoint
    resolve to the lower index (matches argmin-first semantics).
    Operates on w in its native (possibly sharded) shape — no reshape.
    """
    mid = (d[:-1] + d[1:]) * 0.5
    idx = jnp.searchsorted(mid, w.astype(d.dtype), side="left")
    return idx.astype(jnp.int8)


def _fixed_scale_update(d: jax.Array, w, a, spec: QuantSpec) -> jax.Array:
    """TWN/BWN-style per-tensor scale for fixed dictionaries.

    alpha = mean |w| over weights assigned to nonzero entries; effective
    dictionary = alpha * sign pattern. With spec.fixed_scale=False the
    literal {-1[,0],1} dictionary is kept (BinaryConnect)."""
    if not spec.fixed_scale:
        return d
    sign = jnp.sign(d)
    aw = jnp.abs(w.astype(jnp.float32))
    if spec.constraint == "ternary":  # TWN rule, anchored to the masters
        # Delta = 0.7 E|w|; alpha = E{|w| : |w| > Delta}. Anchoring the
        # threshold to the full master distribution (not the previous
        # alpha) avoids the all-zeros death spiral of the pure
        # nearest-assignment fixed point.
        delta = 0.7 * jnp.mean(aw)
        sel = aw > delta
        num = jnp.sum(jnp.where(sel, aw, 0.0))
        den = jnp.maximum(jnp.sum(sel.astype(jnp.float32)), 1.0)
        alpha = jnp.maximum(num / den, 1e-12)
    else:  # binary (BWN-scaled): alpha = E|w|
        alpha = jnp.maximum(jnp.mean(aw), 1e-12)
    return sign * alpha


def _prune_mask(w: jax.Array, prune_frac: float) -> jax.Array:
    """Boolean mask of weights forced to the zero entry (smallest |w|)."""
    flat = jnp.abs(w.ravel())
    k = int(round(prune_frac * flat.size))
    if k <= 0:
        return jnp.zeros(w.shape, dtype=bool)
    # threshold = k-th smallest magnitude, via top_k on negated values:
    # O(N log k) selection instead of a full O(N log N) sort per step.
    thresh = -jax.lax.top_k(-flat, k)[0][k - 1]
    return jnp.abs(w) <= thresh


# ---------------------------------------------------------------------------
# step 4: M k-means iterations
# ---------------------------------------------------------------------------

def kmeans_update(w: jax.Array, d: jax.Array, spec: QuantSpec) -> Tuple[jax.Array, jax.Array]:
    """Run M k-means iterations on the (sorted) dictionary; reassign.

    Returns (new_d, new_a). Empty clusters keep their previous centroid
    (documented deviation: paper does not specify empty-cluster handling).
    For constrained dictionaries the recentered centroids are projected
    back onto the constraint set each iteration (paper: "rounding the
    output of the k-means algorithm to powers-of-two").
    """
    K = spec.K
    flat = w.ravel().astype(jnp.float32)

    if spec.prune_frac > 0.0:
        pmask = _prune_mask(w, spec.prune_frac).ravel()
    else:
        pmask = None

    def one_iter(d, _):
        a = jnp.searchsorted((d[:-1] + d[1:]) * 0.5, flat, side="left")
        if pmask is not None:
            zi = jnp.argmin(jnp.abs(d))
            a = jnp.where(pmask, zi, a)
        if spec.fixed_dictionary:
            return _fixed_scale_update(d, flat, a, spec), None
        onehot = jax.nn.one_hot(a, K, dtype=jnp.float32)  # (N, K)
        counts = onehot.sum(axis=0)
        sums = onehot.T @ flat
        new_d = jnp.where(counts > 0, sums / jnp.maximum(counts, 1.0), d)
        new_d = apply_constraint(new_d.astype(d.dtype), spec)
        return new_d, None

    d, _ = jax.lax.scan(one_iter, d, None, length=spec.kmeans_iters)

    a = assign(w, d)
    if pmask is not None:
        zi = jnp.argmin(jnp.abs(d)).astype(jnp.int8)
        a = jnp.where(pmask.reshape(w.shape), zi, a)
    return d, a


def kmeans_update_segsum(w: jax.Array, d: jax.Array, spec: QuantSpec) -> Tuple[jax.Array, jax.Array]:
    """Sharding-friendly variant of :func:`kmeans_update` for big tensors.

    No reshape, no one-hot, no scatter: assignments come from an
    elementwise bucketize on w *in place*, and per-entry sums/counts are
    K masked reductions (lax.map over K). Every op is elementwise or a
    full reduction, so XLA partitions it along whatever sharding w
    already has — this is what keeps the paper's per-minibatch step 4
    cheap on 100B-parameter FSDP-sharded weights (the scatter/segment_sum
    formulation forces an SPMD full rematerialization). Identical results
    to :func:`kmeans_update`. On-TPU, the Pallas ``kmeans_stats`` kernel
    fuses all K reductions into one pass over w.
    """
    K = spec.K
    w32 = w.astype(jnp.float32)
    pmask = _prune_mask(w, spec.prune_frac) if spec.prune_frac > 0 else None

    def assign_ids(d):
        a = jnp.searchsorted((d[:-1] + d[1:]) * 0.5, w32, side="left")
        if pmask is not None:
            zi = jnp.argmin(jnp.abs(d))
            a = jnp.where(pmask, zi, a)
        return a

    def one_iter(d, _):
        if spec.fixed_dictionary:
            return _fixed_scale_update(d, w32, assign_ids(d), spec), None
        a = assign_ids(d)

        def stat(k):
            m = a == k
            return (jnp.sum(jnp.where(m, w32, 0.0)),
                    jnp.sum(m.astype(jnp.float32)))

        sums, counts = jax.lax.map(stat, jnp.arange(K))
        new_d = jnp.where(counts > 0, sums / jnp.maximum(counts, 1.0), d)
        new_d = apply_constraint(new_d.astype(d.dtype), spec)
        return new_d, None

    d, _ = jax.lax.scan(one_iter, d, None, length=spec.kmeans_iters)
    a = assign_ids(d).astype(jnp.int8)
    return d, a


def kmeans_update_stats(w: jax.Array, d: jax.Array, spec: QuantSpec,
                        *, bn: int = 4096, interpret: Optional[bool] = None
                        ) -> Tuple[jax.Array, jax.Array]:
    """:func:`kmeans_update` through the Pallas ``kmeans_stats`` kernel.

    One fused pass per iteration computes assignments and per-entry
    sums/counts (one HBM read of w, one int8 write of a), instead of the
    K separate masked reductions of :func:`kmeans_update_segsum`. The
    constraints the kernel cannot express are composed around its stats:

      * fixed dictionaries (binary/ternary) take the kernel's assignment
        but recenter via :func:`_fixed_scale_update` (cheap reductions);
      * prune masks move each pruned weight's contribution from its
        kernel-assigned cluster to the zero entry with K masked
        correction reductions — only paid when ``prune_frac > 0``;
      * pow2/sort projections run on the (K,)-sized dictionary on host
        as in the reference.

    Same results as :func:`kmeans_update` / ``kmeans_update_segsum`` up
    to f32 accumulation order (the kernel reduces block-partials over a
    sequential grid).

    ``bn`` is the kernel's block: its ``(bn, K)`` one-hot and compare
    tiles take about 1 KiB of VMEM per element at K <= 128 and twice
    that at K = 256, so 4096 stays inside v5e's 16 MiB scoped VMEM for
    every dictionary size (65536 needed 66 MiB).
    """
    from repro.kernels import ops  # local: kernels.ops imports this module

    K = spec.K
    flat = w.ravel().astype(jnp.float32)
    pmask = (_prune_mask(w, spec.prune_frac).ravel()
             if spec.prune_frac > 0.0 else None)

    def stats(d):
        a, sums, counts = ops.kmeans_stats(flat, d, bn=bn,
                                           interpret=interpret)
        a = a.astype(jnp.int32)
        if pmask is not None:
            zi = jnp.argmin(jnp.abs(d))

            def corr(k):
                m = pmask & (a == k)
                return (jnp.sum(jnp.where(m, flat, 0.0)),
                        jnp.sum(m.astype(jnp.float32)))

            csum, ccnt = jax.lax.map(corr, jnp.arange(K))
            sums = (sums - csum).at[zi].add(jnp.sum(csum))
            counts = (counts - ccnt).at[zi].add(jnp.sum(ccnt))
            a = jnp.where(pmask, zi, a)
        return a, sums, counts

    def one_iter(d, _):
        a, sums, counts = stats(d)
        if spec.fixed_dictionary:
            return _fixed_scale_update(d, flat, a, spec), None
        new_d = jnp.where(counts > 0, sums / jnp.maximum(counts, 1.0), d)
        return apply_constraint(new_d.astype(d.dtype), spec), None

    d, _ = jax.lax.scan(one_iter, d, None, length=spec.kmeans_iters)
    a, _, _ = stats(d)
    return d, a.astype(jnp.int8).reshape(w.shape)


_SEGSUM_THRESHOLD = 1 << 16

_KMEANS_IMPLS = {
    "dense": kmeans_update,
    "segsum": kmeans_update_segsum,
    "stats": kmeans_update_stats,
}


def resolve_kmeans_impl(n: int, impl: Optional[str] = None) -> str:
    """Structural step-4 implementation choice for an n-element leaf.

    ``None`` resolves: dense one-hot below ``_SEGSUM_THRESHOLD``; above
    it the fused Pallas ``kmeans_stats`` kernel on TPU, and the
    sharding-friendly masked-reduction ``segsum`` form elsewhere (CPU /
    interpret — where the kernel would just emulate the same reductions
    slower). Explicit names force a path (tests, benches).
    """
    if impl is not None:
        if impl not in _KMEANS_IMPLS:
            raise ValueError(
                f"unknown kmeans impl {impl!r}; expected one of "
                f"{tuple(_KMEANS_IMPLS)}")
        return impl
    if n < _SEGSUM_THRESHOLD:
        return "dense"
    return "stats" if jax.default_backend() == "tpu" else "segsum"


def update_state(state: LutqState, spec: QuantSpec,
                 impl: Optional[str] = None) -> LutqState:
    """Paper step 4 applied to a LutqState (after the optimizer touched w).

    ``impl``: force "dense" | "segsum" | "stats"; default structural
    (see :func:`resolve_kmeans_impl`).
    """
    fn = _KMEANS_IMPLS[resolve_kmeans_impl(state.w.size, impl)]
    d, a = fn(state.w, state.d, spec)
    return LutqState(w=state.w, d=d, a=a, sid=state.sid, act=state.act)


# ---------------------------------------------------------------------------
# nested dictionaries: coarsen K entries to K' over the same assignments
# ---------------------------------------------------------------------------

def coarsen_dictionary(d: jax.Array, a: jax.Array, k_out: int,
                       *, iters: int = 8) -> Tuple[jax.Array, jax.Array]:
    """Re-cluster the K dictionary entries into ``k_out`` coarse entries.

    Weighted 1-D k-means over the *entries themselves* (weights = how
    many weights each entry serves, from the assignment histogram), so a
    low-bit "draft view" of a served tensor costs only a second tiny
    dictionary plus remapped indices — the original assignments ``a``
    never change, they just compose with the returned fine->coarse map:
    ``a_draft = fmap[a]``. Empty fine entries keep a vanishing weight so
    they still land in a defined coarse cell (the map must be total).

    d: (K,) sorted dictionary (f32); a: int assignments of any shape
    (only used for usage counts). Returns ``(d_coarse (k_out,) sorted
    f32, fmap (K,) int32 monotone)``. Monotonicity of the map follows
    from both dictionaries being sorted — nested views preserve the
    order structure the packed kernels rely on.
    """
    K = d.shape[-1]
    if k_out > K:
        raise ValueError(f"k_out {k_out} exceeds dictionary size {K}")
    d32 = d.astype(jnp.float32)
    counts = jnp.zeros((K,), jnp.float32).at[
        a.astype(jnp.int32).ravel()].add(1.0)
    w = counts + 1e-3

    # weighted-quantile init: sorted by construction, duplicates spread
    # by a hair exactly like init_dictionary
    cum = jnp.cumsum(w)
    targets = (jnp.arange(k_out, dtype=jnp.float32) + 0.5) / k_out * cum[-1]
    dc = d32[jnp.clip(jnp.searchsorted(cum, targets), 0, K - 1)]
    eps = 1e-8 * (1.0 + jnp.abs(dc))
    dc = jnp.sort(dc + eps * jnp.arange(k_out, dtype=jnp.float32))

    def one_iter(dc, _):
        g = jnp.searchsorted((dc[:-1] + dc[1:]) * 0.5, d32, side="left")
        oh = jax.nn.one_hot(g, k_out, dtype=jnp.float32)        # (K, k_out)
        cnt = (w[:, None] * oh).sum(axis=0)
        s = (w[:, None] * oh * d32[:, None]).sum(axis=0)
        new = jnp.where(cnt > 0, s / jnp.maximum(cnt, 1e-6), dc)
        return jnp.sort(new), None

    dc, _ = jax.lax.scan(one_iter, dc, None, length=iters)
    fmap = jnp.searchsorted((dc[:-1] + dc[1:]) * 0.5, d32,
                            side="left").astype(jnp.int32)
    return dc, fmap


# ---------------------------------------------------------------------------
# initialization
# ---------------------------------------------------------------------------

_QUANTILE_SAMPLE = 1 << 20


def init_dictionary(w: jax.Array, spec: QuantSpec) -> jax.Array:
    """Initialize a sorted dictionary from the weight distribution.

    Free/pow2 dictionaries start at the (k+0.5)/K quantiles of w (a good
    1-D k-means init that is sorted by construction); fixed dictionaries
    are the constraint set itself. Tensors above ``_QUANTILE_SAMPLE``
    elements take the quantiles of an evenly strided sample of about
    that many weights: they only seed k-means, and sorting every weight
    of a full-width layer stack would need gigabytes of scratch.
    """
    if spec.fixed_dictionary:
        base = _fixed_dictionary(spec)
        if spec.fixed_scale:
            # TWN-compatible init: alpha0 = 1.4 E|w| puts the assignment
            # threshold (alpha/2) at TWN's Delta = 0.7 E|w|.
            alpha0 = 1.4 * jnp.mean(jnp.abs(w.astype(jnp.float32))) + 1e-12
            return base * alpha0
        return base
    flat = w.ravel().astype(jnp.float32)
    if flat.size > _QUANTILE_SAMPLE:
        flat = flat[::flat.size // _QUANTILE_SAMPLE]
    qs = (jnp.arange(spec.K, dtype=jnp.float32) + 0.5) / spec.K
    d = jnp.quantile(flat, qs)
    # Quantile init can duplicate on spiky distributions; spread exact
    # duplicates by a hair so intervals stay well-defined.
    eps = 1e-8 * (1.0 + jnp.abs(d))
    d = d + eps * jnp.arange(spec.K, dtype=jnp.float32)
    return apply_constraint(d.astype(jnp.float32), spec)


def init_state(w: jax.Array, spec: QuantSpec) -> LutqState:
    d = init_dictionary(w, spec)
    d, a = _KMEANS_IMPLS[resolve_kmeans_impl(w.size)](w, d, spec)
    return LutqState(w=w, d=d, a=a)
