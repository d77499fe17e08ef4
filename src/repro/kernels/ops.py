"""Kernel execution-backend layer: jit'd wrappers + the ``lutq_dot`` entry.

``interpret`` defaults to True on CPU (this container) and False on TPU,
so the same call sites work in tests and production.

The raw Pallas kernels (``lutq_matmul``, ``lutq_gemv_packed``) demand
tile-multiple shapes, 2-D operands and a single shared dictionary.
:func:`lutq_dot` is the entry point the model layer actually calls: it
resolves a *backend* per quantized leaf, pads/reshapes real-world shapes
onto the kernel grids, consumes serve-packed uint8 assignments directly
(no unpack round-trip), and falls back to the dense-decode reference
wherever a kernel cannot apply (training STE, stacked per-layer /
per-expert dictionaries, transposed packed layouts).

Backends
--------
``decode``   dense reference: ``x @ d[A]`` with the STE master when
             training — the numerics oracle for everything else.
``fused``    :mod:`repro.kernels.lutq_matmul` — int8 assignments stream
             HBM->VMEM at 1 byte/weight and decode against the
             SMEM-resident dictionary in front of the MXU.
``packed4``  :mod:`repro.kernels.lutq_gemv_packed` — 4-bit pairs stay
             packed in HBM (0.5 byte/weight), unpacked in VMEM.
``pow2``     :mod:`repro.kernels.lutq_shift` — pow2 dictionaries stored
             as int8 sign+exponent planes, applied as integer shifted
             adds over int8-quantized activations; the only fp multiply
             is the O(M·N) epilogue scale. Bit-identical to its integer
             decode oracle under any tiling (int32 accumulation).
``auto``     per-leaf structural resolution (see :func:`resolve_backend`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.lutq import LutqState, decode_any, quantize_ste_any
from repro.kernels.autotune import (
    KERNEL_OF_BACKEND,
    TileConfig,
    TuningCache,
    make_key,
    platform_key,
)
from repro.kernels.kmeans_tpu import kmeans_stats as _kmeans_stats
from repro.kernels.lutq_gemv_packed import lutq_gemv_packed as _gemv_packed
from repro.kernels.lutq_matmul import lutq_matmul as _lutq_matmul
from repro.kernels.lutq_shift import lutq_shift as _lutq_shift
from repro.kernels.ref import (  # noqa: F401  (re-export for callers)
    lutq_shift_ref,
    pack4,
    pack4_kin,
    pow2_shift_scale,
    pow2_shift_weights,
    unpack4,
    unpack4_kin,
)

#: Backend names accepted by ``lutq_dot`` / policy rules / CLI flags.
BACKENDS = ("auto", "decode", "fused", "packed4", "pow2")

#: Default tiles when the tuning cache has no entry for a shape.
DEFAULT_TILE = TileConfig(bm=256, bn=256, bk=512)

# process-level tuning cache: ``lutq_dot`` consults it at trace time,
# ``--autotune cache|search`` fills it, ``serve_view`` / checkpoints
# persist it. Its monotonic version feeds the serving-jit lru keys (via
# :func:`tuning_fingerprint`) so late-arriving tiles force a re-trace.
_TUNING_CACHE = TuningCache()


def tuning_cache() -> TuningCache:
    """The process-level :class:`TuningCache` instance."""
    return _TUNING_CACHE


def tuning_fingerprint() -> int:
    """Monotonic version of the process tuning cache — salt this into
    any lru key whose cached trace bakes in tuned tile choices."""
    return _TUNING_CACHE.version


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def lutq_matmul(x, a, d, *, bm=256, bn=256, bk=512, interpret=None):
    interpret = _default_interpret() if interpret is None else interpret
    return _lutq_matmul(x, a, d, bm=bm, bn=bn, bk=bk, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def lutq_gemv_packed(x, packed, d, *, bm=256, bn=256, bk=512, interpret=None):
    interpret = _default_interpret() if interpret is None else interpret
    return _gemv_packed(x, packed, d, bm=bm, bn=bn, bk=bk,
                        interpret=interpret)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def lutq_shift(xq, a, wsh, *, bm=256, bn=256, bk=512, interpret=None):
    interpret = _default_interpret() if interpret is None else interpret
    return _lutq_shift(xq, a, wsh, bm=bm, bn=bn, bk=bk, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("bn", "interpret"))
def kmeans_stats(w, d, *, bn=4096, interpret=None):
    interpret = _default_interpret() if interpret is None else interpret
    return _kmeans_stats(w, d, bn=bn, interpret=interpret)


def kmeans_step_fused(w_flat, d, *, bn=4096, interpret=None):
    """One full k-means iteration via the Pallas stats kernel: assign +
    recenter (empty clusters keep their centroid). Drop-in for the inner
    loop of repro.core.lutq.kmeans_update."""
    a, sums, counts = kmeans_stats(w_flat, d, bn=bn, interpret=interpret)
    new_d = jnp.where(counts > 0, sums / jnp.maximum(counts, 1.0), d)
    return a, jnp.sort(new_d)


# ---------------------------------------------------------------------------
# backend resolution
# ---------------------------------------------------------------------------

def resolve_backend(state: LutqState, backend: str = "auto", *,
                    transpose_rhs: bool = False, sliced: bool = False) -> str:
    """Concrete backend ("decode" | "fused" | "packed4") for one leaf.

    Resolution only consults trace-static leaf structure (dtypes, shapes,
    presence of the fp master), so the result is stable under jit and
    identical to what ``serve_view``'s backend manifest records:

      * train-form leaves (``w`` present) -> ``decode`` — the STE forward
        must stay differentiable and bit-exact with the paper's step 2/3;
      * stacked dictionaries (``d.ndim > 1``: scan-over-layers slices
        them away before the matmul, but MoE expert einsums see them
        whole) -> ``decode``;
      * packed uint8 assignments -> ``packed4`` (the packed kernel reads
        them in place), except transposed use, where the row-pair layout
        is along the wrong axis -> ``decode``;
      * pow2-*encoded* dictionaries (``d.dtype == int8``: the sign+
        exponent plane ``serve_view`` emits for ``backend="pow2"``
        rules) -> ``pow2`` when the shift-add kernel applies (serve
        form, 2-D int8 assignments, K <= 256), else ``decode`` — and
        the decode path on an encoded leaf runs the *integer* oracle,
        so it stays token-identical to the kernel;
      * int8 assignments, K <= 256 -> ``fused``.

    Explicit requests degrade down the same ladder
    (pow2 -> fused -> decode for float dictionaries, since the shift
    trick needs the encoded plane; packed4 -> fused -> decode) instead
    of erroring, so a policy can pin ``backend="packed4"`` on rules
    whose leaves may not all pack.

    ``sliced=True`` resolves the *per-slice* view of a stacked leaf —
    what the kernels see after lax.scan slices a layer stack or
    ``moe_apply`` vmaps over experts. ``serve_view``'s backend manifest
    records this per-tensor resolution.
    """
    if backend not in BACKENDS:
        raise ValueError(
            f"unknown backend {backend!r}; expected one of {BACKENDS}")
    nstack = state.d.ndim - 1
    d_ndim = 1 if sliced else state.d.ndim
    a_ndim = state.a.ndim - nstack if sliced else state.a.ndim
    if state.w is not None or d_ndim > 1 or a_ndim != 2:
        return "decode"
    if backend == "decode":
        return "decode"
    K = state.d.shape[-1]
    if state.d.dtype == jnp.int8:  # pow2 sign+exponent plane
        if state.a.dtype == jnp.uint8 or K > 256:
            return "decode"
        return "pow2"
    if state.a.dtype == jnp.uint8:  # serve-packed 4-bit pairs (pack4_kin)
        if transpose_rhs or K > 16:
            return "decode"
        return "packed4"
    return "fused" if K <= 256 else "decode"


# ---------------------------------------------------------------------------
# shape plumbing: tile choice + zero-padding onto the kernel grids
# ---------------------------------------------------------------------------

def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _tile(dim: int, block: int, base: int):
    """(tile, padded_dim): tile <= block, tile % base == 0, padded % tile == 0.

    In interpret mode base is 1 (any block shape emulates); on real TPU
    base is the hardware tiling (8 sublanes / 128 lanes for f32), so the
    padded operand is always Mosaic-layout friendly.
    """
    t = min(block, _round_up(dim, base))
    return t, _round_up(dim, t)


def _tuned_tile(be: str, M: int, N: int, Kin: int, K: int, dtype,
                interpret: bool) -> TileConfig:
    """Cache lookup for one kernel shape; defaults when absent."""
    key = make_key(KERNEL_OF_BACKEND[be], M, N, Kin, K, dtype, be,
                   platform_key(interpret))
    return _TUNING_CACHE.get(key) or DEFAULT_TILE


# ---------------------------------------------------------------------------
# pow2 shift-add path (multiplier-less serving)
# ---------------------------------------------------------------------------

def _pow2_act_quant(x2, act, axis_name=None):
    """int8-quantize activations for the shift-add path.

    ``act`` is the leaf's frozen calibration pair ``[scale, qmax]``
    (``LutqState.act``, trailing shape (2,)) or None for dynamic
    per-call scaling (``stop_grad(max|x|) / 127``). Returns
    (xq int8, scale f32 scalar). Under K-sharding pass ``axis_name`` so
    the dynamic amax is a global ``pmax`` — max is exact, so the sharded
    quantization is bit-identical to the unsharded one.
    """
    xf = x2.astype(jnp.float32)
    if act is not None:
        qmax = jnp.minimum(act[..., 1].astype(jnp.float32), 127.0)
        s = act[..., 0].astype(jnp.float32)
    else:
        qmax = jnp.float32(127.0)
        amax = jnp.max(jnp.abs(xf))
        if axis_name is not None:
            amax = jax.lax.pmax(amax, axis_name)
        s = jax.lax.stop_gradient(amax) / qmax
    s = jnp.where(s > 0, s, 1.0)
    xq = jnp.clip(jnp.round(xf / s), -qmax, qmax).astype(jnp.int8)
    return xq, s


def _pow2_dot_acc(x2, code, a, act, *, transpose_rhs=False, axis_name=None,
                  use_kernel=True, bm=None, bn=None, bk=None,
                  interpret=None):
    """(int32 accumulator (M, N), f32 epilogue scale) of the pow2 path.

    Shared by the ``pow2`` Pallas backend, the integer decode oracle
    (``use_kernel=False``) and the shard_map local function — all three
    run the same quantize / shifted-dict / int32-accumulate algebra, so
    results are bit-identical (int32 accumulation is exact under any
    tiling or psum order; the fp epilogue multiplies identical values).
    """
    interpret = _default_interpret() if interpret is None else interpret
    if transpose_rhs:
        a = a.T
    M, Kin = x2.shape
    assert a.shape[0] == Kin, (a.shape, x2.shape)
    N = a.shape[1]
    K = code.shape[-1]
    wsh = pow2_shift_weights(code)            # (K,) int32, O(K) exponent-add
    xq, s = _pow2_act_quant(x2, act, axis_name)
    scale = s * pow2_shift_scale(code)        # the single fp multiply factor
    if not use_kernel:
        return lutq_shift_ref(xq, a, wsh), scale
    tile = _tuned_tile("pow2", M, N, Kin, K, jnp.int8, interpret)
    bm = tile.bm if bm is None else bm
    bn = tile.bn if bn is None else bn
    bk = tile.bk if bk is None else bk
    base_m = 1 if interpret else 8
    base_l = 1 if interpret else 128
    tm, Mp = _tile(M, bm, base_m)
    tn, Np = _tile(N, bn, base_l)
    tk, Kp = _tile(Kin, bk, base_l)
    if Mp != M or Kp != Kin:
        xq = jnp.pad(xq, ((0, Mp - M), (0, Kp - Kin)))
    if Kp != Kin or Np != N:
        a = jnp.pad(a, ((0, Kp - Kin), (0, Np - N)))
    acc = lutq_shift(xq, a, wsh, bm=tm, bn=tn, bk=tk, interpret=interpret)
    return acc[:M, :N], scale


def lutq_dot(
    x: jax.Array,
    state: LutqState,
    *,
    backend: str = "auto",
    transpose_rhs: bool = False,
    out_dtype=None,
    bm: int = None,
    bn: int = None,
    bk: int = None,
    interpret: bool = None,
) -> jax.Array:
    """``x @ d[A]`` (or ``x @ d[A].T``) through the resolved backend.

    x: (..., Kin) — leading dims are flattened for the kernels and
    restored on return. state: a LutqState whose assignments are
    (Kin, N) int8, (Kin/2, N) packed uint8, or any stacked/train form
    (those fall back to the dense decode path, which also carries the
    training STE). Returns (..., N) in ``out_dtype`` (default x.dtype).

    Tile sizes default to the process :class:`TuningCache` entry for
    this (kernel, shape, dtype, platform) key — :data:`DEFAULT_TILE`
    when untuned. Explicit ``bm/bn/bk`` arguments override the cache
    field-by-field. Callers that
    jit around ``lutq_dot`` must salt their jit/lru keys with
    :func:`tuning_fingerprint` or a tile tuned after the first trace
    would be silently ignored.

    Fused backends never materialize the decoded weight matrix in HBM:
    non-tile-multiple shapes are zero-padded onto the kernel grid
    (padded x rows/K-columns are zero, padded assignment entries index
    dictionary slot 0 against zero activations, padded dictionary lanes
    are never indexed), and the pad is sliced off the f32 kernel output.
    The dictionary stays unpadded: the kernels read it as SMEM scalars.
    """
    be = resolve_backend(state, backend, transpose_rhs=transpose_rhs)
    out_dtype = out_dtype or x.dtype

    if be == "decode":
        a = state.a
        if (state.d.dtype == jnp.int8 and state.w is None
                and state.d.ndim == 1 and a.ndim == 2
                and a.dtype != jnp.uint8):
            # encoded pow2 leaf: run the *integer* decode oracle so the
            # decode backend stays token-identical to the shift-add kernel
            lead = x.shape[:-1]
            x2 = x.reshape(-1, x.shape[-1])
            acc, scale = _pow2_dot_acc(x2, state.d, a, state.act,
                                       transpose_rhs=transpose_rhs,
                                       use_kernel=False)
            y = acc.astype(jnp.float32) * scale
            return y.reshape(*lead, y.shape[-1]).astype(out_dtype)
        if a.dtype == jnp.uint8:
            a = unpack4_kin(a)
        if state.w is not None:
            w = quantize_ste_any(state.w, state.d, a)
        else:
            w = decode_any(state.d, a)
        w = w.astype(x.dtype)
        if transpose_rhs:
            w = jnp.swapaxes(w, -1, -2)
        return jnp.matmul(x, w).astype(out_dtype)

    interpret = _default_interpret() if interpret is None else interpret
    lead, Kin = x.shape[:-1], x.shape[-1]
    x2 = x.reshape(-1, Kin)
    M = x2.shape[0]
    d = state.d
    K = d.shape[-1]
    base_m = 1 if interpret else 8
    base_l = 1 if interpret else 128

    if be == "pow2":
        acc, scale = _pow2_dot_acc(x2, d, state.a, state.act,
                                   transpose_rhs=transpose_rhs,
                                   bm=bm, bn=bn, bk=bk,
                                   interpret=interpret)
        y = acc.astype(jnp.float32) * scale
        N = y.shape[-1]
    elif be == "fused":
        a = state.a.T if transpose_rhs else state.a  # (Kin, N) int8
        assert a.shape[0] == Kin, (a.shape, x.shape)
        N = a.shape[1]
        tile = _tuned_tile(be, M, N, Kin, K, x.dtype, interpret)
        bm = tile.bm if bm is None else bm
        bn = tile.bn if bn is None else bn
        bk = tile.bk if bk is None else bk
        tm, Mp = _tile(M, bm, base_m)
        tn, Np = _tile(N, bn, base_l)
        tk, Kp = _tile(Kin, bk, base_l)
        if Mp != M or Kp != Kin:
            x2 = jnp.pad(x2, ((0, Mp - M), (0, Kp - Kin)))
        if Kp != Kin or Np != N:
            a = jnp.pad(a, ((0, Kp - Kin), (0, Np - N)))
        y = lutq_matmul(x2, a, d, bm=tm, bn=tn, bk=tk, interpret=interpret)
        y = y[:M, :N]
    else:  # packed4: x (M, Kin) @ unpack(packed (Kin/2, N))
        p = state.a
        assert p.shape[0] * 2 == Kin, (p.shape, x.shape)
        N = p.shape[1]
        tile = _tuned_tile(be, M, N, Kin, K, x.dtype, interpret)
        bm = tile.bm if bm is None else bm
        bn = tile.bn if bn is None else bn
        bk = tile.bk if bk is None else bk
        tm, Mp = _tile(M, bm, base_m)
        tn, Np = _tile(N, bn, base_l)
        tk, Kp = _tile(Kin, bk, 2 if interpret else 2 * base_l)
        if Mp != M or Kp != Kin:
            x2 = jnp.pad(x2, ((0, Mp - M), (0, Kp - Kin)))
        if Kp != Kin or Np != N:
            p = jnp.pad(p, ((0, (Kp - Kin) // 2), (0, Np - N)))
        y = lutq_gemv_packed(x2, p, d, bm=tm, bn=tn, bk=tk,
                             interpret=interpret)
        y = y[:M, :N]
    return y.reshape(*lead, N).astype(out_dtype)


# ---------------------------------------------------------------------------
# SPMD: explicit shard_map path over a device mesh
# ---------------------------------------------------------------------------

def _spec_parts(spec, ndim: int):
    """Right-pad a PartitionSpec to ``ndim`` entries."""
    parts = list(tuple(spec) if spec is not None else ())
    return parts + [None] * (ndim - len(parts))


def lutq_dot_spmd(
    x: jax.Array,
    state: LutqState,
    mesh,
    *,
    a_spec,
    x_spec=None,
    backend: str = "auto",
    transpose_rhs: bool = False,
    out_dtype=None,
):
    """:func:`lutq_dot` under ``shard_map``: each device runs the fused
    Pallas kernel on its **local** index shard.

    This is the path GSPMD cannot give a ``pallas_call``: the automatic
    partitioner has no rule for the custom call, so inside a plain jit a
    sharded Pallas matmul falls back to replicate-and-gather. Here the
    grid is split by hand instead:

      * ``a_spec``: PartitionSpec of the assignments, matching their
        actual layout — ``(K, N)`` int8, ``(K/2, N)`` packed uint8
        (shards then hold whole row *pairs* by construction), or
        ``(E, K, N)`` expert-stacked, where sharding E is expert
        parallelism (each device computes its local experts; ``x`` must
        then carry a matching leading E axis, e.g. the MoE capacity
        buffer ``(E, C, D)``).
      * output-dim (N) sharding keeps the full reduction local — the
        result is bit-identical to the unsharded kernel, just sharded.
      * reduction-dim (K) sharding emits one ``psum`` over the named
        axes of the partial products (f32 accumulation; not bit-exact
        against a single device, like any reduce-scatter matmul).
      * ``transpose_rhs`` (tied logits: ``x @ d[A].T``): the roles of
        a's last two dims swap — sharding the vocab dim shards the
        output, sharding the feature dim triggers the psum.

    ``x_spec`` defaults to replicated leading dims with the last dim
    matching a's reduction sharding (so local shards always line up);
    pass e.g. ``P("data", ...)`` to batch-shard activations too. The
    dictionary (and any stacked per-expert dictionaries) are replicated
    across the sharded matmul axes — LUT-Q's tiny-d / big-A split is
    exactly what makes this cheap.
    """
    from jax.sharding import PartitionSpec as P

    nstack = state.a.ndim - 2
    if nstack not in (0, 1):
        raise ValueError(f"lutq_dot_spmd supports at most one stack axis, "
                         f"got assignments of rank {state.a.ndim}")
    if nstack and transpose_rhs:
        raise ValueError("transpose_rhs with expert-stacked assignments "
                         "is not supported")
    aparts = _spec_parts(a_spec, state.a.ndim)
    # contraction/output entries of the *assignment* spec
    k_entry, n_entry = (aparts[-1], aparts[-2]) if transpose_rhs else \
                       (aparts[-2], aparts[-1])
    stack_entry = aparts[0] if nstack else None

    if x_spec is None:
        x_spec = P(*([stack_entry] if nstack else []),
                   *([None] * (x.ndim - nstack - 1)), k_entry)
    xparts = _spec_parts(x_spec, x.ndim)
    out_spec = P(*xparts[:-1], n_entry)
    d_spec = P(stack_entry, None) if nstack else P()

    def local(x_l, d_l, a_l, *act_rest):
        act_l = act_rest[0] if act_rest else None
        if (d_l.dtype == jnp.int8 and a_l.dtype == jnp.int8
                and k_entry is not None):
            # encoded pow2 under K-sharding: psum the *int32* partial
            # accumulators (exact) and pmax the dynamic act amax inside
            # _pow2_act_quant, so the sharded result is bit-identical to
            # one device — unlike the f32 psum below
            use_kernel = backend != "decode"

            def parts(xe, de, ae, ce):
                x2 = xe.reshape(-1, xe.shape[-1])
                acc, scale = _pow2_dot_acc(
                    x2, de, ae, ce, transpose_rhs=transpose_rhs,
                    axis_name=k_entry, use_kernel=use_kernel)
                return acc.reshape(*xe.shape[:-1], acc.shape[-1]), scale

            if nstack:
                acc, scale = jax.vmap(parts)(x_l, d_l, a_l, act_l)
                scale = scale.reshape(scale.shape + (1,) * (acc.ndim - 1))
            else:
                acc, scale = parts(x_l, d_l, a_l, act_l)
            acc = jax.lax.psum(acc, k_entry)
            return (acc.astype(jnp.float32) * scale).astype(
                out_dtype or x_l.dtype)
        # K-sharded partials stay f32 through the psum and round once,
        # like the single-device kernel's f32 accumulator
        part_dtype = (out_dtype or x_l.dtype) if k_entry is None \
            else jnp.float32
        if nstack:
            y = jax.vmap(lambda xe, de, ae, ce: lutq_dot(
                xe, LutqState(w=None, d=de, a=ae, act=ce), backend=backend,
                out_dtype=part_dtype))(x_l, d_l, a_l, act_l)
        else:
            y = lutq_dot(x_l, LutqState(w=None, d=d_l, a=a_l, act=act_l),
                         backend=backend,
                         transpose_rhs=transpose_rhs, out_dtype=part_dtype)
        if k_entry is not None:
            y = jax.lax.psum(y, k_entry).astype(out_dtype or x_l.dtype)
        return y

    operands = [x, state.d, state.a]
    in_specs = [P(*xparts), d_spec, P(*aparts)]
    if state.act is not None:
        # act [scale, qmax] pairs are tiny and replicated across the
        # sharded matmul axes, like the dictionary
        operands.append(state.act)
        in_specs.append(P(stack_entry, None) if nstack else P(None))
    return jax.shard_map(local, mesh=mesh, in_specs=tuple(in_specs),
                         out_specs=out_spec, check_vma=False)(*operands)


# ---------------------------------------------------------------------------
# SPMD annotation: route model-layer dots to lutq_dot_spmd inside a jit
# ---------------------------------------------------------------------------

class SpmdLutqState:
    """A :class:`LutqState` tagged with its mesh + assignment sharding.

    Trace-local wrapper: the meshed serving jits call
    :func:`annotate_spmd` on their *tracer* params, so model-layer code
    (``nn/linear.dot_kernel``, ``nn/moe._expert_dot``) can dispatch the
    leaf to :func:`lutq_dot_spmd` — running each ``pallas_call`` on its
    local index shard — instead of letting GSPMD gather the assignments
    around the custom call. The wrapper never escapes the trace, so
    checkpointing, manifests and tests always see plain LutqStates.

    Registered as a pytree with (mesh, a_spec) static so scan/vmap/remat
    transparently slice the inner state while the annotation rides along.
    """

    __slots__ = ("state", "mesh", "a_spec")

    def __init__(self, state: LutqState, mesh, a_spec):
        self.state = state
        self.mesh = mesh
        self.a_spec = a_spec

    # convenience passthroughs so shape probes keep working
    @property
    def w(self):
        return self.state.w

    @property
    def d(self):
        return self.state.d

    @property
    def a(self):
        return self.state.a

    @property
    def sid(self):
        return self.state.sid

    @property
    def act(self):
        return self.state.act

    def tree_flatten(self):
        return (self.state,), (self.mesh, self.a_spec)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], *aux)


jax.tree_util.register_pytree_node(
    SpmdLutqState,
    lambda s: s.tree_flatten(),
    SpmdLutqState.tree_unflatten,
)


def annotate_spmd(params, axes, mesh):
    """Wrap serve-form LutqState leaves with their serve PartitionSpecs.

    Call *inside* a meshed jit on the params tracers. Leaves whose
    assignment spec names a mesh axis are wrapped. Replicated leaves are
    wrapped too when the kernels compile for the TPU, where a Pallas
    kernel under a multi-device jit must run inside a shard_map; in
    interpret mode the kernels are plain XLA that the partitioner
    handles, so those leaves pass through and keep the layouts
    propagation gives them. Train-form and non-LutqState leaves pass
    through.
    """
    if mesh is None:
        return params
    from repro.distributed.sharding import serve_pspecs

    pspecs = serve_pspecs(axes, mesh, params)
    interpret = _default_interpret()

    def wrap(leaf, spec):
        if not isinstance(leaf, LutqState) or leaf.w is not None:
            return leaf
        a_spec = getattr(spec, "a", None)
        live = a_spec is not None and any(e is not None for e in a_spec)
        if not live and interpret:
            return leaf
        # full rank, so lutq_dot_sharded's right-alignment after scan
        # slicing maps each entry to its own dim: P(None, "model") on a
        # (L, K, N) stack shards K, not N
        return SpmdLutqState(leaf, mesh, jax.sharding.PartitionSpec(
            *_spec_parts(a_spec, leaf.a.ndim)))

    return jax.tree_util.tree_map(
        wrap, params, pspecs,
        is_leaf=lambda n: isinstance(n, LutqState))


def lutq_dot_sharded(
    x: jax.Array,
    leaf: "SpmdLutqState",
    *,
    backend: str = "auto",
    transpose_rhs: bool = False,
    out_dtype=None,
):
    """Dispatch an annotated leaf: shard-local kernels when they apply.

    scan-over-layers slices leading stack axes off the *arrays* but not
    off the recorded spec, so the spec's trailing entries are
    right-aligned to the runtime assignment rank. Leaves that resolve to
    ``decode`` take the plain :func:`lutq_dot` path (GSPMD shards dense
    decode fine on its own); a kernel on a replicated leaf still runs
    under shard_map, computing the whole product on every device.
    """
    from jax.sharding import PartitionSpec as P

    state = leaf.state
    # right-align to the runtime rank: scan/vmap slicing removed leading
    # stack axes from a but specs were recorded on the full stacked leaf
    ndim = state.a.ndim
    parts = list(tuple(leaf.a_spec))[-ndim:] if leaf.a_spec else []
    parts = [None] * (ndim - len(parts)) + parts
    nstack = state.a.ndim - 2
    be = resolve_backend(state, backend, transpose_rhs=transpose_rhs,
                         sliced=True)
    if be == "decode" or nstack not in (0, 1) or (nstack and transpose_rhs):
        return lutq_dot(x, state, backend=backend,
                        transpose_rhs=transpose_rhs, out_dtype=out_dtype)
    return lutq_dot_spmd(x, state, leaf.mesh, a_spec=P(*parts),
                         backend=backend, transpose_rhs=transpose_rhs,
                         out_dtype=out_dtype)


# ---------------------------------------------------------------------------
# Paged attention (decode): block-table Pallas kernel vs gather oracle
# ---------------------------------------------------------------------------

#: dispatch names accepted by :func:`paged_attention`.
PAGED_BACKENDS = ("auto", "kernel", "gather")


def paged_attention_reference(q, k_pool, v_pool, block, cache_len, *,
                              window=None, scale=None, k_scale=None,
                              v_scale=None):
    """Gather oracle: assemble the row once, dequant once, attend.

    This is the pre-kernel paged decode path and the numerics contract
    the kernel must match bit-for-bit. The int8 scale planes are
    gathered exactly once each and reused for the dequant (the old
    in-model path re-gathered them right after scattering the new
    token's scales).
    """
    from repro.nn.attention import decode_attention, gather_pages

    kc = gather_pages(k_pool, block)
    vc = gather_pages(v_pool, block)
    if k_scale is not None:
        ks = gather_pages(k_scale, block)
        vs = gather_pages(v_scale, block)
        kc = kc.astype(jnp.bfloat16) * ks[..., None]
        vc = vc.astype(jnp.bfloat16) * vs[..., None]
    return decode_attention(q, kc, vc, cache_len, window=window, scale=scale)


def _paged_attention_sharded(q, k_pool, v_pool, block, cache_len, *,
                             window, scale, k_scale, v_scale, interpret,
                             mesh, vmem_budget_bytes=None):
    """KV-head-sharded kernel dispatch under a ("data","model") mesh.

    ``paged_serve_shardings`` lays pool leaves out with the Hkv axis on
    "model" and the block table / batch on "data"; the kernel grid is
    purely parallel over (batch, kv-head), so a shard_map over both axes
    runs the identical kernel on local shards — bit-identical by
    construction. Falls back to the gather oracle (which GSPMD
    partitions on its own) when an axis does not divide.
    """
    from jax.sharding import PartitionSpec as P

    from repro.kernels.paged_attn import paged_attention_tpu

    B, _, H, _ = q.shape
    hkv = k_pool.shape[2]
    sizes = dict(mesh.shape)
    data, model = sizes.get("data", 1), sizes.get("model", 1)
    if B % data or hkv % model:
        return paged_attention_reference(
            q, k_pool, v_pool, block, cache_len, window=window, scale=scale,
            k_scale=k_scale, v_scale=v_scale)
    dp = "data" if data > 1 else None
    tp = "model" if model > 1 else None
    quant = k_scale is not None

    def local(q_l, k_l, v_l, blk_l, cl_l, *scales):
        ks_l, vs_l = scales if scales else (None, None)
        return paged_attention_tpu(
            q_l, k_l, v_l, blk_l, cl_l, window=window, scale=scale,
            k_scale=ks_l, v_scale=vs_l, interpret=interpret,
            vmem_budget_bytes=vmem_budget_bytes)

    in_specs = [P(dp, None, tp, None), P(None, None, tp, None),
                P(None, None, tp, None), P(dp, None), P(dp)]
    operands = [q, k_pool, v_pool, block, cache_len]
    if quant:
        in_specs += [P(None, None, tp), P(None, None, tp)]
        operands += [k_scale, v_scale]
    return jax.shard_map(local, mesh=mesh, in_specs=tuple(in_specs),
                         out_specs=P(dp, None, tp, None),
                         check_vma=False)(*operands)


def paged_attention(q, k_pool, v_pool, block, cache_len, *, window=None,
                    scale=None, k_scale=None, v_scale=None, backend="auto",
                    interpret=None, mesh=None, vmem_budget_bytes=None):
    """One-token decode attention over a paged KV pool.

    q: (B, 1, H, dh); k_pool/v_pool: (P, page, Hkv, dh); block: (B, NB)
    int32 block table; cache_len: (B,) or scalar valid lengths. int8
    pools carry bf16 per-token scale planes (P, page, Hkv) in
    ``k_scale``/``v_scale``.

    ``backend="kernel"`` walks the block table in Pallas
    (:mod:`repro.kernels.paged_attn`), streaming ``ceil(cache_len/page)``
    live pages per row instead of the full ``NB*page`` gather —
    ``window/page`` pages under SWA. ``"gather"`` is the materializing
    oracle. ``"auto"`` consults the process :class:`TuningCache` under
    the ``paged_attn`` key (both entries are bit-identical, so tuning
    only ever trades bytes for bytes) and defaults to the kernel.
    ``mesh`` routes through a shard_map over ("data","model") so
    KV-head-sharded serving keeps shard-local pages.
    ``vmem_budget_bytes`` caps the kernel's per-row VMEM scratch (see
    ``paged_attn.vmem_plan``): rows too long for the single-pass scratch
    run the two-pass kernel instead of failing.
    """
    interpret = _default_interpret() if interpret is None else interpret
    if backend not in PAGED_BACKENDS:
        raise ValueError(f"backend={backend!r} not in {PAGED_BACKENDS}")
    _, page, hkv, dh = k_pool.shape
    nb = block.shape[1]
    if backend == "auto":
        from repro.kernels.autotune import paged_attn_key

        tile = _TUNING_CACHE.get(paged_attn_key(
            page, nb, hkv, dh, k_pool.dtype, interpret=interpret))
        backend = tile.strategy if tile is not None else "kernel"
    cl = jnp.broadcast_to(
        jnp.asarray(cache_len, jnp.int32).reshape(-1), (q.shape[0],))
    if backend == "gather":
        return paged_attention_reference(
            q, k_pool, v_pool, block, cl, window=window, scale=scale,
            k_scale=k_scale, v_scale=v_scale)
    if mesh is not None:
        return _paged_attention_sharded(
            q, k_pool, v_pool, block, cl, window=window, scale=scale,
            k_scale=k_scale, v_scale=v_scale, interpret=interpret, mesh=mesh,
            vmem_budget_bytes=vmem_budget_bytes)
    from repro.kernels.paged_attn import paged_attention_tpu

    return paged_attention_tpu(
        q, k_pool, v_pool, block, cl, window=window, scale=scale,
        k_scale=k_scale, v_scale=v_scale, interpret=interpret,
        vmem_budget_bytes=vmem_budget_bytes)


def tune_paged_attention(*, batch=4, page=16, pages_per_row=4, hkv=2,
                         dh=16, g=2, kv_dtype=jnp.float32, window=None,
                         interpret=None, reps=3, warmup=2, seed=0,
                         cache=None):
    """Time kernel vs gather on one paged geometry; record the winner.

    Returns ``(key, best_tile, {candidate: us})`` like
    :func:`repro.kernels.autotune.tune` (which this wraps — the cache
    key is ``paged_attn|M<page>|N<NB>|Kin<Hkv>|K<dh>|...``). Both
    candidates are bit-identical, so a recorded entry only ever changes
    which byte stream the decode jits trace; the TuningCache version
    bump re-traces them.
    """
    import numpy as np

    from repro.kernels import autotune

    interpret = _default_interpret() if interpret is None else interpret
    rng = np.random.RandomState(seed)
    n_pages = 1 + batch * pages_per_row
    quant = jnp.dtype(kv_dtype) == jnp.int8
    if quant:
        kp = jnp.asarray(rng.randint(-127, 128,
                                     (n_pages, page, hkv, dh)), jnp.int8)
        vp = jnp.asarray(rng.randint(-127, 128,
                                     (n_pages, page, hkv, dh)), jnp.int8)
        ks = jnp.asarray(np.abs(rng.randn(n_pages, page, hkv)) * 0.05,
                         jnp.bfloat16)
        vs = jnp.asarray(np.abs(rng.randn(n_pages, page, hkv)) * 0.05,
                         jnp.bfloat16)
    else:
        kp = jnp.asarray(rng.randn(n_pages, page, hkv, dh), kv_dtype)
        vp = jnp.asarray(rng.randn(n_pages, page, hkv, dh), kv_dtype)
        ks = vs = None
    q = jnp.asarray(rng.randn(batch, 1, hkv * g, dh), jnp.float32)
    blk = jnp.asarray(
        rng.randint(1, n_pages, (batch, pages_per_row)), jnp.int32)
    cl = jnp.asarray(
        rng.randint(1, pages_per_row * page + 1, (batch,)), jnp.int32)

    def measure(tile):
        def run(q, kp, vp, blk, cl):
            return paged_attention(q, kp, vp, blk, cl, window=window,
                                   k_scale=ks, v_scale=vs,
                                   backend=tile.strategy,
                                   interpret=interpret)

        return autotune.measure_call(jax.jit(run), q, kp, vp, blk, cl,
                                     reps=reps, warmup=warmup)

    return autotune.tune("paged_attn", M=page, N=pages_per_row, Kin=hkv,
                         K=dh, dtype=kv_dtype, backend="paged",
                         interpret=interpret, measure=measure, cache=cache)
