"""Shape-keyed autotuner for the Pallas LUT-Q kernels.

The compiled-mode perf story of this repo rests on tile choice: the
fused/packed kernels stream the assignment matrix HBM->VMEM in
(bk x bn) blocks and decode in front of the MXU, so the right bm/bn/bk
is a per-shape, per-platform property.
This module searches a pruned candidate grid per

    (kernel, M, N, Kin, K, dtype, backend, platform)

key, times each candidate with proper warmup + ``block_until_ready``,
and records the winner in a :class:`TuningCache` that JSON-persists —
``kernels.ops`` holds the process-level instance that ``lutq_dot``
consults at trace time, ``serve_view(with_manifest=True)`` and the
checkpoint manifest carry it, so a tuned deployment never re-searches.

Candidate grids
---------------
* **Real TPU** (``platform == "tpu"``): MXU-aligned tiles — bm in
  sublane multiples, bn/bk in lane multiples — pruned by a VMEM budget
  (x block + assignment block + f32 accumulator + the decoded weight
  tile must fit comfortably in ~16 MB).
* **Interpret mode** (CPU/GPU emulation, this container): a tiny grid
  that varies only bm/bn while pinning the k
  extent to the whole (padded) reduction axis. Splitting k changes the
  f32 accumulation grouping; keeping it whole matches the default-tile
  reduction order exactly, so every interpret-mode candidate is
  **bit-identical** to the default tiles (asserted by
  tests/test_autotune.py) and the tuner can never change serving
  numerics under CI.

Candidates are generated in deterministic sorted order and the timing
loop takes a strict improvement to switch winners, so repeated searches
on the same machine are stable; ``tune(measure=...)`` injects the
timing function for deterministic tests. A search in which every
candidate fails raises: an untimed default is never recorded as a
winner.
"""
from __future__ import annotations

import dataclasses
import json
import statistics
import time
from pathlib import Path
from typing import Callable, Dict, Iterable, List, Optional, Tuple

import jax
import jax.numpy as jnp

#: kernel component of a cache key per backend
KERNEL_OF_BACKEND = {"fused": "matmul", "packed4": "gemv_packed",
                     "pow2": "shift"}

#: paged-attention decode dispatch choices: the Pallas block-table
#: kernel vs the gather+decode_attention oracle. Both are bit-identical
#: by contract, so this knob is always safe to tune.
PAGED_STRATEGIES = ("kernel", "gather")

_VMEM_BUDGET = 12 * 2**20  # leave headroom under the ~16 MiB/core VMEM


def platform() -> str:
    """The backend JAX actually dispatches to ("cpu" | "tpu" | "gpu").

    Recorded in every cache key and BENCH JSON so interpret-mode numbers
    can never masquerade as real-hardware ones.
    """
    return jax.default_backend()


def default_interpret() -> bool:
    """Pallas interpret mode default: emulate everywhere but real TPU."""
    return platform() != "tpu"


def platform_key(interpret: bool) -> str:
    """Platform component of a cache key.

    Interpret-mode timings are meaningless for real-TPU tile choice, so
    forcing interpret on a TPU host keys as ``"interpret"`` rather than
    polluting the ``"tpu"`` namespace.
    """
    p = platform()
    return "interpret" if (interpret and p == "tpu") else p


@dataclasses.dataclass(frozen=True)
class TileConfig:
    """One tuned kernel configuration.

    ``bm``/``bn``/``bk`` are tile *limits* (``lutq_dot`` clamps them to
    the padded operand dims, so ``bk >= Kin`` means "one k step").
    ``strategy`` is set by paged-attention entries only: the dispatch
    (``"kernel"`` | ``"gather"``) that won; matmul tiles leave it empty.
    """

    bm: int
    bn: int
    bk: int
    strategy: str = ""

    def to_json_dict(self) -> Dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_json_dict(cls, d: Dict) -> "TileConfig":
        return cls(bm=int(d["bm"]), bn=int(d["bn"]), bk=int(d["bk"]),
                   strategy=str(d.get("strategy", "")))


def make_key(kernel: str, M: int, N: int, Kin: int, K: int, dtype,
             backend: str, plat: Optional[str] = None) -> str:
    """Canonical cache key: every field that changes the optimal tile."""
    plat = platform() if plat is None else plat
    return (f"{kernel}|M{int(M)}|N{int(N)}|Kin{int(Kin)}|K{int(K)}"
            f"|{jnp.dtype(dtype).name}|{backend}|{plat}")


class TuningCache:
    """Process-level {key -> TileConfig} map with JSON persistence.

    ``version`` increments on every mutation; ``kernels.ops`` feeds it
    into the lru keys of the serving jits (``decode_fn`` etc.), so a
    tuned tile landing after a trace was cached forces a re-trace
    instead of silently serving stale tiles.
    """

    def __init__(self, entries: Optional[Dict[str, TileConfig]] = None):
        self._entries: Dict[str, TileConfig] = dict(entries or {})
        self.version = 0

    def __len__(self) -> int:
        return len(self._entries)

    def get(self, key: str) -> Optional[TileConfig]:
        return self._entries.get(key)

    def put(self, key: str, tile: TileConfig) -> None:
        self._entries[key] = tile
        self.version += 1

    def update(self, other: "TuningCache | Dict[str, TileConfig]") -> None:
        items = other._entries if isinstance(other, TuningCache) else other
        self._entries.update(items)
        self.version += 1

    def clear(self) -> None:
        self._entries.clear()
        self.version += 1

    def items(self):
        return sorted(self._entries.items())

    def to_json_dict(self) -> Dict[str, Dict]:
        return {k: t.to_json_dict() for k, t in self.items()}

    @classmethod
    def from_json_dict(cls, d: Dict[str, Dict]) -> "TuningCache":
        return cls({k: TileConfig.from_json_dict(v) for k, v in d.items()})

    def save(self, path) -> str:
        Path(path).write_text(json.dumps(self.to_json_dict(), indent=1,
                                         sort_keys=True))
        return str(path)

    @classmethod
    def load(cls, path) -> "TuningCache":
        return cls.from_json_dict(json.loads(Path(path).read_text()))


def paged_attn_key(page: int, pages_per_row: int, hkv: int, dh: int,
                   kv_dtype, *, interpret: Optional[bool] = None) -> str:
    """Cache key for the paged-attention decode dispatch.

    Reuses ``make_key``'s field layout so one cache file carries both
    matmul tiles and attention entries: M=page, N=pages_per_row (NB),
    Kin=Hkv, K=dh, dtype=the pool dtype (int8 vs fp distinguishes the
    dequant variant), platform via ``platform_key`` (an interpret-forced
    TPU host never pollutes the "tpu" namespace).
    """
    interpret = default_interpret() if interpret is None else interpret
    return make_key("paged_attn", page, pages_per_row, hkv, dh, kv_dtype,
                    "paged", platform_key(interpret))


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _vmem_bytes(bm: int, bn: int, bk: int, packed: bool) -> int:
    """Rough per-step VMEM footprint of one fused-kernel grid cell:
    double-buffered x and assignment blocks, the f32 accumulator, and
    the int32 index + decoded weight tiles."""
    a_bytes = (bk // 2) * bn if packed else bk * bn
    x_bytes = bm * bk * 4
    acc_bytes = bm * bn * 4
    tile_bytes = 2 * bk * bn * 4
    return 2 * (x_bytes + a_bytes) + acc_bytes + tile_bytes


def candidates(kernel: str, M: int, N: int, Kin: int, K: int, *,
               interpret: Optional[bool] = None) -> List[TileConfig]:
    """Deterministic pruned candidate grid for one shape.

    Interpret mode pins ``bk`` to the full reduction extent (single k
    step == default reduction grouping == bit-identical outputs) and
    varies bm/bn only. Real TPU searches MXU-aligned bk too,
    pruned by the VMEM budget.
    """
    interpret = default_interpret() if interpret is None else interpret
    if kernel == "paged_attn":
        # no tile grid: the kernel's blocking IS the page geometry. The
        # only knob is which bit-identical dispatch wins the byte race;
        # bm/bn/bk echo the keyed geometry for JSON readability.
        return [TileConfig(bm=M, bn=N, bk=K, strategy=s)
                for s in PAGED_STRATEGIES]
    packed = kernel == "gemv_packed"
    out: List[TileConfig] = []
    if interpret:
        bk = max(512, _round_up(Kin, 2))
        bms = sorted({b for b in (8, 32, 256) if b <= _round_up(M, 8)} | {256})
        if packed:
            bms = [256]  # holds a decode batch whole; prefill tiles by it
        bns = sorted({b for b in (32, 128, 256) if b <= _round_up(N, 8)}
                     | {256})
        for bm in bms:
            for bn in bns:
                out.append(TileConfig(bm=bm, bn=bn, bk=bk))
    else:
        bms = ([256] if packed else
               [b for b in (8, 64, 128, 256, 512)
                if b <= _round_up(M, 8)] or [8])
        bns = [b for b in (128, 256, 512) if b <= _round_up(N, 128)] or [128]
        bks = [b for b in (256, 512, 1024, 2048)
               if b <= _round_up(Kin, 256)] or [256]
        for bm in bms:
            for bn in bns:
                for bk in bks:
                    if _vmem_bytes(bm, bn, bk, packed) <= _VMEM_BUDGET:
                        out.append(TileConfig(bm=bm, bn=bn, bk=bk))
    # deterministic order -> stable winner under timing ties
    return sorted(out, key=lambda t: (t.bm, t.bn, t.bk, t.strategy))


def measure_call(fn: Callable, *args, reps: int = 3, warmup: int = 2) -> float:
    """Median wall-clock us of ``fn(*args)`` with the compile + warmup
    calls excluded and every rep fenced by ``block_until_ready``."""
    try:
        jax.block_until_ready(fn(*args))  # compile
        for _ in range(max(warmup - 1, 0)):
            jax.block_until_ready(fn(*args))
    except Exception:  # infeasible candidate (e.g. Mosaic layout reject)
        return float("inf")
    ts = []
    for _ in range(max(reps, 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return statistics.median(ts) * 1e6


def _operands(kernel: str, M: int, N: int, Kin: int, K: int, dtype, seed: int):
    from repro.core.lutq import LutqState, pow2_encode
    from repro.kernels.ref import pack4_kin

    key = jax.random.PRNGKey(seed)
    kx, ka, kd = jax.random.split(key, 3)
    # the shift kernel consumes int8-quantized activations internally;
    # its probe x stays f32 (lutq_dot quantizes at the boundary)
    xdt = jnp.float32 if kernel == "shift" else dtype
    x = jax.random.normal(kx, (M, Kin), jnp.float32).astype(xdt)
    a = jax.random.randint(ka, (Kin, N), 0, K, jnp.int8)
    d = jnp.sort(jax.random.normal(kd, (K,), jnp.float32))
    if kernel == "gemv_packed":
        a = pack4_kin(a)
    if kernel == "shift":
        d = pow2_encode(d)  # int8 sign+exponent plane
    return x, LutqState(w=None, d=d, a=a)


def tune(kernel: str, *, M: int, N: int, Kin: int, K: int,
         dtype=jnp.float32, backend: Optional[str] = None,
         interpret: Optional[bool] = None, reps: int = 3, warmup: int = 2,
         seed: int = 0, cache: Optional[TuningCache] = None,
         measure: Optional[Callable[[TileConfig], float]] = None,
         ) -> Tuple[str, TileConfig, Dict[str, float]]:
    """Search the candidate grid for one shape; returns
    ``(key, best_tile, {repr(tile): us})`` and records the winner in
    ``cache`` when given.

    ``measure`` (tests): maps a TileConfig to a time, replacing the real
    benchmark loop. The winner is the first strict minimum in candidate
    order, so equal-time candidates resolve deterministically. Raises
    ``RuntimeError`` when every candidate fails (infinite time).
    """
    import functools

    from repro.kernels import ops

    backend = backend or {"gemv_packed": "packed4",
                          "shift": "pow2"}.get(kernel, "fused")
    interpret = default_interpret() if interpret is None else interpret
    if kernel == "shift":
        # the shift kernel's hot operand is the int8 quantized x; key on
        # int8 regardless of the model compute dtype so trace-time
        # lookups (_tuned_tile("pow2", ...)) always hit
        dtype = jnp.int8
    key = make_key(kernel, M, N, Kin, K, dtype, backend,
                   platform_key(interpret))
    if measure is None:
        x, state = _operands(kernel, M, N, Kin, K, dtype, seed)

        def measure(tile: TileConfig) -> float:
            fn = jax.jit(functools.partial(
                ops.lutq_dot, backend=backend, bm=tile.bm, bn=tile.bn,
                bk=tile.bk, interpret=interpret))
            return measure_call(fn, x, state, reps=reps, warmup=warmup)

    best: Optional[TileConfig] = None
    best_us = float("inf")
    timings: Dict[str, float] = {}
    for tile in candidates(kernel, M, N, Kin, K, interpret=interpret):
        us = measure(tile)
        name = f"{tile.bm}x{tile.bn}x{tile.bk}"
        timings[name + (f"/{tile.strategy}" if tile.strategy else "")] = us
        if us < best_us:
            best, best_us = tile, us
    if best is None:
        raise RuntimeError(f"autotune {key}: every candidate failed")
    if cache is not None:
        cache.put(key, best)
    return key, best, timings


def leaf_shapes_for_tree(params, *, batch_m: int = 8,
                         transpose_batch_m: Optional[int] = None,
                         ) -> List[Dict]:
    """Distinct fused/packed kernel shapes a serve tree will dispatch.

    Walks the tree like ``backend_manifest`` does, resolves each leaf's
    backend per-slice (what the kernels actually see after scan/vmap
    slicing), and emits one record per distinct
    ``(kernel, M, N, Kin, K)``: the decode-time matmul shape with
    ``M = batch_m`` (the engine's decode batch), plus the transposed
    orientation for tied-logits leaves.
    """
    from repro.core.lutq import LutqState
    from repro.kernels.ops import resolve_backend
    from repro.nn.tree import tree_paths

    seen: Dict[Tuple, Dict] = {}
    for path, leaf in tree_paths(params):
        if not isinstance(leaf, LutqState) or leaf.w is not None:
            continue
        be = resolve_backend(leaf, "auto", sliced=True)
        if be == "decode":
            continue
        K = int(leaf.d.shape[-1])
        nstack = leaf.d.ndim - 1
        a_shape = leaf.a.shape[nstack:]
        Kin, N = int(a_shape[0]), int(a_shape[1])
        if leaf.a.dtype == jnp.uint8:
            Kin *= 2
        kernel = KERNEL_OF_BACKEND[be]
        rec_key = (kernel, batch_m, N, Kin, K)
        seen.setdefault(rec_key, {"kernel": kernel, "backend": be,
                                  "M": batch_m, "N": N, "Kin": Kin, "K": K,
                                  "paths": []})["paths"].append("/".join(path))
        if be in ("fused", "pow2") and path and path[-1] == "table":
            # tied-logits orientation: x @ d[A].T swaps Kin/N
            tm = batch_m if transpose_batch_m is None else transpose_batch_m
            tkey = (kernel, tm, Kin, N, K)
            seen.setdefault(tkey, {"kernel": kernel, "backend": be,
                                   "M": tm, "N": Kin, "Kin": N, "K": K,
                                   "paths": []})["paths"].append(
                                       "/".join(path) + ".T")
    return [seen[k] for k in sorted(seen)]


def tune_tree(params, *, batch_m: int = 8, dtype=jnp.float32,
              cache: Optional[TuningCache] = None,
              reps: int = 3, warmup: int = 2,
              emit: Optional[Callable[[str], None]] = None) -> TuningCache:
    """Autotune every distinct kernel shape of a serve tree.

    ``dtype`` is the *activation* dtype the model computes in (part of
    every cache key — bf16 and f32 tiles tune separately). Returns the
    cache (the given one, or a fresh TuningCache) with one entry per
    shape; ``emit`` receives a per-shape report line.
    """
    cache = TuningCache() if cache is None else cache
    for rec in leaf_shapes_for_tree(params, batch_m=batch_m):
        key, tile, _ = tune(rec["kernel"], M=rec["M"], N=rec["N"],
                            Kin=rec["Kin"], K=rec["K"], dtype=dtype,
                            backend=rec["backend"],
                            reps=reps, warmup=warmup, cache=cache)
        if emit is not None:
            emit(f"[autotune] {rec['kernel']:12s} M={rec['M']:<5d} "
                 f"N={rec['N']:<6d} Kin={rec['Kin']:<6d} K={rec['K']:<4d} -> "
                 f"bm={tile.bm} bn={tile.bn} bk={tile.bk} "
                 f"({len(rec['paths'])} leaves)")
    return cache
