"""Serving utilities: prefill-cache adaptation + batched generation.

Bridges ``prefill`` (which returns caches sized to the prompt) and
``decode_step`` (which expects max_len caches, ring-layout for SWA):
  * ``grow_cache``: right-pad linear caches to max_len;
  * ``ring_from_linear``: re-lay a linear KV cache into the SWA ring
    (slot = position % window) so decode can continue a long prompt;
  * ``decode_fn`` / ``prefill_fn``: jit-cached entry points keyed on
    the (hashable) ModelConfig, shared by the library loop and the
    serving CLI so both reuse one trace per config;
  * ``generate``: static-batch generation — a thin wrapper over the
    continuous-batching slot pool in ``runtime.engine`` (one batched
    prefill, then the engine's decode/retire loop), with per-stream
    ``lengths`` support for ragged right-padded batches.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.models import api
from repro.models.config import ModelConfig


def ring_from_linear(lin: jax.Array, prompt_len, window: int) -> jax.Array:
    """lin: (B, S_prompt, ...) linear cache -> (B, window, ...) ring.

    Position p lands in slot p % window; only the last `window`
    positions survive (they are the only live ones under SWA).
    ``prompt_len`` may be a python int, a scalar, or a per-stream (B,)
    vector — ragged batches relay each stream at its own length. The
    relay is a pure gather (slot s reads position
    ``s + window * floor((len-1-s)/window)``), so it traces without a
    host sync and vmaps over stacked layers.
    """
    B, S = lin.shape[:2]
    L = jnp.broadcast_to(jnp.asarray(prompt_len, jnp.int32).reshape(-1),
                         (B,))[:, None]                       # (B, 1)
    s = jnp.arange(window, dtype=jnp.int32)[None, :]          # (1, W)
    p = s + window * ((L - 1 - s) // window)   # slot's live position
    valid = p >= 0                             # slot empty when len < window
    idx = jnp.clip(p, 0, S - 1).reshape((B, window) + (1,) * (lin.ndim - 2))
    gathered = jnp.take_along_axis(lin, idx, axis=1)
    mask = valid.reshape((B, window) + (1,) * (lin.ndim - 2))
    return jnp.where(mask, gathered, jnp.zeros((), lin.dtype))


def grow_cache(cache_small, cache_big):
    """Right-pad every linear-seq leaf of `cache_small` into the
    max_len-sized `cache_big` (leaves with matching shape pass through)."""

    def merge(big, small):
        if big.shape == small.shape:
            return small.astype(big.dtype)
        pad = [(0, b - s) for b, s in zip(big.shape, small.shape)]
        return jnp.pad(small.astype(big.dtype), pad)

    return jax.tree.map(merge, cache_big, cache_small)


def adapt_prefill_cache(cfg: ModelConfig, cache, batch: int, max_len: int,
                        *, src_len: int = 0, lengths=None):
    """Convert a prefill cache into a decode-ready cache of max_len.

    ``lengths``: optional per-stream (B,) prompt lengths for ragged
    (right-padded) batches. Defaults to the prefill cache's own ``len``
    vector — never ``len[0]`` broadcast to the batch, and never forced
    to the host: the whole adaptation traces, so it can run inside jit
    (the engine's admission path relies on this).
    """
    target = api.init_cache(cfg, batch, max_len, src_len=src_len)
    if lengths is None:
        lengths = cache["len"]
    lengths = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32).reshape(-1),
                               (batch,))
    cache = dict(cache)
    cache["len"] = lengths

    attn_kv = cfg.family in ("dense", "moe", "vlm") and not cfg.use_mla
    if attn_kv and cfg.kv_cache_bits == 8:
        # prefill emits float K/V; the decode cache holds int8 + scales
        # (§Perf cell C), so quantize on adaptation.
        from repro.models.lm import _kv_quant

        def quant_kv(layers):
            layers = dict(layers)
            for key in ("k", "v"):
                q, s = _kv_quant(layers[key], 8)
                layers[key], layers[f"{key}_scale"] = q, s
            return layers

        cache["layers"] = quant_kv(cache["layers"])
        if "prefix_layers" in cache:
            cache["prefix_layers"] = {k: quant_kv(v)
                                      for k, v in cache["prefix_layers"].items()}

    if attn_kv and cfg.window is not None:
        # SWA ring: re-lay per-position leaves at the decode ring width,
        # each stream at its own length
        layers = dict(cache["layers"])
        keys = [k for k in ("k", "v", "k_scale", "v_scale") if k in layers]
        for key in keys:
            lin = layers[key]  # (L, B, S, ...)
            eff = target["layers"][key].shape[2]
            ring = jax.vmap(lambda x: ring_from_linear(x, lengths, eff))(lin)
            layers[key] = ring.astype(target["layers"][key].dtype)
        out = dict(cache)
        out["layers"] = layers
        return out
    return grow_cache(cache, target)


def _decode_step(cfg: ModelConfig, params, token, cache):
    return api.decode_step(params, cfg, token, cache)


def _decode_step_meshed(cfg: ModelConfig, axes, mesh, params, token, cache):
    # annotate inside the trace: serve-form LutqState leaves gain their
    # mesh + assignment spec so nn-layer dots run shard-local Pallas
    # kernels instead of GSPMD's gather-around-the-custom-call fallback
    params = ops.annotate_spmd(params, axes, mesh)
    return api.decode_step(params, cfg, token, cache)


def _prefill(cfg: ModelConfig, max_len: int, params, batch, lengths=None):
    return api.prefill(params, cfg, batch, max_len=max_len, lengths=lengths)


def _prefill_meshed(cfg: ModelConfig, max_len: int, axes, mesh, params,
                    batch, lengths=None):
    params = ops.annotate_spmd(params, axes, mesh)
    return api.prefill(params, cfg, batch, max_len=max_len, lengths=lengths)


@functools.lru_cache(maxsize=64)
def _decode_fn_cached(cfg: ModelConfig, mesh, batch, max_len, src_len,
                      tuning):
    del tuning  # lru salt only: tuned tiles are baked into the trace
    if mesh is None:
        return jax.jit(functools.partial(_decode_step, cfg))
    from repro.launch.partition import serve_shardings

    axes = api.init_axes(cfg)
    sh = serve_shardings(cfg, mesh, batch=batch, max_len=max_len,
                         src_len=src_len)
    return jax.jit(functools.partial(_decode_step_meshed, cfg, axes, mesh),
                   in_shardings=(None, sh["token"], sh["cache"]),
                   out_shardings=(sh["logits"], sh["cache"]))


def decode_fn(cfg: ModelConfig, mesh=None, batch: Optional[int] = None,
              max_len: Optional[int] = None, src_len: int = 0):
    """Jit-cached one-token decode for a config (and optionally a mesh).

    ModelConfig is a frozen (hashable) dataclass, so repeated ``generate``
    calls — and the serving CLI — share one compiled decode per config
    instead of re-wrapping (and re-tracing) a fresh lambda per call. The
    lru key carries :func:`repro.kernels.ops.tuning_fingerprint`, so a
    tuning-cache update (``--autotune``) invalidates traces that baked
    in stale tile choices.

    With ``mesh`` (a hashable ``jax.sharding.Mesh`` — it is part of the
    cache key, so switching meshes in one process never reuses a stale
    trace) the jit takes explicit in/out NamedShardings from
    ``partition.serve_shardings``: token + cache batch-sharded on the
    data axis, cache layout preserved through the step, params left to
    their committed placement (``shard_serve_params``) and annotated
    in-trace (``ops.annotate_spmd``) so fused LUT-Q dots run on local
    index shards.
    """
    if mesh is not None and (batch is None or max_len is None):
        raise ValueError("decode_fn(cfg, mesh) needs the pool geometry: "
                         "pass batch= and max_len= (they size the cache "
                         "shardings)")
    return _decode_fn_cached(cfg, mesh, batch, max_len, src_len,
                             ops.tuning_fingerprint())


@functools.lru_cache(maxsize=64)
def _prefill_fn_cached(cfg: ModelConfig, max_len: int, mesh, tuning):
    del tuning
    if mesh is None:
        return jax.jit(functools.partial(_prefill, cfg, max_len))
    from repro.launch.partition import data_batch_shardings

    axes = api.init_axes(cfg)
    fn = jax.jit(functools.partial(_prefill_meshed, cfg, max_len, axes, mesh))

    def sharded(params, batch, lengths=None):
        batch = jax.device_put(batch, data_batch_shardings(batch, mesh))
        if lengths is None:
            return fn(params, batch)
        return fn(params, batch, lengths)

    return sharded


def prefill_fn(cfg: ModelConfig, max_len: int, mesh=None):
    """Jit-cached prefill for (config, max_len[, mesh]).

    The mesh variant places batch inputs onto their data-parallel
    NamedShardings before the call (prefill's cache output is re-laid by
    the admission splice, whose jit pins the pool shardings) and
    annotates params in-trace so fused LUT-Q dots run shard-local. Like
    ``decode_fn``, the lru key carries the tuning-cache fingerprint.
    """
    return _prefill_fn_cached(cfg, max_len, mesh, ops.tuning_fingerprint())


# ---------------------------------------------------------------------------
# paged entry points (block-table KV; see runtime/paged_kv.py)
# ---------------------------------------------------------------------------

def _paged_decode_step(cfg: ModelConfig, params, token, cache):
    return api.paged_decode_step(params, cfg, token, cache)


def _paged_decode_step_meshed(cfg: ModelConfig, axes, mesh, params, token,
                              cache):
    params = ops.annotate_spmd(params, axes, mesh)
    # the mesh rides into the paged-attention kernel dispatch so it can
    # shard_map over ("data", "model") instead of leaving GSPMD to
    # partition the block-table walk
    return api.paged_decode_step(params, cfg, token, cache, mesh=mesh)


@functools.lru_cache(maxsize=64)
def _paged_decode_fn_cached(cfg: ModelConfig, mesh, batch, n_pages,
                            page_size, n_blocks, src_len, tuning):
    del tuning
    if mesh is None:
        return jax.jit(functools.partial(_paged_decode_step, cfg))
    from repro.launch.partition import paged_serve_shardings

    axes = api.init_axes(cfg)
    sh = paged_serve_shardings(cfg, mesh, batch=batch, n_pages=n_pages,
                               page_size=page_size, n_blocks=n_blocks,
                               src_len=src_len)
    return jax.jit(
        functools.partial(_paged_decode_step_meshed, cfg, axes, mesh),
        in_shardings=(None, sh["token"], sh["cache"]),
        out_shardings=(sh["logits"], sh["cache"]))


def paged_decode_fn(cfg: ModelConfig, mesh=None, batch: Optional[int] = None,
                    n_pages: Optional[int] = None,
                    page_size: Optional[int] = None,
                    n_blocks: Optional[int] = None, src_len: int = 0):
    """Jit-cached paged decode step (same contract as ``decode_fn``).

    With ``mesh`` the jit pins the paged-cache NamedShardings from
    ``partition.paged_serve_shardings``: the page pool is model-sharded
    on the KV-head axis and replicated over data (any slot's block row
    may reference any page), block table/lengths batch-sharded on data.
    """
    if mesh is not None and (batch is None or n_pages is None
                             or page_size is None or n_blocks is None):
        raise ValueError("paged_decode_fn(cfg, mesh) needs the pool "
                         "geometry: batch=, n_pages=, page_size=, n_blocks=")
    return _paged_decode_fn_cached(cfg, mesh, batch, n_pages, page_size,
                                   n_blocks, src_len,
                                   ops.tuning_fingerprint())


def _annotator(cfg: ModelConfig, mesh):
    """In-trace ``ops.annotate_spmd`` for meshed jits (identity without
    a mesh): every Pallas kernel under a multi-device jit must run
    inside a shard_map."""
    if mesh is None:
        return lambda params: params
    axes = api.init_axes(cfg)
    return lambda params: ops.annotate_spmd(params, axes, mesh)


@functools.lru_cache(maxsize=64)
def _paged_chunk_fn_cached(cfg: ModelConfig, mesh, tuning):
    del tuning
    from repro.models import lm as m_lm

    annotate = _annotator(cfg, mesh)

    def paged_prefill_chunk(params, tokens, ws, start, n_real):
        return m_lm.lm_paged_prefill_chunk(annotate(params), cfg, tokens, ws,
                                           start, n_real)

    return jax.jit(paged_prefill_chunk)


def paged_chunk_fn(cfg: ModelConfig, mesh=None):
    """One jit for every chunk width: jax re-traces per (1, C) token
    shape, so ``_cache_size()`` counts exactly the bucket widths hit —
    the engine's no-new-traces-after-warmup assertion keys on this."""
    return _paged_chunk_fn_cached(cfg, mesh, ops.tuning_fingerprint())


@functools.lru_cache(maxsize=64)
def _paged_packed_fn_cached(cfg: ModelConfig, wws: int, mesh, tuning):
    del tuning
    from repro.models import lm as m_lm

    annotate = _annotator(cfg, mesh)

    def paged_prefill_packed(params, tokens, pool, blocks, bases, hists,
                             lens):
        return m_lm.lm_paged_prefill_packed(annotate(params), cfg, tokens,
                                            pool, blocks, bases, hists, lens,
                                            wws)

    return jax.jit(paged_prefill_packed)


def paged_packed_fn(cfg: ModelConfig, wws: int, mesh=None):
    """Fused packed prefill (hydrate + chunk + splice + per-segment
    logits) for several short prompts in one call. Like
    ``paged_chunk_fn``, jax re-traces per packed (1, C) bucket width —
    ``_cache_size()`` counts exactly the widths hit."""
    return _paged_packed_fn_cached(cfg, wws, mesh, ops.tuning_fingerprint())


@functools.lru_cache(maxsize=64)
def paged_splice_fn(cfg: ModelConfig):
    from repro.models import lm as m_lm

    return jax.jit(functools.partial(m_lm.lm_paged_splice, cfg))


@functools.lru_cache(maxsize=64)
def paged_hydrate_fn(cfg: ModelConfig, wws: int):
    from repro.models import lm as m_lm

    def paged_hydrate(pool, row, hist):
        return m_lm.lm_paged_hydrate(cfg, pool, row, hist, wws)

    return jax.jit(paged_hydrate)


@functools.lru_cache(maxsize=64)
def paged_encdec_splice_fn(cfg: ModelConfig):
    from repro.models import encdec as m_encdec

    return jax.jit(functools.partial(m_encdec.encdec_paged_splice, cfg))


def generate(
    params,
    cfg: ModelConfig,
    batch: Dict[str, jax.Array],
    *,
    steps: int,
    lengths=None,
    max_len: Optional[int] = None,
    temperature: float = 0.0,
    rng: Optional[jax.Array] = None,
    backend: Optional[str] = None,
    eos_id: Optional[int] = None,
    return_stats: bool = False,
    mesh=None,
    speculative: int = 0,
    draft_bits: int = 3,
    draft_params=None,
):
    """Prefill the prompt then decode `steps` tokens. Returns (B, steps).

    A thin static-batch wrapper over ``runtime.engine.Engine``: the
    whole batch is preloaded into a capacity-B slot pool with one
    batched prefill, then decoded by the engine's slot loop. This keeps
    one code path for sampling, retirement and stats between static and
    continuous batching.

    ``lengths``: per-stream prompt lengths for ragged (right-padded)
    batches. Each stream's first token is sampled from the logits at its
    own last *real* position and its cache continues from its own
    length. For recurrent families (ssm/hybrid) a padded prefill would
    corrupt the state, so ragged batches are prefilled per stream at
    exact length through the engine's admission path instead.

    ``eos_id``: optional early stop per stream; retired streams are
    right-padded with ``eos_id`` so the result stays (B, steps).

    ``backend``: optional kernel-backend override (auto | decode | fused
    | packed4) applied as ``cfg.replace(kernel_backend=...)``, so serve
    trees hit the requested Pallas LUT-Q path. ``return_stats=True``
    additionally returns {"t_prefill_s", "t_decode_s", "decode_tok_s",
    "backend"} from the engine's ``engine.admit`` and ``engine.decode``
    spans (``runtime/tracing.py``; the same ones the CLI prints, so
    library and CLI numbers agree).

    ``mesh``: optional ``("data", "model")`` device mesh for SPMD
    serving — params should already be placed (``shard_serve_params``);
    the engine places its slot pool/caches batch-on-data and the decode
    jits take explicit NamedShardings (see docs/sharding.md). Output is
    token-identical to the un-meshed path.

    ``speculative``: draft block length k for self-speculative decoding
    (0 disables; see ``runtime.speculative``). ``draft_bits`` sizes the
    coarsened draft view derived from the SAME LUT-Q weights unless an
    explicit ``draft_params`` tree is given. Greedy output is
    token-identical to ``speculative=0``; the cache is sized with k
    extra positions of verify-window headroom.
    """
    import numpy as np

    from repro.runtime.engine import Engine

    toks = batch["tokens"]
    B, P = toks.shape
    if lengths is not None:
        lengths = np.broadcast_to(
            np.asarray(jax.device_get(lengths), np.int32).reshape(-1), (B,))
    eng = Engine(
        params, cfg, capacity=B,
        max_len=max_len or (P + steps + int(speculative)),
        src_len=batch["frames"].shape[1] if cfg.family == "encdec" else 0,
        temperature=temperature, rng=rng, backend=backend, mesh=mesh,
        speculative=speculative, draft_bits=draft_bits,
        draft_params=draft_params)

    # recurrent state has no positions to mask and MoE expert capacity
    # couples real tokens to padding, so ANY padding (ragged or
    # uniformly short-of-P) corrupts those families — prefill each
    # stream at its exact length through the admission path instead
    padded = lengths is not None and (int(lengths.min()) != int(lengths.max())
                                      or int(lengths.max()) != P)
    if padded and (cfg.family in ("ssm", "hybrid") or cfg.n_experts):
        toks_h = np.asarray(jax.device_get(toks), np.int32)
        for i in range(B):
            eng.submit(toks_h[i, :int(lengths[i])], max_new=steps,
                       eos_id=eos_id)
        results = eng.run()
    else:
        eng.preload(batch, steps, lengths=lengths, eos_id=eos_id)
        results = eng.run()

    pad = 0 if eos_id is None else eos_id
    gen = np.full((B, steps), pad, np.int32)
    for r in results:
        t = r["tokens"]
        gen[r["rid"], :len(t)] = t
    gen = jnp.asarray(gen)
    if return_stats:
        stats = eng.stats()
        out = {
            "t_prefill_s": stats["t_prefill_s"],
            "t_decode_s": stats["t_decode_s"],
            "decode_tok_s": stats["decode_tok_s"],
            "backend": cfg.kernel_backend if backend is None else backend,
        }
        if speculative:
            for k in ("acceptance_rate", "spec_rounds",
                      "spec_tokens_per_round", "tokens_per_engine_step",
                      "draft_extra_bytes"):
                if k in stats:
                    out[k] = stats[k]
        return gen, out
    return gen
