"""Continuous-batching serving engine over the jit-cached decode path.

A fixed-capacity **slot pool**: every decode step advances all
``capacity`` slots through one jit-compiled ``decode_step + sample``
trace (fixed shapes — no retracing as traffic changes), while a FIFO
admission queue prefills new requests into free slots mid-flight and
EOS / max-token retirement frees slots immediately. This converts the
fused LUT-Q kernel win (weight bytes / HBM bandwidth per decode step)
into *served* throughput on ragged, asynchronous traffic — the decode
batch stays full instead of lock-stepping on the slowest member of a
static batch.

Lifecycle per request (see docs/serving.md):

  submit -> [queue] -> admit: requests taken the same step share ONE
                              batched prefill when exactness allows it
                              -> adapt_prefill_cache -> cache.at[slot]
         -> decode: one token per engine step, per-slot position/rng
         -> retire: EOS or max_new reached; slot freed the same step

Correctness contract: a request's tokens are **identical to a solo
``generate``** run of the same prompt (the ragged-parity suite pins
this per family, including ``kernel_backend="fused"``). Admission
prefills at the request's exact length by default — which is what makes
this hold for recurrent families (rwkv/zamba) whose state cannot mask
padding after the fact — and groups compatible requests into one
batched prefill (attention-only families batch ragged prompts via the
per-stream ``lengths`` threading in ``models.api.prefill``; recurrent
and MoE families group by exact length). ``prefill_bucket > 1``
right-pads admission prompts onto bucket boundaries for attention
families, closing the jit trace set over ragged lengths.
"""
from __future__ import annotations

import dataclasses
import functools
import itertools
import time
from collections import deque
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops
from repro.models import api
from repro.models.config import ModelConfig
from repro.runtime import paged_kv, tracing
from repro.runtime.serving import (adapt_prefill_cache, paged_chunk_fn,
                                   paged_encdec_splice_fn, paged_hydrate_fn,
                                   paged_packed_fn, paged_splice_fn,
                                   prefill_fn)
from repro.runtime.speculative import spec_step_fn


def _batch_axes(cfg: ModelConfig, max_len: int, src_len: int):
    """Per-leaf batch axis of the decode cache (structural finder;
    shared with ``partition.serve_shardings`` which needs the same
    answer to batch-shard the pool)."""
    from repro.launch.partition import cache_batch_axes

    return cache_batch_axes(cfg, max_len, src_len)


@functools.lru_cache(maxsize=64)
def _splice_fn(cfg: ModelConfig, axes: tuple, max_len: int, src_len: int,
               m: int, mesh=None, capacity: int = 0):
    """Jit-cached admission splice: adapt a batch=m prefill cache to the
    decode layout (ring relay, int8-KV quant, length override) and write
    row i into slot ``slots[i]`` of the pooled cache — one compiled
    dispatch per admission *group* instead of a trail of small
    host-driven ops. ``adapt_prefill_cache`` traces (no host sync),
    which is what makes this composition possible. Under a mesh the
    pool keeps its batch-on-data NamedShardings through the splice
    (mesh is part of the cache key — no stale traces across meshes)."""

    def splice(pool, prefill_cache, slots, lengths):
        grp = adapt_prefill_cache(cfg, prefill_cache, m, max_len,
                                  src_len=src_len, lengths=lengths)
        leaves_p, treedef = jax.tree.flatten(pool)
        leaves_g = jax.tree.leaves(grp)
        out = []
        for p, g, ax in zip(leaves_p, leaves_g, axes):
            g = g.astype(p.dtype)
            for i in range(m):
                row = jax.lax.dynamic_slice_in_dim(g, i, 1, axis=ax)
                p = jax.lax.dynamic_update_slice_in_dim(p, row, slots[i],
                                                        axis=ax)
            out.append(p)
        return jax.tree.unflatten(treedef, out)

    if mesh is None:
        return jax.jit(splice)
    from repro.launch.partition import serve_shardings

    sh = serve_shardings(cfg, mesh, batch=capacity, max_len=max_len,
                         src_len=src_len)
    return jax.jit(splice, in_shardings=(sh["cache"], None, None, None),
                   out_shardings=sh["cache"])


def _sample(logits, keys, temp, greedy: bool):
    """Per-slot sampling: logits (B,1,V) -> (tok (B,1), new keys).

    Each slot owns an rng chain, so a request's samples depend only on
    its own key — not on batch composition — which is what makes
    continuous-batch output reproducible against solo runs."""
    lg = logits[:, -1].astype(jnp.float32)
    if greedy:
        return jnp.argmax(lg, -1)[:, None].astype(jnp.int32), keys
    split = jax.vmap(jax.random.split)(keys)  # (B, 2, key)
    sub, new = split[:, 0], split[:, 1]
    tok = jax.vmap(jax.random.categorical)(sub, lg / jnp.maximum(temp, 1e-6))
    return tok[:, None].astype(jnp.int32), new


@functools.lru_cache(maxsize=64)
def _sample_fn(greedy: bool):
    # no explicit shardings: jit keys its executables on the input
    # shardings itself, so meshed and un-meshed engines can share this
    return jax.jit(functools.partial(_sample, greedy=greedy))


@functools.lru_cache(maxsize=64)
def _step_fn_cached(cfg: ModelConfig, greedy: bool, mesh, capacity: int,
                    max_len: int, src_len: int, tuning: int):
    del tuning  # lru salt: tuned tiles are baked into the trace
    axes = api.init_axes(cfg) if mesh is not None else None

    def decode_step(params, tok, cache, keys, temp):
        if mesh is not None:
            params = ops.annotate_spmd(params, axes, mesh)
        logits, cache = api.decode_step(params, cfg, tok, cache)
        tok, keys = _sample(logits, keys, temp, greedy)
        return tok, cache, keys

    if mesh is None:
        return jax.jit(decode_step)
    from repro.launch.partition import serve_shardings

    sh = serve_shardings(cfg, mesh, batch=capacity, max_len=max_len,
                         src_len=src_len)
    return jax.jit(
        decode_step,
        in_shardings=(None, sh["token"], sh["cache"], sh["keys"], None),
        out_shardings=(sh["token"], sh["cache"], sh["keys"]))


def _step_fn(cfg: ModelConfig, greedy: bool, mesh=None, capacity: int = 0,
             max_len: int = 0, src_len: int = 0):
    """One fused engine step: decode_step + per-slot sampling.

    With a mesh, the step takes explicit in/out NamedShardings
    (``partition.serve_shardings``): tok/cache/keys batch-sharded on
    the data axis, params at their committed placement and annotated
    in-trace (``ops.annotate_spmd``) so fused LUT-Q dots run on local
    index shards. The mesh is in the lru key, so one process can serve
    several meshes without trace reuse; the tuning-cache fingerprint is
    too, so ``--autotune`` invalidates traces with stale tiles."""
    return _step_fn_cached(cfg, greedy, mesh, capacity, max_len, src_len,
                           ops.tuning_fingerprint())


@functools.lru_cache(maxsize=64)
def _paged_step_fn_cached(cfg: ModelConfig, greedy: bool, mesh,
                          capacity: int, n_pages: int, page_size: int,
                          n_blocks: int, src_len: int, tuning: int):
    del tuning
    axes = api.init_axes(cfg) if mesh is not None else None

    def paged_decode_step(params, tok, cache, keys, temp):
        if mesh is not None:
            params = ops.annotate_spmd(params, axes, mesh)
        logits, cache = api.paged_decode_step(params, cfg, tok, cache,
                                              mesh=mesh)
        tok, keys = _sample(logits, keys, temp, greedy)
        return tok, cache, keys

    if mesh is None:
        return jax.jit(paged_decode_step)
    from repro.launch.partition import paged_serve_shardings

    sh = paged_serve_shardings(cfg, mesh, batch=capacity, n_pages=n_pages,
                               page_size=page_size, n_blocks=n_blocks,
                               src_len=src_len)
    return jax.jit(
        paged_decode_step,
        in_shardings=(None, sh["token"], sh["cache"], sh["keys"], None),
        out_shardings=(sh["token"], sh["cache"], sh["keys"]))


def _paged_step_fn(cfg: ModelConfig, greedy: bool, mesh=None,
                   capacity: int = 0, n_pages: int = 0, page_size: int = 0,
                   n_blocks: int = 0, src_len: int = 0):
    """Paged twin of ``_step_fn``: paged decode_step + per-slot sampling.

    The page-pool geometry is part of the lru key (it sizes the cache
    shardings under a mesh and keeps engines with different pools from
    sharing a trace)."""
    return _paged_step_fn_cached(cfg, greedy, mesh, capacity, n_pages,
                                 page_size, n_blocks, src_len,
                                 ops.tuning_fingerprint())


def synthetic_requests(cfg: ModelConfig, n: int, *, max_prompt: int,
                       max_new: int, seed: int = 0, src_len: int = 0,
                       rate: float = 0.0):
    """Deterministic ragged workload: ``n`` requests with uniform prompt
    lengths in [max(2, max_prompt//4), max_prompt], uniform max_new in
    [max(1, max_new//8), max_new] (wide on purpose — real generation
    lengths are heavy-tailed, which is exactly the straggle a lock-step
    batch pays for), and (for ``rate > 0``) Poisson arrival offsets at
    ``rate`` requests/s. Returns a list of kwargs dicts for
    ``Engine.submit`` plus an ``arrival_s`` field (callers that serve an
    open queue pop requests as their arrival time passes; batch callers
    ignore it)."""
    rng = np.random.default_rng(seed)
    t = 0.0
    reqs = []
    for i in range(n):
        L = int(rng.integers(max(2, max_prompt // 4), max_prompt + 1))
        g = int(rng.integers(max(1, max_new // 8), max_new + 1))
        req: Dict[str, Any] = {
            "tokens": rng.integers(0, cfg.vocab, size=(L,)).astype(np.int32),
            "max_new": g,
            "arrival_s": t,
        }
        if cfg.family == "encdec":
            sl = int(rng.integers(max(2, src_len // 2), src_len + 1))
            req["frames"] = rng.standard_normal((sl, cfg.d_model)).astype(np.float32)
        if cfg.family == "vlm":
            req["prefix_embeds"] = rng.standard_normal(
                (cfg.n_prefix_tokens, cfg.d_model)).astype(np.float32)
        reqs.append(req)
        if rate > 0:
            t += float(rng.exponential(1.0 / rate))
    return reqs


# cumulative counters that ``Engine.stats()`` reports
COUNTERS = ("prefill_tokens_real", "prefill_tokens_padded",
            "admitted_prompt_tokens", "prefix_hit_tokens", "decode_rows",
            "syncs", "compiles")


def _count_prefill(start: int, n_real: int, width: int):
    """One prefill entry of the step: ``n_real`` prompt tokens from
    position ``start``, computed ``width`` wide (the rest is padding)."""
    tracing.note("prefill", (start, n_real, width))
    tracing.count("prefill_tokens_real", n_real)
    tracing.count("prefill_tokens_padded", width - n_real)


def _count_admitted(prompt_len: int, hit: int):
    """One admission: its prompt length and the prefix-cache hit."""
    tracing.note("admitted", (prompt_len, hit))
    tracing.count("admitted_prompt_tokens", prompt_len)
    tracing.count("prefix_hit_tokens", hit)


@dataclasses.dataclass
class Request:
    rid: int
    tokens: np.ndarray                      # (L,) int32 prompt
    max_new: int
    eos_id: Optional[int]
    key: jax.Array
    frames: Optional[np.ndarray] = None     # encdec source embeddings (S, D)
    prefix_embeds: Optional[np.ndarray] = None  # vlm prefix (P, D)
    out: List[int] = dataclasses.field(default_factory=list)
    pstart: int = 0   # index into the engine's pending-token ring
    kv_pages: int = 0  # pages reserved at admission (paged engines)
    finish: str = ""
    t_submit: float = 0.0
    t_admit: float = 0.0
    t_first: float = 0.0
    t_done: float = 0.0


class Engine:
    """Fixed-capacity continuous-batching engine.

    Usage::

        eng = Engine(params, cfg, capacity=8, max_len=128)
        eng.submit(prompt_tokens, max_new=32, eos_id=2)
        for result in eng.run(stream=True):
            ...                      # per-request dict as it retires
        print(eng.stats())

    ``capacity``: decode slots (the fixed decode batch).
    ``max_len``: per-slot cache width in text tokens (prompt + new);
    the vlm modality prefix widens it internally.
    ``src_len``: cross-attention memory width (encdec only).
    ``prefill_bucket``: round admission prefills up to a multiple of
    this to bound jit retraces across ragged prompt lengths (attention
    families only; recurrent families always prefill exact).
    ``mesh``: optional ``("data", "model")`` device mesh. The slot pool
    (cache, pending tokens, per-slot rng chains) is placed batch-on-data
    and every engine jit — splice dispatch, decode step, sampler —
    takes explicit NamedShardings keyed on the mesh, so continuous
    batching composes with tensor parallelism (params should already be
    placed via ``distributed.sharding.shard_serve_params``). Results
    are token-identical to an un-meshed engine.
    ``speculative``: draft block length k (0 disables). Each engine step
    becomes one speculative round: a coarsened ``draft_bits`` view of
    the same LUT-Q weights (``api.draft_view`` — or an explicit
    ``draft_params``) proposes k tokens, ONE target forward over the
    k+1 window verifies them. Greedy rounds are token-identical to
    non-speculative serving; temperature uses rejection sampling
    (distribution-exact, not bitwise). Requires k extra tokens of cache
    headroom per request (``submit`` enforces it).
    """

    def __init__(self, params, cfg: ModelConfig, *, capacity: int = 8,
                 max_len: int = 128, src_len: int = 0,
                 temperature: float = 0.0, rng: Optional[jax.Array] = None,
                 backend: Optional[str] = None, prefill_bucket: int = 1,
                 mesh=None, kv_pages: Optional[int] = None,
                 page_size: int = 64, prefix_cache: bool = True,
                 max_chunk: int = 256, prefill_pack: bool = True,
                 warmup: bool = True, speculative: int = 0,
                 draft_bits: int = 3, draft_params=None):
        if backend is not None:
            cfg = cfg.replace(kernel_backend=backend)
        self.cfg = cfg
        self.params = params
        self.capacity = int(capacity)
        self.prefix = cfg.n_prefix_tokens if cfg.family == "vlm" else 0
        self.max_len = int(max_len) + self.prefix
        self.src_len = int(src_len)
        self.temperature = float(temperature)
        self.greedy = self.temperature <= 0
        self.mesh = mesh
        self.prefill_bucket = max(1, int(prefill_bucket))
        if cfg.family in ("ssm", "hybrid") or cfg.n_experts:
            # padded prefill corrupts recurrent state, and MoE routing
            # capacity couples real tokens to padding — always exact
            self.prefill_bucket = 1
        self._base_rng = rng if rng is not None else jax.random.PRNGKey(0)

        # paged KV (runtime/paged_kv.py): slot caches become block-table
        # rows over a global page pool. Families the paged layout does
        # not support (fixed-size recurrent state, MLA latents, MoE /
        # prefix-layer caches) silently keep the slot path behind the
        # same API — `stats()["paged"]` reports which path ran.
        self.paged = kv_pages is not None and api.paged_supported(cfg)

        # self-speculative decoding: a coarsened view of the SAME LUT-Q
        # weights drafts k tokens per round, one verify window checks
        # them (runtime/speculative.py). Greedy rounds are token-
        # identical to non-speculative serving; the refusal reasons
        # (activation quant, recurrent state, MLA, MoE, meshes) are
        # exactness gates, not missing plumbing.
        self.spec_k = int(speculative)
        self.draft_bits = int(draft_bits)
        self.draft_report: Optional[Dict[str, Dict]] = None
        self.n_spec_rounds = 0
        self._spec_acc_tok = 0    # accepted tokens over live slot-rounds
        self._spec_acc_draft = 0  # accepted DRAFT tokens (excl. bonus)
        self._spec_live = 0       # live slot-rounds
        if self.spec_k:
            ok, why = api.speculative_supported(cfg)
            if not ok:
                raise ValueError(why)
            if mesh is not None:
                raise ValueError(
                    "speculative decoding does not compose with SPMD "
                    "meshes yet (per-slot rewind vs sharded caches); run "
                    "speculative engines un-meshed")
            if not self.paged and cfg.window is not None:
                eff = min(self.max_len, cfg.window)
                if self.spec_k + 1 > eff:
                    raise ValueError(
                        f"speculative k={self.spec_k} needs k+1 <= ring "
                        f"width {eff} (verify window must fit in the SWA "
                        "ring)")
            if draft_params is None:
                draft_params, self.draft_report = api.draft_view(
                    params, draft_bits=self.draft_bits, with_report=True)
        self.draft_params = draft_params if self.spec_k else None
        self._chunking: Optional[Dict[str, Any]] = None
        self.n_chunk_calls = 0
        self.n_packed_groups = 0
        self.n_packed_reqs = 0
        # packed prefill: several short queue-head prompts share ONE
        # chunk call (encdec prefills whole prompts through the dense
        # prefill path — nothing to pack there). Dynamic activation
        # quantization disables it: fake_quant's per-TENSOR max scale
        # couples every row of a packed batch (and of the decode batch)
        # to its neighbours, so packed tokens would not be bit-identical
        # to unpacked serving — the same exactness discipline that pins
        # prefill_bucket=1 for recurrent families.
        self.prefill_pack = (bool(prefill_pack) and self.paged
                             and cfg.family != "encdec"
                             and cfg.act_bits >= 32)
        if self.paged:
            self.page_size = int(page_size)
            self.n_blocks = -(-self.max_len // self.page_size)
            self.n_pages = int(kv_pages)
            self.pkv = paged_kv.PagedKV(
                self.n_pages, self.page_size, self.n_blocks, self.capacity,
                # encdec KV depends on the source frames — never shareable
                prefix_cache=prefix_cache and cfg.family != "encdec")
            self.cache = api.init_paged_cache(
                cfg, self.capacity, self.n_pages, self.page_size,
                self.n_blocks, src_len=self.src_len)
            if cfg.family == "encdec":
                # encdec prefills whole prompts in one shot (the decoder
                # attends the full source anyway), padded onto pow2
                # buckets up to the context width
                self.max_chunk = paged_kv.next_pow2(self.max_len)
            else:
                self.max_chunk = min(int(max_chunk),
                                     paged_kv.next_pow2(self.max_len))
                self.wws = paged_kv.workspace_len(
                    self.max_len, self.n_blocks, self.page_size)
                from repro.models.lm import init_paged_workspace

                self.ws = init_paged_workspace(cfg, self.wws)
            self.buckets = paged_kv.prefill_buckets(self.max_chunk)
            self.prefill_chunks_per_step = 1
        else:
            self.cache = api.init_cache(cfg, self.capacity, self.max_len,
                                        src_len=self.src_len)
            self._axes = _batch_axes(cfg, self.max_len, self.src_len)
        self.tok = jnp.zeros((self.capacity, 1), jnp.int32)
        self.keys = jnp.stack([jax.random.fold_in(self._base_rng, i)
                               for i in range(self.capacity)])
        self._pool_sharding = None
        if mesh is not None:
            from repro.launch.partition import (paged_serve_shardings,
                                                serve_shardings)

            if self.paged:
                sh = paged_serve_shardings(
                    cfg, mesh, batch=self.capacity, n_pages=self.n_pages,
                    page_size=self.page_size, n_blocks=self.n_blocks,
                    src_len=self.src_len)
            else:
                sh = serve_shardings(cfg, mesh, batch=self.capacity,
                                     max_len=self.max_len,
                                     src_len=self.src_len)
            self.cache = jax.device_put(self.cache, sh["cache"])
            self.tok = jax.device_put(self.tok, sh["token"])
            self.keys = jax.device_put(self.keys, sh["keys"])
            if self.paged:
                self._pool_sharding = sh["cache"]["pool"]
        self.slots: List[Optional[Request]] = [None] * self.capacity
        self.queue: deque = deque()
        self._pending: List[jax.Array] = []  # un-synced decode tokens
        self.results: Dict[int, Dict[str, Any]] = {}
        self._next_rid = 0
        self.n_decode_steps = 0
        self.n_admitted = 0
        # this engine's span time and counters (runtime/tracing.py)
        self.totals = tracing.Totals()
        self.t_warmup = 0.0
        self._t_start: Optional[float] = None
        if self.paged and warmup:
            self._warm_paged()

    # ------------------------------------------------------------- queue

    def submit(self, tokens, *, max_new: int, eos_id: Optional[int] = None,
               rng: Optional[jax.Array] = None, frames=None,
               prefix_embeds=None) -> int:
        """Enqueue one request; returns its rid (FIFO admission order).

        ``rng``: per-request sampling key (defaults to
        ``fold_in(engine_rng, rid)``). ``generate`` gives its stream i
        the key ``fold_in(generate_rng, i)``, so to reproduce a
        temperature>0 stream against a solo ``generate(..., rng=K)``
        run, submit with ``rng=jax.random.fold_in(K, 0)``.
        """
        prompt = np.asarray(jax.device_get(tokens), np.int32).reshape(-1)
        if len(prompt) == 0:
            raise ValueError("empty prompt")
        if (len(prompt) + int(max_new) + self.prefix + self.spec_k
                > self.max_len):
            extra = (f" (+{self.spec_k} speculative headroom)"
                     if self.spec_k else "")
            raise ValueError(
                f"prompt {len(prompt)} + max_new {max_new}{extra} exceeds "
                f"engine max_len {self.max_len - self.prefix}")
        if self.cfg.family == "encdec":
            if frames is None:
                raise ValueError("encdec requests need `frames`")
            if frames.shape[0] > self.src_len:
                raise ValueError(
                    f"frames {frames.shape[0]} exceed engine src_len "
                    f"{self.src_len}")
        if self.paged:
            n_need = self.pkv.n_pages_for(
                len(prompt) + int(max_new) + self.spec_k)
            if n_need > self.n_pages - 1:
                raise ValueError(
                    f"request needs {n_need} KV pages but the pool only has "
                    f"{self.n_pages - 1} allocatable pages")
        rid = self._next_rid
        self._next_rid += 1
        key = rng if rng is not None else jax.random.fold_in(self._base_rng, rid)
        req = Request(rid, prompt, int(max_new), eos_id, key,
                      frames=frames, prefix_embeds=prefix_embeds,
                      t_submit=time.perf_counter())
        self.queue.append(req)
        return rid

    # --------------------------------------------------------- admission

    def _group_key(self, req: Request):
        """Requests admitted in the same step share one batched prefill
        when exactness allows it: attention-only families batch ragged
        prompts freely (per-stream ``lengths`` keeps them exact);
        recurrent state and MoE routing are batch-coupled under padding,
        so those group by exact prompt length; encdec additionally needs
        equal source widths (the encoder is bidirectional — padded
        frames would corrupt real positions)."""
        if self.cfg.family in ("ssm", "hybrid") or self.cfg.n_experts:
            return ("exact", len(req.tokens))
        if self.cfg.family == "encdec":
            return ("src", req.frames.shape[0])
        # text-only and prefixed vlm requests occupy different cache
        # layouts — never share a prefill
        return ("any", req.prefix_embeds is not None)

    def _admit_group(self, slots: List[int], reqs: List[Request]):
        """Prefill a group of compatible requests with ONE batched call
        and splice each row into its slot."""
        t0 = time.perf_counter()
        cfg = self.cfg
        m = len(reqs)
        Ls = [len(r.tokens) for r in reqs]
        Lb = -(-max(Ls) // self.prefill_bucket) * self.prefill_bucket
        toks = np.zeros((m, Lb), np.int32)
        for i, r in enumerate(reqs):
            toks[i, :Ls[i]] = r.tokens
        batch: Dict[str, Any] = {"tokens": jnp.asarray(toks)}
        if cfg.family == "encdec":
            batch["frames"] = jnp.stack([jnp.asarray(r.frames) for r in reqs])
        # a modality prefix occupies cache slots only when it is really
        # present (text-only vlm requests prefill without one, and the
        # group key keeps the two kinds apart)
        pfx = 0
        if reqs[0].prefix_embeds is not None:
            batch["prefix_embeds"] = jnp.stack(
                [jnp.asarray(r.prefix_embeds) for r in reqs])
            pfx = self.prefix
        lengths = jnp.asarray(Ls, jnp.int32)
        slots_j = jnp.asarray(slots, jnp.int32)

        logits, cache = prefill_fn(cfg, self.max_len, self.mesh)(
            self.params, batch, lengths)
        # prefill wants *text* lengths (its logit gather offsets the vlm
        # prefix itself); the decode cache's `len` counts cache slots,
        # which include any prefix positions
        self.cache = _splice_fn(cfg, self._axes, self.max_len, self.src_len,
                                m, self.mesh, self.capacity)(
                                    self.cache, cache, slots_j, lengths + pfx)
        keys = jnp.stack([r.key for r in reqs])
        tok1, keys1 = _sample_fn(self.greedy)(
            logits, keys, jnp.float32(self.temperature))
        self.tok = self.tok.at[slots_j].set(tok1)
        self.keys = self.keys.at[slots_j].set(keys1)
        for L in Ls:
            _count_admitted(L, 0)
            _count_prefill(0, L, Lb)
        firsts = np.asarray(self._sync(tok1[:, 0]))  # one sync per group

        now = time.perf_counter()
        for i, (slot, req) in enumerate(zip(slots, reqs)):
            req.t_admit = t0
            req.t_first = now
            req.out = [int(firsts[i])]
            req.pstart = len(self._pending)  # earlier pending rows belong
            self.slots[slot] = req           # to the slot's prior occupant
            self.n_admitted += 1
            self._maybe_retire(slot)

    def _sync(self, x):
        """Wait for ``x`` and copy it to the host: every place the host
        blocks on the device goes through here (an ``engine.sync``)."""
        with tracing.span("engine.sync"):
            tracing.count("syncs")
            return jax.device_get(x)

    def _maybe_retire(self, slot: int):
        req = self.slots[slot]
        done_eos = req.eos_id is not None and req.out[-1] == req.eos_id
        done_len = len(req.out) >= req.max_new
        if not (done_eos or done_len):
            return
        with tracing.span("engine.retire", rid=req.rid):
            self._retire(slot, req, "eos" if done_eos else "length")

    def _retire(self, slot: int, req: Request, finish: str):
        req.finish = finish
        req.t_done = time.perf_counter()
        # the request's own life, from the times it kept
        for name, t0, t1 in (("request.queue", req.t_submit, req.t_admit),
                             ("request.prefill", req.t_admit, req.t_first),
                             ("request.decode", req.t_first, req.t_done)):
            tracing.add(name, t0 * 1e9, t1 * 1e9, rid=req.rid)
        self.slots[slot] = None
        # pin the freed slot's position and token so its dead-slot
        # decode writes stay inside the slot (and stay deterministic)
        # until the next admission overwrites it
        self.cache = dict(self.cache)
        self.cache["len"] = self.cache["len"].at[slot].set(0)
        self.tok = self.tok.at[slot].set(0)
        if self.paged and self.pkv.rows[slot] is not None:
            # release the refcounts AND zero the device block row: the
            # freed pages may be reallocated immediately, and a stale
            # row would let this dead slot's trash-writes corrupt them
            self.pkv.release_slot(slot)
            self.cache["block"] = self.cache["block"].at[slot].set(0)
        self.results[req.rid] = {
            "rid": req.rid,
            "tokens": np.asarray(req.out, np.int32),
            "prompt_len": len(req.tokens),
            "n_new": len(req.out),
            "finish": req.finish,
            "t_queue_s": req.t_admit - req.t_submit,
            "t_first_token_s": req.t_first - req.t_submit,
            "t_total_s": req.t_done - req.t_submit,
            "kv_pages": req.kv_pages,
        }

    # ---------------------------------------------------- paged admission

    def _paged_admit(self):
        """Paged-mode admission: advance the in-flight chunked prefill
        (one chunk per engine step — decode slots keep stepping between
        chunks, which is the point of chunking), then FIFO-admit queue
        heads into free slots while pages hold out. A page shortfall
        defers the queue head (FIFO preserved) until retirements or
        prefix-cache eviction free pages."""
        budget = self.prefill_chunks_per_step
        while budget > 0:
            if self._chunking is not None:
                self._chunk_step()
                budget -= 1
                continue
            if not self.queue or None not in self.slots:
                return
            req = self.queue[0]
            slot = self.slots.index(None)
            if self.cfg.family == "encdec":
                if not self._admit_paged_encdec(slot, req):
                    return
                budget -= 1
                continue
            if self.prefill_pack and self._try_packed_admit():
                budget -= 1
                continue
            got = self.pkv.admit(slot, req.tokens,
                                 len(req.tokens) + req.max_new + self.spec_k)
            if got is None:
                return  # deferred: not enough pages even after eviction
            self.queue.popleft()
            row, hit = got
            req.t_admit = time.perf_counter()
            req.kv_pages = len(self.pkv.rows[slot])
            self._start_chunking(slot, req, row, hit)

    def _try_packed_admit(self) -> bool:
        """Admit several queue-head requests as ONE packed prefill call.

        A group packs consecutive FIFO requests whose tails (prompt
        minus prospective prefix hit, via the side-effect-free
        ``pkv.peek``) sum within one ``max_chunk`` bucket and whose
        kv_block-aligned workspace spans fit ``wws`` — at most one
        segment per free slot. Returns True when it consumed this
        step's chunk budget (packed call, or a degenerate single-request
        group handed to the normal chunked path); False hands admission
        back to the unpacked path with the queue untouched.
        """
        free = [i for i, s in enumerate(self.slots) if s is None]
        if len(free) < 2 or len(self.queue) < 2:
            return False
        kvb = min(self.cfg.attn_kv_block, self.wws)

        def span(L):
            return -(-L // kvb) * kvb

        plan: List[Request] = []
        c_tot = base = 0
        for req in itertools.islice(self.queue, len(free)):
            if req.frames is not None or req.prefix_embeds is not None:
                break
            L = len(req.tokens)
            tail = L - self.pkv.peek(req.tokens)
            if (tail > self.max_chunk or c_tot + tail > self.max_chunk
                    or base + span(L) > self.wws):
                break
            plan.append(req)
            c_tot += tail
            base += span(L)
        if len(plan) < 2:
            return False
        # commit: reserve pages in FIFO order, stopping at the first
        # shortfall. The fit is re-checked against the ACTUAL hit — an
        # earlier admit's eviction can shrink a later candidate's hit
        # and grow its tail past the planned bucket.
        admitted: List[tuple] = []
        c_tot = base = 0
        for req in plan:
            slot = free[len(admitted)]
            L = len(req.tokens)
            got = self.pkv.admit(slot, req.tokens,
                                 L + req.max_new + self.spec_k)
            if got is None:
                break
            row, hit = got
            if c_tot + (L - hit) > self.max_chunk or base + span(L) > self.wws:
                self.pkv.release_slot(slot)
                break
            self.queue.popleft()
            req.t_admit = time.perf_counter()
            req.kv_pages = len(self.pkv.rows[slot])
            admitted.append((slot, req, row, hit))
            c_tot += L - hit
            base += span(L)
        if not admitted:
            return False  # page shortfall at the head: defer, FIFO held
        if len(admitted) == 1:
            slot, req, row, hit = admitted[0]
            self._start_chunking(slot, req, row, hit)
            return True
        self._packed_prefill(admitted)
        return True

    def _packed_prefill(self, admitted: List[tuple]):
        """Run one fused packed prefill for an admitted group and
        install every member: per-segment logits row -> first token,
        block row + length -> device cache, prompt -> prefix cache."""
        cfg = self.cfg
        S, NB = self.capacity, self.n_blocks
        kvb = min(cfg.attn_kv_block, self.wws)
        blocks = np.zeros((S, NB), np.int32)
        bases = np.zeros(S, np.int32)
        hists = np.zeros(S, np.int32)
        lens = np.zeros(S, np.int32)
        tails = []
        base = 0
        for s, (slot, req, row, hit) in enumerate(admitted):
            blocks[s] = row
            bases[s] = base
            hists[s] = hit
            lens[s] = len(req.tokens)
            tails.append(req.tokens[hit:])
            base += -(-len(req.tokens) // kvb) * kvb
        bases[len(admitted):] = base  # inactive segments park at the end
        tail_tot = sum(len(t) for t in tails)
        C = paged_kv.next_pow2(max(tail_tot, self.buckets[0]))
        with tracing.span("engine.prefill_packed",
                          rids=[a[1].rid for a in admitted], tail=tail_tot,
                          width=C):
            toks = np.zeros((1, C), np.int32)
            toks[0, :tail_tot] = np.concatenate(tails)
            logits, pool = paged_packed_fn(cfg, self.wws, self.mesh)(
                self.params, jnp.asarray(toks), self.cache["pool"],
                jnp.asarray(blocks), jnp.asarray(bases), jnp.asarray(hists),
                jnp.asarray(lens))
            # one entry per segment; the call's padding is the last one's
            for s, (_, req, _, hit) in enumerate(admitted):
                L, last = len(req.tokens), s == len(admitted) - 1
                _count_admitted(L, hit)
                _count_prefill(hit, L - hit,
                               L - hit + (C - tail_tot if last else 0))
        self.n_chunk_calls += 1
        self.n_packed_groups += 1
        self.n_packed_reqs += len(admitted)
        self.cache = dict(self.cache)
        self.cache["pool"] = self._placed_pool(pool)
        for s, (slot, req, row, hit) in enumerate(admitted):
            with tracing.span("engine.finish_prefill", rid=req.rid):
                L = len(req.tokens)
                self.cache["block"] = self.cache["block"].at[slot].set(
                    jnp.asarray(row))
                self.cache["len"] = self.cache["len"].at[slot].set(L)
                self.pkv.insert_prefix(slot, req.tokens)
                self._install_first_token(slot, req, logits[s][None], L)

    def _start_chunking(self, slot: int, req: Request, row: np.ndarray,
                        hit_tokens: int):
        """Begin a chunked prefill. The device block row stays all-trash
        until the prefill finishes (``_finish_chunking`` installs it), so
        the slot's dead decode writes land in the trash page meanwhile.

        The workspace is ALWAYS hydrated — from cached pages on a prefix
        hit, and to zeros otherwise — so chunk inputs never depend on a
        previous request's leftovers (masked garbage is a numeric no-op,
        but a deterministic workspace keeps replays bit-stable)."""
        _count_admitted(len(req.tokens), hit_tokens)
        with tracing.span("engine.hydrate", rid=req.rid):
            row_j = jnp.asarray(row)
            self.ws = paged_hydrate_fn(self.cfg, self.wws)(
                self.cache["pool"], row_j, jnp.int32(hit_tokens))
        self._chunking = {
            "req": req, "slot": slot, "row": row_j, "hit": hit_tokens,
            "plan": paged_kv.chunk_plan(len(req.tokens), hit_tokens,
                                        self.max_chunk),
            "i": 0,
        }

    def _chunk_step(self):
        st = self._chunking
        start, width, n_real = st["plan"][st["i"]]
        req = st["req"]
        with tracing.span("engine.prefill_chunk", rid=req.rid, start=start,
                          n_real=n_real, width=width):
            _count_prefill(start, n_real, width)
            toks = np.zeros((1, width), np.int32)
            toks[0, :n_real] = req.tokens[start:start + n_real]
            logits, self.ws = paged_chunk_fn(self.cfg, self.mesh)(
                self.params, jnp.asarray(toks), self.ws, jnp.int32(start),
                jnp.int32(n_real))
        self.n_chunk_calls += 1
        st["i"] += 1
        if st["i"] == len(st["plan"]):
            self._finish_chunking(logits)

    def _finish_chunking(self, logits):
        """Commit the finished prefill: splice workspace KV [hit, L) to
        the pages (never rewriting shared prefix-hit pages), install the
        block row + length, publish the prompt to the prefix cache, and
        sample the first token."""
        st, self._chunking = self._chunking, None
        req, slot, row_j = st["req"], st["slot"], st["row"]
        with tracing.span("engine.finish_prefill", rid=req.rid):
            L = len(req.tokens)
            self.cache = dict(self.cache)
            self.cache["pool"] = self._placed_pool(paged_splice_fn(self.cfg)(
                self.cache["pool"], self.ws, row_j, jnp.int32(st["hit"]),
                jnp.int32(L)))
            self.cache["block"] = self.cache["block"].at[slot].set(row_j)
            self.cache["len"] = self.cache["len"].at[slot].set(L)
            self.pkv.insert_prefix(slot, req.tokens)
            self._install_first_token(slot, req, logits, L)

    def _placed_pool(self, pool):
        """The prefill jits leave the pool's sharding to propagation;
        under a mesh, put it back on the layout the decode step pins."""
        if self._pool_sharding is None:
            return pool
        return jax.device_put(pool, self._pool_sharding)

    def _admit_paged_encdec(self, slot: int, req: Request) -> bool:
        """encdec admission: reserve pages, run ONE bucket-padded prefill
        (per-stream ``lengths`` keeps the causal decoder exact under
        right-padding), splice self-attn KV to the pages and park the
        cross-attn memory in the slot's dense lane. Returns False on a
        page shortfall (head-of-line waits)."""
        total = len(req.tokens) + req.max_new + self.spec_k
        got = self.pkv.admit(slot, None, total)
        if got is None:
            return False
        self.queue.popleft()
        row, _ = got
        req.t_admit = time.perf_counter()
        req.kv_pages = len(self.pkv.rows[slot])
        L = len(req.tokens)
        Lb = paged_kv.next_pow2(max(L, self.buckets[0]))
        _count_admitted(L, 0)
        with tracing.span("engine.prefill_chunk", rid=req.rid, start=0,
                          n_real=L, width=Lb):
            _count_prefill(0, L, Lb)
            toks = np.zeros((1, Lb), np.int32)
            toks[0, :L] = req.tokens
            batch = {"tokens": jnp.asarray(toks),
                     "frames": jnp.asarray(req.frames)[None]}
            logits, pcache = prefill_fn(self.cfg, self.max_len, self.mesh)(
                self.params, batch, jnp.asarray([L], jnp.int32))
        with tracing.span("engine.finish_prefill", rid=req.rid):
            row_j = jnp.asarray(row)
            self.cache = dict(paged_encdec_splice_fn(self.cfg)(
                self.cache, pcache["layers"], row_j, jnp.int32(L),
                jnp.int32(slot)))
            self.cache["block"] = self.cache["block"].at[slot].set(row_j)
            self.cache["len"] = self.cache["len"].at[slot].set(L)
            self.cache["src_len"] = self.cache["src_len"].at[slot].set(
                req.frames.shape[0])
            self._install_first_token(slot, req, logits, L)
        return True

    def _install_first_token(self, slot: int, req: Request, logits,
                             length: int):
        """Shared admission tail: sample token 1, arm the slot, retire
        immediately if max_new == 1 or the first token is EOS."""
        tok1, keys1 = _sample_fn(self.greedy)(
            logits, req.key[None], jnp.float32(self.temperature))
        self.tok = self.tok.at[slot].set(tok1[0])
        self.keys = self.keys.at[slot].set(keys1[0])
        first = int(np.asarray(self._sync(tok1))[0, 0])
        req.t_first = time.perf_counter()
        req.out = [first]
        req.pstart = len(self._pending)
        self.slots[slot] = req
        self.n_admitted += 1
        self.pkv.lens[slot] = length
        self._maybe_retire(slot)

    def _release_window_pages(self):
        """Sliding-window decode never reads KV behind ``len - window``:
        free those pages (refcount-aware — shared prefix pages stay) and
        zero their device block entries so the freed physical pages
        can't be read or written through stale rows."""
        with tracing.span("engine.release_window"):
            updates = []
            for slot, r in enumerate(self.slots):
                if r is None:
                    continue
                for j in self.pkv.release_behind_window(slot,
                                                        self.cfg.window):
                    updates.append((slot, j))
            if updates:
                rows = jnp.asarray([u[0] for u in updates], jnp.int32)
                cols = jnp.asarray([u[1] for u in updates], jnp.int32)
                self.cache = dict(self.cache)
                self.cache["block"] = self.cache["block"].at[rows, cols].set(0)

    def _warm_paged(self):
        """AOT-warm every jit the paged engine can hit, closing the trace
        set at startup: all prefill bucket widths, hydrate, splice, the
        sampler, and the decode step. All calls are functional and their
        outputs are discarded — the engine cache stays zeroed. Serving
        must add no traces after this (``paged_trace_counts`` lets tests
        assert exactly that)."""
        t0 = time.perf_counter()
        cfg = self.cfg
        temp = jnp.float32(self.temperature)
        zrow = jnp.zeros((self.n_blocks,), jnp.int32)
        lg = None
        if cfg.family == "encdec":
            frames = jnp.zeros((1, self.src_len, cfg.d_model), cfg.dtype)
            for width in self.buckets:
                lg, pc = prefill_fn(cfg, self.max_len, self.mesh)(
                    self.params,
                    {"tokens": jnp.zeros((1, width), jnp.int32),
                     "frames": frames},
                    jnp.asarray([1], jnp.int32))
                paged_encdec_splice_fn(cfg)(
                    self.cache, pc["layers"], zrow, jnp.int32(0),
                    jnp.int32(0))
            # NOTE: source widths re-trace per width — warmup covers the
            # full src_len; ragged-source workloads trace on first use
        else:
            ws = paged_hydrate_fn(cfg, self.wws)(
                self.cache["pool"], zrow, jnp.int32(0))
            for width in self.buckets:
                lg, ws = paged_chunk_fn(cfg, self.mesh)(
                    self.params, jnp.zeros((1, width), jnp.int32), ws,
                    jnp.int32(0), jnp.int32(width))
            paged_splice_fn(cfg)(self.cache["pool"], ws, zrow,
                               jnp.int32(0), jnp.int32(0))
            if self.prefill_pack:
                # all-inactive group: every row masked, splice targets
                # the trash page — functional, outputs discarded
                zb = jnp.zeros((self.capacity, self.n_blocks), jnp.int32)
                zs = jnp.zeros((self.capacity,), jnp.int32)
                for width in self.buckets:
                    paged_packed_fn(cfg, self.wws, self.mesh)(
                        self.params, jnp.zeros((1, width), jnp.int32),
                        self.cache["pool"], zb, zs, zs, zs)
        _sample_fn(self.greedy)(lg, self.keys[:1], temp)
        out = _paged_step_fn(cfg, self.greedy, self.mesh, self.capacity,
                             self.n_pages, self.page_size, self.n_blocks,
                             self.src_len)(
            self.params, self.tok, self.cache, self.keys, temp)
        jax.block_until_ready(out)
        if self.spec_k:
            # the spec round is one fused trace (drafts + verify window);
            # all-zero state drafts garbage into the trash page — outputs
            # discarded, the engine cache stays zeroed
            out = spec_step_fn(cfg, k=self.spec_k, greedy=self.greedy,
                               paged=True, mesh=self.mesh)(
                self.params, self.draft_params, self.tok, self.cache,
                self.keys, temp)
            jax.block_until_ready(out)
        self.t_warmup = time.perf_counter() - t0

    def paged_trace_counts(self) -> Dict[str, int]:
        """Jit-cache entry counts for every paged entry point this
        engine drives. The warmup closes the trace set, so serving must
        not grow these — the paged test-suite asserts the dict is
        unchanged across a full serve. NOTE: the underlying jits are
        lru-shared process-wide per config, so tests comparing engines
        that share a config should assert deltas, not absolutes."""
        cfg = self.cfg
        out = {
            "decode": _paged_step_fn(
                cfg, self.greedy, self.mesh, self.capacity, self.n_pages,
                self.page_size, self.n_blocks, self.src_len)._cache_size(),
            "sample": _sample_fn(self.greedy)._cache_size(),
        }
        if self.spec_k:
            out["spec"] = spec_step_fn(
                cfg, k=self.spec_k, greedy=self.greedy, paged=True,
                mesh=self.mesh)._cache_size()
        if cfg.family == "encdec":
            pf = prefill_fn(cfg, self.max_len, self.mesh)
            if hasattr(pf, "_cache_size"):
                out["prefill"] = pf._cache_size()
            out["splice"] = paged_encdec_splice_fn(cfg)._cache_size()
        else:
            out["chunk"] = paged_chunk_fn(cfg, self.mesh)._cache_size()
            out["splice"] = paged_splice_fn(cfg)._cache_size()
            out["hydrate"] = paged_hydrate_fn(cfg, self.wws)._cache_size()
            if self.prefill_pack:
                out["packed"] = paged_packed_fn(cfg, self.wws, self.mesh)._cache_size()
        return out

    # ------------------------------------------------------ static batch

    def preload(self, batch: Dict[str, jax.Array], steps: int, *,
                lengths=None, eos_id: Optional[int] = None):
        """Admit a whole padded batch with ONE batched prefill.

        The static-batch fast path used by ``serving.generate``: the
        engine must be idle and ``batch["tokens"]`` must have exactly
        ``capacity`` rows. ``lengths`` carries per-stream real prompt
        lengths for ragged batches (attention families; see
        ``api.prefill``). Slot i samples with ``fold_in(engine_rng, i)``
        — the same key a solo ``submit`` of that request would get.
        """
        if self.queue or any(s is not None for s in self.slots):
            raise RuntimeError("preload requires an idle engine")
        if self.paged:
            raise RuntimeError("preload is a slot-pool fast path; submit "
                               "requests individually on a paged engine")
        toks = batch["tokens"]
        B, P = toks.shape
        if B != self.capacity:
            raise ValueError(f"batch {B} != capacity {self.capacity}")
        t0 = time.perf_counter()
        with tracing.span("engine.admit", totals=self.totals):
            lengths_j = (jnp.full((B,), P, jnp.int32) if lengths is None
                         else jnp.asarray(lengths, jnp.int32))
            pf = prefill_fn(self.cfg, self.max_len, self.mesh)
            if lengths is None:
                logits, cache = pf(self.params, batch)
            else:
                logits, cache = pf(self.params, batch, lengths_j)
            pfx = self.prefix if "prefix_embeds" in batch else 0
            self.cache = adapt_prefill_cache(
                self.cfg, cache, B, self.max_len, src_len=self.src_len,
                lengths=lengths_j + pfx)
            if self.mesh is not None:
                from repro.launch.partition import serve_shardings

                sh = serve_shardings(self.cfg, self.mesh, batch=self.capacity,
                                     max_len=self.max_len,
                                     src_len=self.src_len)
                self.cache = jax.device_put(self.cache, sh["cache"])
            tok1, keys = _sample_fn(self.greedy)(
                logits, self.keys, jnp.float32(self.temperature))
            self.tok, self.keys = tok1, keys
            firsts = np.asarray(self._sync(tok1[:, 0]))
            lens_h = np.asarray(jax.device_get(lengths_j))
            for L in lens_h:
                _count_admitted(int(L), 0)
                _count_prefill(0, int(L), P)

        now = time.perf_counter()
        toks_h = np.asarray(jax.device_get(toks), np.int32)
        for i in range(B):
            req = Request(self._next_rid, toks_h[i, :int(lens_h[i])],
                          int(steps), eos_id, self.keys[i],
                          t_submit=t0)
            self._next_rid += 1
            req.t_admit = t0
            req.t_first = now
            req.out = [int(firsts[i])]
            self.slots[i] = req
            self.n_admitted += 1
            self._maybe_retire(i)

    # -------------------------------------------------------------- loop

    def _materialize(self):
        """Pull all pending decode tokens to the host in one sync and
        run the retirement checks they unlock."""
        if not self._pending:
            return
        with tracing.span("engine.materialize"):
            vals = np.asarray(self._sync(jnp.stack(self._pending)))  # (k, B)
            k = len(self._pending)
            self._pending = []
            for slot, req in enumerate(self.slots):
                if req is None:
                    continue
                for j in range(req.pstart, k):
                    req.out.append(int(vals[j, slot]))
                req.pstart = 0
            for slot in range(self.capacity):
                if self.slots[slot] is not None:
                    self._maybe_retire(slot)

    def _spec_round(self):
        """One speculative round over all slots: k draft steps with the
        coarse view + ONE verify window with the target weights, then a
        single host sync of the accepted blocks. Speculation trades the
        pipelined pending-token scheme for multi-token rounds — the host
        must see ``n_acc`` each round to know how far every slot got, so
        rounds materialize eagerly (one sync per round, not per token).

        EOS landing *inside* an accepted block retires the request the
        same step: the block is truncated at EOS (or at ``max_new``) and
        the slot is freed immediately — trailing accepted tokens are
        dropped, exactly as sequential decode would never have produced
        them. The cache length still advances by the full ``n_acc`` for
        the round, which is harmless: retirement pins the freed slot's
        length to 0 and admission overwrites it."""
        fn = spec_step_fn(self.cfg, k=self.spec_k, greedy=self.greedy,
                          paged=self.paged, mesh=self.mesh)
        out, n_acc, self.cache, self.keys = fn(
            self.params, self.draft_params, self.tok, self.cache,
            self.keys, jnp.float32(self.temperature))
        # next pending token = last accepted (its KV is not written yet)
        self.tok = jnp.take_along_axis(
            out, (n_acc - 1)[:, None], axis=1).astype(jnp.int32)
        # (B, k+1) tokens and (B,) accepted counts
        out_h, acc_h = map(np.asarray, self._sync((out, n_acc)))
        self.n_spec_rounds += 1
        for slot, req in enumerate(self.slots):
            if req is None:
                continue
            A = int(acc_h[slot])
            self._spec_live += 1
            self._spec_acc_tok += A
            self._spec_acc_draft += A - 1
            for t in out_h[slot, :A]:
                req.out.append(int(t))
                if ((req.eos_id is not None and int(t) == req.eos_id)
                        or len(req.out) >= req.max_new):
                    break
            if self.paged:
                self.pkv.lens[slot] += A
            self._maybe_retire(slot)
        if self.paged and self.cfg.window is not None:
            self._release_window_pages()

    def step(self) -> List[Dict[str, Any]]:
        """One engine iteration: admit into free slots, then advance all
        slots one decode step. Returns the requests retired this step.

        Sampled tokens stay on the device as pending handles — dispatch
        runs ahead of the host — and are materialized in ONE sync only
        when a retirement decision needs their values: every step while
        a live request carries an ``eos_id`` (the decision depends on
        the token), otherwise only on the host-predictable step where
        some request reaches ``max_new``. The static ``generate`` path
        (no EOS) therefore syncs once per run, like the loop it
        replaced; admission stays per-step responsive because it needs
        a free slot, not token values."""
        if self._t_start is None:
            self._t_start = time.perf_counter()
        before = set(self.results)
        with tracing.span("engine.step", totals=self.totals):
            if self._chunking is not None or (self.queue
                                              and None in self.slots):
                with tracing.span("engine.admit"):
                    if self.paged:
                        self._paged_admit()
                    else:
                        self._admit_slots()
            active = [r for r in self.slots if r is not None]
            if active:
                tracing.count("decode_rows", len(active))
                if self.spec_k:
                    with tracing.span("engine.spec_round", rows=len(active)):
                        self._spec_round()
                else:
                    with tracing.span("engine.decode", rows=len(active)):
                        self._decode(active)
                self.n_decode_steps += 1
        return [self.results[r] for r in sorted(set(self.results) - before)]

    def _admit_slots(self):
        """Slot-pool admission: take queue heads into the free slots,
        one batched prefill per compatible group."""
        free = [i for i, s in enumerate(self.slots) if s is None]
        take = [self.queue.popleft()
                for _ in range(min(len(free), len(self.queue)))]
        groups: Dict[Any, List[Request]] = {}
        for req in take:
            groups.setdefault(self._group_key(req), []).append(req)
        for reqs in groups.values():
            slots, free = free[:len(reqs)], free[len(reqs):]
            self._admit_group(slots, reqs)

    def _decode(self, active: List[Request]):
        """Dispatch one decode step over every slot, then materialize
        the pending tokens if a retirement decision needs them."""
        if self.paged:
            fn = _paged_step_fn(self.cfg, self.greedy, self.mesh,
                                self.capacity, self.n_pages, self.page_size,
                                self.n_blocks, self.src_len)
        else:
            fn = _step_fn(self.cfg, self.greedy, self.mesh,
                          self.capacity, self.max_len, self.src_len)
        self.tok, self.cache, self.keys = fn(
            self.params, self.tok, self.cache, self.keys,
            jnp.float32(self.temperature))
        self._pending.append(self.tok[:, 0])
        if self.paged:
            for i, r in enumerate(self.slots):
                if r is not None:
                    self.pkv.lens[i] += 1
            if self.cfg.window is not None:
                self._release_window_pages()
        n_pend = len(self._pending)
        if (any(r.eos_id is not None for r in active)
                or any(len(r.out) + n_pend - r.pstart >= r.max_new
                       for r in active)):
            self._materialize()

    @property
    def idle(self) -> bool:
        return (not self.queue and self._chunking is None
                and all(s is None for s in self.slots))

    def run(self, stream: bool = False):
        """Drive the engine until every request retires.

        ``stream=True`` yields per-request result dicts as they finish;
        otherwise returns the full list ordered by rid."""

        def _gen():
            while not self.idle:
                for res in self.step():
                    yield res

        if stream:
            return _gen()
        for _ in _gen():
            pass
        return [self.results[rid] for rid in sorted(self.results)]

    # ------------------------------------------------------------- stats

    def stats(self) -> Dict[str, Any]:
        """Counts and times of this engine so far.

        ``t_prefill_s`` sums the recorder's ``engine.admit`` spans and
        ``t_decode_s`` its ``engine.decode`` and ``engine.spec_round``
        spans (``runtime/tracing.py``): host-side times, which include
        the device syncs they waited on, and read 0 with the recorder
        off. The ``COUNTERS`` are cumulative."""
        done = list(self.results.values())
        new_toks = sum(r["n_new"] for r in done)
        # first tokens come from prefill; decode produced the rest
        decoded = sum(max(r["n_new"] - 1, 0) for r in done)
        lat = sorted(r["t_total_s"] for r in done) or [0.0]
        ttft = sorted(r["t_first_token_s"] for r in done) or [0.0]
        wall = ((time.perf_counter() - self._t_start)
                if self._t_start is not None else 0.0)
        t_decode = self.totals.seconds("engine.decode", "engine.spec_round")
        out = {
            "capacity": self.capacity,
            "max_len": self.max_len,
            "backend": self.cfg.kernel_backend,
            "mesh": (None if self.mesh is None else "x".join(
                str(self.mesh.shape[a]) for a in self.mesh.axis_names)),
            "admitted": self.n_admitted,
            "completed": len(done),
            "decode_steps": self.n_decode_steps,
            "new_tokens": new_toks,
            "t_prefill_s": self.totals.seconds("engine.admit"),
            "t_decode_s": t_decode,
            "wall_s": wall,
            "decode_tok_s": decoded / t_decode if t_decode > 0 else 0.0,
            "goodput_tok_s": new_toks / max(wall, 1e-9),
            "tokens_per_engine_step": decoded / max(self.n_decode_steps, 1),
            "p50_latency_s": float(np.percentile(lat, 50)),
            "p95_latency_s": float(np.percentile(lat, 95)),
            "ttft_p50_s": float(np.percentile(ttft, 50)),
            "ttft_p99_s": float(np.percentile(ttft, 99)),
            "paged": self.paged,
        }
        out.update({k: self.totals.counts.get(k, 0) for k in COUNTERS})
        if self.spec_k:
            live = max(self._spec_live, 1)
            out["speculative_k"] = self.spec_k
            out["draft_bits"] = self.draft_bits
            out["spec_rounds"] = self.n_spec_rounds
            # fraction of proposed draft tokens the verify pass accepted
            out["acceptance_rate"] = self._spec_acc_draft / (self.spec_k * live)
            # raw accepted tokens per live slot-round (incl. the bonus /
            # correction token — >= 1 by construction)
            out["spec_tokens_per_round"] = self._spec_acc_tok / live
            if self.draft_report is not None:
                out["draft_extra_bytes"] = sum(
                    v["draft_bytes"] for v in self.draft_report.values())
                out["draft_shared_leaves"] = sum(
                    1 for v in self.draft_report.values() if v["shared"])
                out["draft_coarse_leaves"] = sum(
                    1 for v in self.draft_report.values() if not v["shared"])
        if self.paged:
            out.update(self.pkv.stats())
            out["kv_bytes_per_token"] = paged_kv.kv_bytes_per_token(self.cfg)
            out["t_warmup_s"] = self.t_warmup
            out["prefill_chunk_calls"] = self.n_chunk_calls
            out["packed_groups"] = self.n_packed_groups
            out["packed_requests"] = self.n_packed_reqs
        return out
