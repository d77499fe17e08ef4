#!/usr/bin/env python3
"""Chip smoke test: LUT-Q serving and training on a TPU, end to end.

Run from the root of a checkout, on a machine with a TPU:

    python3 chip_smoke.py             # serve, kernel and train phases, 1 chip
    python3 chip_smoke.py --chips 4   # sharded serving on a (data=1, model=4)
                                      # mesh against the same requests on
                                      # one chip; nothing else

It drives the functions the CLIs use (``api.init`` / ``api.quantize`` /
``serve_view``, ``launch.serve.run_engine``, ``make_train_step``) at
h2o-danube-1.8b's published widths, with random weights made from
``--seed``. Every check prints a line and any failed check exits
non-zero. The last line of standard output is one JSON object naming
the device. Without a TPU, or outside a checkout of this repository, it
exits non-zero and prints no result. One process, no subprocesses.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
ARCH = "h2o-danube-1.8b"

# serve phase: 8 requests, prompts of 128-512 tokens, 32 new tokens each
N_REQUESTS, MIN_PROMPT, MAX_PROMPT, GEN, CAPACITY = 8, 128, 512, 32, 4
PAGE, KV_PAGES = 64, 64
# train phase: published widths and vocabulary, depth cut to two layers
TRAIN_LAYERS, TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 2, 4, 512, 3

# Tolerances. Kernel outputs are compared with their oracles as relative
# L2 error ||got - want|| / ||want||: bf16 activations and bf16-rounded
# weights put honest results near 1e-3; a wrong decode or a wrong page is
# O(1). The pow2 shift-add kernel accumulates exact int32, so it must
# match its integer oracle to the f32 epilogue. Full-model logits pass
# through 24 random-weight layers with 8-bit activation fake-quant,
# whose rounding amplifies f32 reordering: one slot measured 6.8e-2 on
# a v5e; a wrong kernel gives O(1).
TOL_MATMUL = 1e-2
TOL_POW2 = 1e-6
TOL_ATTN = 2e-2
TOL_LOGITS = 0.15
# Four-chip phase: mesh logits are held to TOL_LOGITS against one chip's
# on the same inputs, and a greedy divergence is accepted only at a
# near-tie of the one-chip logits. Random-weight logits are nearly flat
# (the top two of 32000 sit a few tenths apart), and on one v5e the
# engine's decode and a prefill replay of the same prefix already order
# tokens differently at gaps up to 0.19; a wrong shard moves logits by
# O(1). Per-device quantized bytes must be about a quarter of one chip's.
TOL_TIE = 0.5
SHARE_LO, SHARE_HI = 0.2, 0.3


class CheckFailed(Exception):
    pass


def check(ok: bool, what: str) -> None:
    print(f"[check] {'ok  ' if ok else 'FAIL'} {what}", flush=True)
    if not ok:
        raise CheckFailed(what)


def log(msg: str) -> None:
    print(msg, flush=True)


def rel_l2(got, want) -> float:
    import numpy as np

    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))


def peak_gib() -> float:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use", 0) / 2**30


def compiled_kernel(fn, *args) -> tuple:
    """(compiled text has a Mosaic kernel, compile seconds) for jit(fn)."""
    import jax

    t = time.perf_counter()
    text = jax.jit(fn).lower(*args).compile().as_text()
    return "tpu_custom_call" in text, time.perf_counter() - t


# ---------------------------------------------------------------------------
# shared set-up
# ---------------------------------------------------------------------------

def serve_config():
    from repro.configs import get_config

    # the registered config: 4-bit pow2 LUT-Q, 8-bit activations
    return get_config(ARCH).replace(kernel_backend="auto")


def quantized_tree(cfg, seed: int):
    """init -> quantize at full width; returns (qparams, axes, seconds)."""
    import jax

    from repro.models import api

    t = time.perf_counter()
    params, axes = api.init(jax.random.PRNGKey(seed), cfg)
    # one program instead of op-by-op dispatch: eager quantization of
    # the full model spends minutes compiling and launching small ops.
    # Donated, so the fp masters alias the input instead of a copy.
    qparams = jax.jit(lambda p: api.quantize(p, cfg, axes),
                      donate_argnums=0)(params)
    del params
    jax.block_until_ready(qparams)
    return qparams, axes, time.perf_counter() - t


def make_requests(cfg, seed: int):
    import numpy as np

    rng = np.random.default_rng(seed)
    lens = rng.integers(MIN_PROMPT, MAX_PROMPT + 1, N_REQUESTS)
    return [{"tokens": rng.integers(0, cfg.vocab, int(n)).astype(np.int32),
             "max_new": GEN} for n in lens]


def check_manifest(sparams, cfg) -> None:
    from collections import Counter

    from repro.core.policy import backend_manifest
    from repro.models import api

    man = backend_manifest(sparams, api.resolved_policy(cfg), override="auto")
    body = {p: m["backend"] for p, m in man.items() if p.startswith("layers/")}
    counts = Counter(m["backend"] for m in man.values())
    log(f"[serve] kernel backends: "
        + ", ".join(f"{k} {v}" for k, v in sorted(counts.items())))
    check(bool(body) and all(b != "decode" for b in body.values()),
          f"every quantized body leaf resolves to a kernel "
          f"({len(body)} leaves: {sorted(set(body.values()))})")


# ---------------------------------------------------------------------------
# one chip: serve, kernel and train phases
# ---------------------------------------------------------------------------

def serve_phase(seed: int) -> dict:
    import jax

    from repro.core.policy import serve_view
    from repro.launch.serve import footprint_bytes, run_engine
    from repro.models import api

    cfg = serve_config()
    log(f"[serve] {ARCH}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"vocab {cfg.vocab}, window {cfg.window}, published widths")
    qparams, axes, t_init = quantized_tree(cfg, seed)
    sparams = serve_view(qparams, pack4=True, policy=api.resolved_policy(cfg))
    del qparams
    jax.block_until_ready(sparams)
    log(f"[serve] init + quantize + serve_view {t_init:.1f} s | LUT-Q "
        f"weights {footprint_bytes(sparams) / 2**30:.3f} GiB | peak "
        f"{peak_gib():.2f} GiB")
    check_manifest(sparams, cfg)

    seen = {}

    def probe(eng):
        # the engine's warm-up compiled every serving trace in __init__;
        # step until a slot holds a prefilled request, then take the next
        # decode step's logits under the kernels and under the dense
        # decode backend, on the live paged cache
        seen["t_warm"] = time.perf_counter() - t0
        while (sum(s is not None for s in eng.slots) < CAPACITY
               and not eng.idle):
            eng.step()
        active = [i for i, s in enumerate(eng.slots) if s is not None]

        def logits(c):
            """(decode-step logits of the live slots, Mosaic kernels in
            the compiled step) under config ``c``."""
            args = (eng.params, eng.tok, eng.cache)
            compiled = jax.jit(lambda p, t, k: api.paged_decode_step(
                p, c, t, k)[0]).lower(*args).compile()
            return (compiled(*args)[active, -1],
                    compiled.as_text().count("tpu_custom_call"))

        t = time.perf_counter()
        got, n_kernels = logits(eng.cfg)
        want, _ = logits(eng.cfg.replace(kernel_backend="decode"))
        err = rel_l2(got, want)
        agree = float((got.argmax(-1) == want.argmax(-1)).mean())
        log(f"[serve] decode-step logits, kernels vs dense decode backend: "
            f"rel L2 {err:.2e}, greedy agreement {agree:.2f} over "
            f"{len(active)} slots ({time.perf_counter() - t:.1f} s incl. "
            f"compile); {n_kernels} tpu_custom_call sites in the compiled "
            f"kernel step")
        check(n_kernels > 0, "the paged decode step runs Mosaic kernels")
        check(err <= TOL_LOGITS,
              f"decode-step logits within {TOL_LOGITS} of the decode backend")
        seen.update(n_blocks=eng.n_blocks, n_pages=eng.n_pages)
        seen["t_run0"] = time.perf_counter()

    requests = make_requests(cfg, seed)
    t0 = time.perf_counter()
    stats = run_engine(sparams, cfg, capacity=CAPACITY,
                       n_requests=N_REQUESTS, prompt_len=MAX_PROMPT,
                       gen=GEN, seed=seed, kv_pages=KV_PAGES,
                       page_size=PAGE, requests=requests, probe=probe)
    t_run = time.perf_counter() - seen["t_run0"]
    log(f"[serve] engine warm-up compile {seen['t_warm']:.1f} s | serving "
        f"after the probe {t_run:.1f} s | decode {stats['decode_tok_s']:.1f} "
        f"tok/s | goodput {stats['goodput_tok_s']:.1f} tok/s | "
        f"{stats['decode_steps']} decode steps | peak {peak_gib():.2f} GiB")
    check(stats["completed"] == N_REQUESTS,
          f"{stats['completed']}/{N_REQUESTS} requests completed")
    check(bool(stats["paged"]), "served through the paged KV cache")
    return {"n_blocks": seen["n_blocks"], "n_pages": seen["n_pages"],
            "cfg": cfg}


def kernel_phase(geom: dict, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.lutq import (LutqState, decode_any, init_state,
                                 pow2_encode)
    from repro.core.spec import QuantSpec
    from repro.kernels import ops
    from repro.kernels.paged_attn import vmem_plan
    from repro.kernels.ref import pack4_kin

    cfg = geom["cfg"]
    kin, n = cfg.d_model, cfg.d_ff
    key = jax.random.PRNGKey(seed + 1)
    kx, kw, kp = jax.random.split(key, 3)
    x = jax.random.normal(kx, (CAPACITY, kin), jnp.float32).astype(cfg.dtype)
    w = jax.random.normal(kw, (kin, n), jnp.float32) * kin ** -0.5
    free = init_state(w, QuantSpec(bits=4))
    pow2 = init_state(w, QuantSpec(bits=4, constraint="pow2"))
    leaves = {
        "fused": LutqState(w=None, d=free.d, a=free.a),
        "packed4": LutqState(w=None, d=free.d, a=pack4_kin(free.a)),
        "pow2": LutqState(w=None, d=pow2_encode(pow2.d), a=pow2.a),
    }
    with jax.default_matmul_precision("highest"):
        dense = (x.astype(jnp.float32)
                 @ decode_any(free.d, free.a).astype(jnp.float32))
    for be, leaf in leaves.items():
        def run(x, leaf, be=be):
            return ops.lutq_dot(x, leaf, backend=be, out_dtype=jnp.float32)

        has_kernel, t_c = compiled_kernel(run, x, leaf)
        got = jax.jit(run)(x, leaf)
        if be == "pow2":  # the integer decode oracle of the same leaf
            want = jax.jit(lambda x, l: ops.lutq_dot(
                x, l, backend="decode", out_dtype=jnp.float32))(x, leaf)
            tol = TOL_POW2
        else:
            want, tol = dense, TOL_MATMUL
        err = rel_l2(got, want)
        log(f"[kernel] lutq_dot {be:8s} ({CAPACITY}x{kin}) @ ({kin}x{n}): "
            f"rel L2 {err:.2e} vs oracle, compile {t_c:.1f} s")
        check(has_kernel, f"lutq_dot {be} lowers to a Mosaic kernel")
        check(bool(np.isfinite(np.asarray(got)).all()) and err <= tol,
              f"lutq_dot {be} within {tol} of its oracle")

    # paged attention on the serve phase's pool geometry, and over the
    # whole 4096-token window (the two-pass kernel)
    hkv, dh, h = cfg.n_kv_heads, cfg.d_model // cfg.n_heads, cfg.n_heads
    rng = np.random.default_rng(seed)
    for nb, label in ((geom["n_blocks"], "serve pool"),
                      (-(-cfg.window // PAGE) + 1, "4096 window")):
        n_pages = 1 + CAPACITY * nb
        for quant in (False, True):
            ks = jax.random.split(jax.random.fold_in(kp, nb * 2 + quant), 5)
            shape = (n_pages, PAGE, hkv, dh)
            if quant:
                kpool = jax.random.randint(ks[0], shape, -127, 128, jnp.int8)
                vpool = jax.random.randint(ks[1], shape, -127, 128, jnp.int8)
                kscale = (jax.random.uniform(ks[2], shape[:3]) * 0.02
                          ).astype(jnp.bfloat16)
                vscale = (jax.random.uniform(ks[3], shape[:3]) * 0.02
                          ).astype(jnp.bfloat16)
            else:
                kpool = jax.random.normal(ks[0], shape, cfg.dtype)
                vpool = jax.random.normal(ks[1], shape, cfg.dtype)
                kscale = vscale = None
            q = jax.random.normal(ks[4], (CAPACITY, 1, h, dh), cfg.dtype)
            block = jnp.asarray(1 + rng.permutation(n_pages - 1)[
                :CAPACITY * nb].reshape(CAPACITY, nb), jnp.int32)
            cl = jnp.asarray(rng.integers(1, nb * PAGE + 1, CAPACITY),
                             jnp.int32)

            def attn(q, kpool, vpool, block, cl, kscale, vscale, be):
                return ops.paged_attention(
                    q, kpool, vpool, block, cl, window=cfg.window,
                    k_scale=kscale, v_scale=vscale, backend=be)

            args = (q, kpool, vpool, block, cl, kscale, vscale)
            kern = lambda *a: attn(*a, "kernel")  # noqa: E731
            has_kernel, t_c = compiled_kernel(kern, *args)
            got = jax.jit(kern)(*args).astype(jnp.float32)
            want = jax.jit(lambda *a: attn(*a, "gather"))(*args).astype(
                jnp.float32)
            plan = vmem_plan(nb, PAGE, dh, h // hkv, quant=quant, hkv=hkv,
                             kv_itemsize=kpool.dtype.itemsize, tiled=True)
            kind = "two-pass" if plan["multipass"] else "single-pass"
            err = rel_l2(got, want)
            log(f"[kernel] paged attention {label} ({nb} pages x {PAGE}, "
                f"{'int8' if quant else 'bf16'} KV, {kind}): rel L2 "
                f"{err:.2e} vs gather, compile {t_c:.1f} s")
            check(has_kernel, f"paged attention ({label}, {kind}) lowers "
                              f"to a Mosaic kernel")
            check(bool(np.isfinite(np.asarray(got)).all()) and err <= TOL_ATTN,
                  f"paged attention ({label}, {kind}) within {TOL_ATTN} of "
                  f"the gather oracle")
    log(f"[kernel] peak {peak_gib():.2f} GiB")


def train_phase(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.core.lutq import LutqState, resolve_kmeans_impl
    from repro.core.spec import QuantSpec
    from repro.data.synthetic import MarkovLM
    from repro.models import api
    from repro.nn.tree import tree_paths
    from repro.optim.optimizers import adamw, cosine_schedule
    from repro.optim.train_state import (init_train_state, make_train_step,
                                         state_flat)

    full = get_config(ARCH)
    # the train CLI's quantization: 4-bit pow2, one k-means pass per step
    cfg = full.replace(n_layers=TRAIN_LAYERS,
                       quant=QuantSpec(bits=4, constraint="pow2",
                                       kmeans_iters=1))
    log(f"[train] {ARCH} cut to {TRAIN_LAYERS} of {full.n_layers} layers "
        f"(the full model with AdamW needs ~29 GB, one chip has 16 GB); "
        f"d_model {cfg.d_model}, d_ff {cfg.d_ff}, vocab {cfg.vocab}, batch "
        f"{TRAIN_BATCH} x seq {TRAIN_SEQ}, AdamW")
    t = time.perf_counter()
    params, axes = api.init(jax.random.PRNGKey(seed), cfg)
    params = jax.jit(lambda p: api.quantize(p, cfg, axes),
                     donate_argnums=0)(params)
    mlp = {"/".join(p): resolve_kmeans_impl(leaf.w[0].size)
           for p, leaf in tree_paths(params)
           if isinstance(leaf, LutqState) and "mlp" in p}
    check(bool(mlp) and set(mlp.values()) == {"stats"},
          f"step-4 k-means resolves to the stats kernel for the MLP leaves "
          f"({sorted(set(mlp.values()))})")
    opt = adamw(cosine_schedule(1e-3, 1, TRAIN_STEPS))
    state = state_flat(init_train_state(params, opt))
    del params
    lm = MarkovLM(cfg.vocab, seed=seed)

    def batch(i):
        return {k: jnp.asarray(v)
                for k, v in lm.batch(seed, i, TRAIN_BATCH, TRAIN_SEQ).items()}

    jax.block_until_ready(state)
    log(f"[train] init + quantize {time.perf_counter() - t:.1f} s")

    def compile_step():
        lowered = jax.jit(make_train_step(cfg, api.loss_fn, opt)).lower(
            state, batch(0))
        t = time.perf_counter()
        return lowered.compile(), time.perf_counter() - t

    _, t_cold = compile_step()
    jax.clear_caches()
    compiled, t_warm = compile_step()
    log(f"[train] train-step compile, tracing excluded: {t_cold:.1f} s "
        f"first, {t_warm:.1f} s again after clearing the in-memory caches "
        f"(persistent cache)")
    check("tpu_custom_call" in compiled.as_text(),
          "the train step runs Mosaic kernels (k-means stats)")

    losses, times = [], []
    for i in range(TRAIN_STEPS):
        t = time.perf_counter()
        state, metrics = compiled(state, batch(i))
        losses.append(float(metrics["loss"]))
        times.append(time.perf_counter() - t)
    steady = times[1:] or times
    tok_s = TRAIN_BATCH * TRAIN_SEQ * len(steady) / sum(steady)
    log(f"[train] loss {' -> '.join(f'{x:.4f}' for x in losses)} | step "
        f"{np.median(steady) * 1e3:.1f} ms | {tok_s:.0f} tok/s | peak "
        f"{peak_gib():.2f} GiB")
    check(all(np.isfinite(losses)), "train loss finite at every step")


# ---------------------------------------------------------------------------
# four chips: sharded serving against one chip
# ---------------------------------------------------------------------------

def replay_logits(cfg, params, mesh, seqs):
    """Last-position logits of each token sequence, run as one
    right-padded prefill batch: (len(seqs), vocab) f32."""
    import jax.numpy as jnp
    import numpy as np

    from repro.runtime.serving import prefill_fn

    width = MAX_PROMPT + GEN
    toks = np.zeros((len(seqs), width), np.int32)
    for i, t in enumerate(seqs):
        toks[i, :len(t)] = t
    lens = jnp.asarray([len(t) for t in seqs], jnp.int32)
    logits, _ = prefill_fn(cfg, width, mesh)(
        params, {"tokens": jnp.asarray(toks)}, lens)
    return np.asarray(logits, np.float32).reshape(len(seqs), -1)


def four_chip_phase(seed: int) -> None:
    import jax
    import numpy as np

    from repro.core.policy import serve_view
    from repro.launch.mesh import make_host_mesh
    from repro.launch.serve import device_footprint, run_engine
    from repro.models import api

    devs = jax.devices()
    check(len(devs) >= 4, f"{len(devs)} devices visible (4 needed)")
    cfg = serve_config()
    mesh = make_host_mesh(1, 4)
    qparams, axes, t_init = quantized_tree(cfg, seed)
    policy = api.resolved_policy(cfg)
    solo = serve_view(qparams, pack4=True, policy=policy)
    meshed = serve_view(qparams, pack4=True, policy=policy, mesh=mesh,
                        axes=axes)
    del qparams
    jax.block_until_ready((solo, meshed))
    log(f"[mesh] {ARCH} on a (data=1, model=4) mesh vs devices[0] alone; "
        f"init + quantize {t_init:.1f} s")
    check_manifest(meshed, cfg)

    q_solo, _ = device_footprint(solo, devs[0])
    for d in mesh.devices.flat:
        q_dev, f_dev = device_footprint(meshed, d)
        share = q_dev / q_solo
        log(f"[mesh] {d}: quantized {q_dev / 2**20:.1f} MiB "
            f"({share:.3f} of one chip's {q_solo / 2**20:.1f} MiB), dense "
            f"{f_dev / 2**20:.1f} MiB")
        check(SHARE_LO <= share <= SHARE_HI,
              f"{d} holds about a quarter of the quantized weight bytes")

    requests = make_requests(cfg, seed)
    engines = {}
    out = {}
    for name, params, m in (("solo", solo, None), ("mesh", meshed, mesh)):
        t = time.perf_counter()
        out[name] = run_engine(params, cfg, capacity=CAPACITY,
                               n_requests=N_REQUESTS, prompt_len=MAX_PROMPT,
                               gen=GEN, seed=seed, mesh=m,
                               kv_pages=KV_PAGES, page_size=PAGE,
                               requests=requests,
                               probe=lambda e, n=name: engines.__setitem__(
                                   n, e))
        st = out[name]
        log(f"[mesh] {name}: {st['completed']}/{N_REQUESTS} requests, "
            f"decode {st['decode_tok_s']:.1f} tok/s, wall "
            f"{time.perf_counter() - t:.1f} s incl. compile")
        check(st["completed"] == N_REQUESTS and bool(st["paged"]),
              f"{name}: every request completed on the paged cache")

    # Teacher-forced comparison: both trees replay each request's prompt
    # plus the new tokens the two engines share, so they see the same
    # inputs; where the greedy streams fork, the replay gives the
    # one-chip logit gap between the two chosen tokens.
    res = {k: e.results for k, e in engines.items()}
    rids = sorted(res["solo"])
    forks, seqs = [], []
    for rid in rids:
        a = np.asarray(res["solo"][rid]["tokens"])
        b = np.asarray(res["mesh"][rid]["tokens"])
        n = min(len(a), len(b))
        diff = np.flatnonzero(a[:n] != b[:n])
        t = int(diff[0]) if diff.size else (None if len(a) == len(b) else n)
        forks.append((t, a, b))
        seqs.append(np.concatenate([requests[rid]["tokens"],
                                    a[:n - 1 if t is None else t]]))
    t = time.perf_counter()
    lg_solo = replay_logits(cfg, solo, None, seqs)
    lg_mesh = replay_logits(cfg, meshed, mesh, seqs)
    log(f"[mesh] replayed {len(seqs)} requests on both trees "
        f"({time.perf_counter() - t:.1f} s incl. compile)")
    errs, gaps = [], []
    for rid, (t, a, b), s_, m_ in zip(rids, forks, lg_solo, lg_mesh):
        errs.append(rel_l2(m_, s_))
        where = "identical greedy tokens"
        if t is not None:
            gap = (float(s_[a[t]] - s_[b[t]])
                   if t < min(len(a), len(b)) else 0.0)
            gaps.append(abs(gap))
            where = (f"first divergence at new token {t} "
                     f"({a[t] if t < len(a) else 'end'} vs "
                     f"{b[t] if t < len(b) else 'end'}), one-chip logit "
                     f"gap {gap:+.4f}")
        log(f"[mesh] request {rid}: {where}; logits mesh vs one chip rel "
            f"L2 {errs[-1]:.2e}, max |diff| {np.abs(m_ - s_).max():.4f}")
    log(f"[mesh] greedy tokens identical for {len(rids) - len(gaps)}/"
        f"{len(rids)} requests")
    check(max(errs) <= TOL_LOGITS,
          f"mesh logits within {TOL_LOGITS} (rel L2) of one chip's on the "
          f"same inputs")
    check(max(gaps, default=0.0) <= TOL_TIE,
          f"every greedy divergence is a near-tie (one-chip gap <= "
          f"{TOL_TIE})")
    log(f"[mesh] peak on devices[0] {peak_gib():.2f} GiB")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded-serving comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print("chip_smoke: src/repro not found next to this script; run it "
              "from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))

    import jax

    from repro.launch.compile_cache import setup_compile_cache

    cache = setup_compile_cache()
    if jax.default_backend() != "tpu":
        print(f"chip_smoke: JAX found no TPU (backend "
              f"{jax.default_backend()!r})", file=sys.stderr)
        return 1
    dev = jax.devices()[0]
    log(f"[setup] {dev.platform} {dev.device_kind} x {len(jax.devices())}, "
        f"jax {jax.__version__}, compile cache {cache}")

    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            t = time.perf_counter()
            four_chip_phase(args.seed)
            log(f"[mesh] phase {time.perf_counter() - t:.1f} s")
        else:
            t = time.perf_counter()
            geom = serve_phase(args.seed)
            log(f"[serve] phase {time.perf_counter() - t:.1f} s")
            t = time.perf_counter()
            kernel_phase(geom, args.seed)
            log(f"[kernel] phase {time.perf_counter() - t:.1f} s")
            t = time.perf_counter()
            train_phase(args.seed)
            log(f"[train] phase {time.perf_counter() - t:.1f} s")
    except CheckFailed as e:
        print(f"chip_smoke: check failed: {e}", file=sys.stderr)
        return 1
    log(f"[setup] total {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
