"""Serving driver: batched prefill + decode with LUT-Q deployment weights.

CPU scale:
    PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-1.6b --reduced \
        --batch 4 --prompt-len 32 --gen 16 --kernel-backend fused

Continuous batching (ragged queue through the slot-pool engine):
    PYTHONPATH=src python -m repro.launch.serve --arch h2o-danube-1.8b \
        --reduced --engine --max-batch 4 --queue 16 --gen 12

Tensor/data-parallel SPMD serving (see docs/sharding.md):
    XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
        python -m repro.launch.serve --arch mistral-nemo-12b --reduced \
        --mesh 2x4 --engine --kernel-backend fused

Uses the paper's deployment form (serve_view: dictionary + int8/packed
assignments, no fp masters) and reports the weight-memory footprint both
ways (fp32 vs LUT-Q) alongside throughput. Decode goes through
``runtime.serving.generate`` — the same jit-cached prefill/decode entry
points and SWA-ring cache re-layout the library path uses — and the
quantized matmuls dispatch through the kernel execution-backend layer
(``--kernel-backend``; see kernels/ops.lutq_dot and docs/kernels.md).
With ``--engine`` the same weights serve a ragged request queue through
``runtime.engine.Engine`` (see docs/serving.md) and the report adds
goodput and p50/p95 request latency.
"""
from __future__ import annotations

import argparse
import sys
from collections import Counter

import jax
import numpy as np

from repro.configs import get_config, list_archs
from repro.core.lutq import LutqState
from repro.core.policy import (backend_manifest, effective_bits,
                               format_breakdown, quantized_fraction,
                               rule_breakdown, serve_view)
from repro.core.rules import get_policy
from repro.core.spec import QuantSpec
from repro.kernels.ops import BACKENDS
from repro.launch.compile_cache import setup_compile_cache
from repro.launch.partition import device_nbytes
from repro.models import api
from repro.models.reduce import reduced
from repro.nn.tree import tree_paths
from repro.runtime.engine import Engine
from repro.runtime.serving import generate


def footprint_bytes(params) -> int:
    total = 0
    for leaf in jax.tree.leaves(params, is_leaf=lambda x: x is None):
        if leaf is not None and hasattr(leaf, "nbytes"):
            total += leaf.nbytes
    return total




def device_footprint(params, dev):
    """(quantized, dense) bytes resident on one device.

    Quantized = dictionary + assignment (+ rule id) shards of LutqState
    leaves; dense = everything else. Shared by the serve CLI report and
    ``benchmarks/shard_bench.py`` so the two always agree on what counts
    as quantized per-device weight bytes.
    """
    q = f = 0
    for _, leaf in tree_paths(params):
        if isinstance(leaf, LutqState):
            q += sum(device_nbytes(t, dev)
                     for t in (leaf.d, leaf.a, leaf.sid) if t is not None)
        elif leaf is not None and hasattr(leaf, "nbytes"):
            f += device_nbytes(leaf, dev)
    return q, f


def shard_report(params, mesh) -> str:
    """Per-device footprint + the resolved pspec of the largest leaves.

    The five largest leaves are listed with the PartitionSpec they
    actually resolved to (including divisibility fallbacks), read back
    from the placed arrays.
    """
    dev = mesh.devices.flat[0]
    q_dev, f_dev = device_footprint(params, dev)
    rows = []
    for path, leaf in tree_paths(params):
        if isinstance(leaf, LutqState):
            spec = getattr(leaf.a.sharding, "spec", None)
            rows.append((leaf.a.nbytes, "/".join(path), tuple(leaf.a.shape),
                         str(leaf.a.dtype), spec))
        elif leaf is not None and hasattr(leaf, "nbytes"):
            spec = getattr(getattr(leaf, "sharding", None), "spec", None)
            rows.append((int(leaf.nbytes), "/".join(path), tuple(leaf.shape),
                         str(leaf.dtype), spec))
    mesh_s = "x".join(str(mesh.shape[a]) for a in mesh.axis_names)
    lines = [f"[serve] mesh {mesh_s} ({','.join(mesh.axis_names)}): "
             f"per-device weights quantized {q_dev/2**20:.2f} MiB + dense "
             f"{f_dev/2**20:.2f} MiB"]
    for nbytes, path, shape, dtype, spec in sorted(rows, reverse=True)[:5]:
        lines.append(f"[serve]   {path}: {dtype}{list(shape)} "
                     f"{nbytes/2**20:.2f} MiB -> "
                     f"{spec if spec is not None else 'unplaced'}")
    return "\n".join(lines)


def check_ckpt_shapes(cfg, trainable) -> None:
    """Fail loudly when a restored train checkpoint doesn't fit the
    serve config.

    Without this, a vocab/width mismatch serves garbage silently —
    out-of-bounds embedding gathers clamp under jit instead of raising.
    Compares every restored trainable leaf against the config's
    eval_shape structure and reports the offenders with the flags that
    usually explain them.
    """
    from repro.core.policy import split_trainable

    struct, axes = api.init_struct(cfg)
    struct = jax.eval_shape(lambda p: api.quantize(p, cfg, axes), struct)
    t_struct, _ = split_trainable(struct)

    bad = []

    def walk(path, exp, got):
        if isinstance(exp, dict) or isinstance(got, dict):
            e_keys = set(exp) if isinstance(exp, dict) else set()
            g_keys = set(got) if isinstance(got, dict) else set()
            for k in sorted(e_keys | g_keys):
                if k not in e_keys or k not in g_keys:
                    bad.append(f"{'/'.join(path + (k,))}: "
                               f"{'missing from checkpoint' if k not in g_keys else 'not in model'}")
                else:
                    walk(path + (k,), exp[k], got[k])
            return
        e_shape = getattr(exp, "shape", None)
        g_shape = getattr(got, "shape", None)
        if e_shape != g_shape:
            bad.append(f"{'/'.join(path)}: model {e_shape} vs "
                       f"checkpoint {g_shape}")

    walk((), t_struct, trainable)
    if bad:
        raise SystemExit(
            "[serve] checkpoint does not fit the serve config "
            f"({len(bad)} mismatched leaves, e.g. {bad[:3]}). "
            "--arch/--reduced/--vocab (and the quant policy, when the "
            "manifest lacks one) must match the training run.")


def run_engine(params, cfg, *, capacity: int, n_requests: int,
               prompt_len: int, gen: int, seed: int = 0,
               temperature: float = 0.0, mesh=None,
               kv_pages=None, page_size: int = 64,
               prefix_cache: bool = True, requests=None,
               speculative: int = 0, draft_bits: int = 3,
               draft_params=None, probe=None):
    """Serve a ragged queue through the continuous-batching engine and
    return its stats dict (shared by the CLI and the example, so both
    report identical fields).

    ``kv_pages`` switches supported families onto the paged KV cache
    (block-table pages + prefix sharing; see docs/serving.md).
    ``requests`` overrides the synthetic workload with an explicit list
    of ``Engine.submit`` kwargs dicts. ``probe``, when given, is called
    with the engine once every request is submitted, before it runs: a
    check may step it, and may keep it to read ``results`` afterwards."""
    from repro.runtime.engine import synthetic_requests

    src_len = prompt_len if cfg.family == "encdec" else 0
    eng = Engine(params, cfg, capacity=capacity,
                 max_len=prompt_len + gen + int(speculative),
                 src_len=src_len, temperature=temperature,
                 rng=jax.random.PRNGKey(seed), mesh=mesh,
                 kv_pages=kv_pages, page_size=page_size,
                 prefix_cache=prefix_cache, speculative=speculative,
                 draft_bits=draft_bits, draft_params=draft_params)
    if requests is None:
        requests = synthetic_requests(cfg, n_requests, max_prompt=prompt_len,
                                      max_new=gen, seed=seed, src_len=src_len)
    for req in requests:
        req = dict(req)
        req.pop("arrival_s", None)
        eng.submit(**req)
    if probe is not None:
        probe(eng)
    eng.run()
    return eng.stats()


def format_engine_stats(stats) -> str:
    out = (f"[serve] engine: {stats['completed']}/{stats['admitted']} requests "
           f"on {stats['capacity']} slots | decode[{stats['backend']}]: "
           f"{stats['decode_tok_s']:.1f} tok/s | goodput "
           f"{stats['goodput_tok_s']:.1f} tok/s | latency p50 "
           f"{stats['p50_latency_s']*1e3:.0f} ms p95 "
           f"{stats['p95_latency_s']*1e3:.0f} ms | ttft p50 "
           f"{stats['ttft_p50_s']*1e3:.0f} ms p99 "
           f"{stats['ttft_p99_s']*1e3:.0f} ms | "
           f"{stats['decode_steps']} decode steps, "
           f"prefill {stats['t_prefill_s']:.2f} s, "
           f"decode {stats['t_decode_s']:.2f} s")
    if stats.get("paged"):
        out += (f"\n[serve] paged KV: {stats['pages_in_use']}/"
                f"{stats['kv_pages'] - 1} pages in use "
                f"(peak {stats['pages_peak']}) x {stats['page_size']} tokens"
                f" | {stats['kv_bytes_per_token']} KV bytes/token")
        if "prefix_hit_rate" in stats:
            out += (f" | prefix cache: {stats['prefix_hits']}/"
                    f"{stats['prefix_queries']} page hits "
                    f"({stats['prefix_hit_rate']*100:.0f}%), "
                    f"{stats['prefix_evictions']} evictions")
    if stats.get("speculative_k"):
        out += (f"\n[serve] speculative: k={stats['speculative_k']} "
                f"draft_bits={stats['draft_bits']} | acceptance "
                f"{stats['acceptance_rate']*100:.0f}% | "
                f"{stats['spec_tokens_per_round']:.2f} tok/round | "
                f"{stats['tokens_per_engine_step']:.2f} tok/engine-step")
        if "draft_extra_bytes" in stats:
            out += (f" | draft view +{stats['draft_extra_bytes']/2**10:.1f} "
                    f"KiB ({stats['draft_coarse_leaves']} coarse, "
                    f"{stats['draft_shared_leaves']} shared leaves)")
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list_archs())
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--quant-policy", default=None,
                    help="mixed-precision policy: preset name, "
                         "'uniform:<bits>[:<constraint>]', inline JSON, or "
                         "@policy.json; supersedes --quant-bits")
    ap.add_argument("--quant-bits", type=int, default=4)
    ap.add_argument("--pack4", action="store_true",
                    help="pack two 4-bit assignments per byte (K<=16 leaves)")
    ap.add_argument("--kernel-backend", default="auto", choices=list(BACKENDS),
                    help="kernel path for quantized matmuls: auto resolves "
                         "per leaf (int8 -> fused Pallas, packed -> packed4); "
                         "decode forces the dense-materialize reference; "
                         "packed4 implies --pack4")
    ap.add_argument("--engine", action="store_true",
                    help="serve a ragged FIFO queue through the "
                         "continuous-batching slot-pool engine instead of "
                         "one static batch (see docs/serving.md)")
    ap.add_argument("--max-batch", type=int, default=4,
                    help="engine slot-pool capacity (decode batch width)")
    ap.add_argument("--queue", type=int, default=16,
                    help="number of ragged requests to enqueue with --engine")
    ap.add_argument("--kv-pages", type=int, default=None,
                    help="serve through the paged KV cache with this many "
                         "pool pages (block-table slots, prefix sharing, "
                         "chunked prefill; attention/encdec families only — "
                         "others fall back to the slot pool; see "
                         "docs/serving.md)")
    ap.add_argument("--page-size", type=int, default=64,
                    help="tokens per KV page (power of two)")
    ap.add_argument("--prefix-cache", default=True,
                    action=argparse.BooleanOptionalAction,
                    help="share identical prompt-prefix pages across "
                         "requests (--no-prefix-cache disables)")
    ap.add_argument("--speculative", type=int, default=0, metavar="K",
                    help="self-speculative decoding: draft K tokens per "
                         "round from a coarsened view of the same LUT-Q "
                         "weights, verify with one target forward (greedy "
                         "output token-identical; requires --act-bits 32 — "
                         "dynamic activation quant couples draft and verify "
                         "rows; see docs/serving.md)")
    ap.add_argument("--draft-bits", type=int, default=3,
                    help="draft-view dictionary size = 2^draft_bits entries "
                         "per leaf (leaves already at or below this share "
                         "their tables with the target, costing 0 extra "
                         "bytes)")
    ap.add_argument("--act-bits", type=int, default=8, choices=(8, 32),
                    help="activation fake-quant bits for the serve regime "
                         "(32 disables; required for --speculative)")
    ap.add_argument("--mesh", default=None, metavar="DxM",
                    help="serve SPMD on a (data, model) host mesh, e.g. 2x4 "
                         "(indices tensor-parallel on the model axis, batch/"
                         "caches on data; see docs/sharding.md). On CPU set "
                         "XLA_FLAGS=--xla_force_host_platform_device_count=N "
                         "first")
    ap.add_argument("--ckpt-dir", default=None,
                    help="serve the (d, A) trained by launch/train.py: "
                         "restore the latest LUT-Q train checkpoint (solo or "
                         "sharded — the manifest's quant policy supersedes "
                         "the --quant flags) instead of initializing from "
                         "--seed; composes with --mesh for the train->serve "
                         "handoff (see docs/training.md)")
    ap.add_argument("--vocab", type=int, default=None,
                    help="override vocab size (must match the checkpoint's "
                         "when restoring with --ckpt-dir)")
    ap.add_argument("--autotune", default="off",
                    choices=("off", "cache", "search"),
                    help="kernel tile autotuning: 'cache' loads tuned tiles "
                         "(--tuning-cache file, else the checkpoint "
                         "manifest); 'search' times the pruned candidate "
                         "grid per distinct kernel shape of this serve tree "
                         "and reports the winners (see docs/kernels.md)")
    ap.add_argument("--tuning-cache", default=None, metavar="PATH",
                    help="tuning-cache JSON: read by --autotune cache, "
                         "written by --autotune search")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    setup_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    if args.vocab:
        cfg = cfg.replace(vocab=args.vocab)
    ckpt_policy = None
    if args.ckpt_dir:
        from repro.checkpoint import ckpt as ckpt_mod

        ckpt_policy = ckpt_mod.load_policy(args.ckpt_dir)
    if ckpt_policy is not None:
        cfg = cfg.replace(quant=ckpt_policy, act_bits=args.act_bits)
    elif args.quant_policy:
        cfg = cfg.replace(quant=get_policy(args.quant_policy),
                          act_bits=args.act_bits)
    else:
        cfg = cfg.replace(quant=QuantSpec(bits=args.quant_bits, min_size=1024),
                          act_bits=args.act_bits)
    cfg = cfg.replace(kernel_backend=args.kernel_backend)
    if args.speculative:
        ok, why = api.speculative_supported(cfg)
        if not ok:
            raise SystemExit(f"[serve] --speculative refused: {why}")

    mesh = None
    if args.mesh:
        from repro.launch.mesh import make_host_mesh, parse_mesh_arg

        dsz, msz = parse_mesh_arg(args.mesh)
        mesh = make_host_mesh(dsz, msz)

    if args.ckpt_dir:
        from repro.checkpoint import ckpt as ckpt_mod
        from repro.core.policy import merge_trainable

        # params subtrees only, memory-mapped: optimizer moments/EF
        # residuals are never read, and serve_view's packing decides
        # what actually lands on device (no eager full-state host copy)
        state, step = ckpt_mod.restore_params(args.ckpt_dir)
        check_ckpt_shapes(cfg, state["trainable"])
        qparams = merge_trainable(state["trainable"], state["static"])
        axes = api.init_axes(cfg)
        fp_bytes = footprint_bytes(state["trainable"])
        del state
        print(f"[serve] restored train checkpoint step {step} from "
              f"{args.ckpt_dir}"
              + (" (policy from manifest)" if ckpt_policy is not None else ""))
    else:
        params, axes = api.init(jax.random.PRNGKey(args.seed), cfg)
        fp_bytes = footprint_bytes(params)
        # one program, not op-by-op dispatch over every layer; donated,
        # so the fp masters alias the input instead of a second copy
        qparams = jax.jit(lambda p: api.quantize(p, cfg, axes),
                          donate_argnums=0)(params)
        del params
    policy = api.resolved_policy(cfg)
    pack = args.pack4 or args.kernel_backend == "packed4"
    sparams = serve_view(qparams, pack4=pack, policy=policy,
                         mesh=mesh, axes=axes)
    # the fp masters are dead weight from here on: let them go before
    # serving (at full width they are most of the device's memory)
    del qparams
    manifest = backend_manifest(sparams, policy,
                                override=args.kernel_backend)

    if args.autotune != "off":
        from repro.kernels import autotune, ops

        tc = ops.tuning_cache()
        if args.autotune == "cache":
            if args.tuning_cache:
                tc.update(autotune.TuningCache.load(args.tuning_cache))
                print(f"[serve] autotune: loaded {len(tc)} tuned tiles from "
                      f"{args.tuning_cache}")
            elif args.ckpt_dir:
                from repro.checkpoint import ckpt as ckpt_mod

                stored = ckpt_mod.load_tuning(args.ckpt_dir)
                if stored is not None:
                    tc.update(stored)
                    print(f"[serve] autotune: loaded {len(tc)} tuned tiles "
                          f"from the checkpoint manifest")
                else:
                    print("[serve] autotune: checkpoint manifest carries no "
                          "tuning cache (run --autotune search)")
            else:
                print("[serve] autotune cache: nothing to load "
                      "(--tuning-cache or --ckpt-dir required)")
        else:  # search
            batch_m = args.max_batch if args.engine else args.batch
            autotune.tune_tree(sparams, batch_m=batch_m, dtype=cfg.dtype,
                               cache=tc, emit=print)
            if args.tuning_cache:
                tc.save(args.tuning_cache)
                print(f"[serve] autotune: saved {len(tc)} tuned tiles to "
                      f"{args.tuning_cache}")
        # per-leaf report: the tile each quantized leaf's decode matmul hits
        batch_m = args.max_batch if args.engine else args.batch
        for rec in autotune.leaf_shapes_for_tree(sparams, batch_m=batch_m):
            key = autotune.make_key(
                rec["kernel"], rec["M"], rec["N"], rec["Kin"], rec["K"],
                cfg.dtype, rec["backend"],
                autotune.platform_key(ops._default_interpret()))
            tile = tc.get(key) or ops.DEFAULT_TILE
            for path in rec["paths"]:
                print(f"[serve]   tile {path}: {rec['backend']} "
                      f"bm={tile.bm} bn={tile.bn} bk={tile.bk}"
                      + ("" if tc.get(key) else " (default, untuned)"))
    q_bytes = footprint_bytes(sparams)
    print(f"[serve] {cfg.name}: weights fp32 {fp_bytes/2**20:.2f} MiB -> "
          f"LUT-Q {q_bytes/2**20:.2f} MiB ({fp_bytes/max(q_bytes,1):.2f}x) | "
          f"quantized {quantized_fraction(sparams)*100:.1f}% of params "
          f"@ {effective_bits(sparams):.2f} effective bits")
    print(format_breakdown(rule_breakdown(sparams, policy)))
    counts = Counter(m["backend"] for m in manifest.values())
    print(f"[serve] kernel backends (requested {args.kernel_backend!r}): "
          + ", ".join(f"{k}: {v} leaves" for k, v in sorted(counts.items())))
    if mesh is not None:
        print(shard_report(sparams, mesh))

    dparams = None
    if args.speculative:
        dparams, report = api.draft_view(sparams, draft_bits=args.draft_bits,
                                         with_report=True)
        extra = sum(v["draft_bytes"] for v in report.values())
        n_shared = sum(1 for v in report.values() if v["shared"])
        print(f"[serve] draft view (2^{args.draft_bits} entries): "
              f"+{extra/2**10:.1f} KiB over the target weights "
              f"({len(report) - n_shared} coarse leaves, {n_shared} shared)")
        for path, v in sorted(report.items()):
            if not v["shared"]:
                print(f"[serve]   draft {path}: K {v['K']} -> "
                      f"{v['draft_K']}, +{v['draft_bytes']/2**10:.1f} KiB")

    if args.engine:
        stats = run_engine(sparams, cfg, capacity=args.max_batch,
                           n_requests=args.queue, prompt_len=args.prompt_len,
                           gen=args.gen, seed=args.seed, mesh=mesh,
                           kv_pages=args.kv_pages, page_size=args.page_size,
                           prefix_cache=args.prefix_cache,
                           speculative=args.speculative,
                           draft_bits=args.draft_bits, draft_params=dparams)
        print(format_engine_stats(stats))
        return 0

    B, P = args.batch, args.prompt_len
    max_len = P + args.gen + args.speculative
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, P), 0, cfg.vocab)
    batch = {"tokens": toks}
    if cfg.family == "encdec":
        batch["frames"] = jax.random.normal(jax.random.PRNGKey(2),
                                            (B, P, cfg.d_model), cfg.dtype)
    if cfg.family == "vlm":
        batch["prefix_embeds"] = jax.random.normal(
            jax.random.PRNGKey(3), (B, cfg.n_prefix_tokens, cfg.d_model), cfg.dtype)

    gen, stats = generate(sparams, cfg, batch, steps=args.gen,
                          max_len=max_len, return_stats=True, mesh=mesh,
                          speculative=args.speculative,
                          draft_bits=args.draft_bits, draft_params=dparams)
    print(f"[serve] prefill {P} toks x{B}: {stats['t_prefill_s']*1e3:.1f} ms | "
          f"decode[{stats['backend']}]: {stats['decode_tok_s']:.1f} tok/s | "
          f"sample: {np.asarray(gen[0])[:8]}")
    if args.speculative:
        print(f"[serve] speculative: k={args.speculative} acceptance "
              f"{stats['acceptance_rate']*100:.0f}% | "
              f"{stats['spec_tokens_per_round']:.2f} tok/round | "
              f"{stats['tokens_per_engine_step']:.2f} tok/engine-step")
    return 0


if __name__ == "__main__":
    sys.exit(main())
