"""Training driver.

CPU scale (this container):
    PYTHONPATH=src python -m repro.launch.train --arch h2o-danube-1.8b \
        --reduced --steps 100 --batch 8 --seq 64 --ckpt-dir /tmp/ckpt

Mesh-parallel SPMD training (see docs/training.md). The same step runs
over a ("data", "model") mesh with FSDP/DP-sharded masters + optimizer
moments, shard-local step-4 k-means (per-shard sums/counts combined via
psum — exact, no gather), and optionally the compressed data-parallel
gradient exchange:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
        python -m repro.launch.train --arch h2o-danube-1.8b --reduced \
        --steps 50 --mesh 2x4 --grad-compress ef --ckpt-dir /tmp/ckpt

The resulting (sharded) checkpoint restores straight into
``launch/serve.py --ckpt-dir ... --mesh DxM`` — the PR 4 sharded serving
path. At pod scale the same driver runs per-host after
``jax.distributed.initialize()`` with the same mesh axes and shardings.
"""
from __future__ import annotations

import argparse
import sys

import jax
import jax.numpy as jnp

from repro.configs import get_config, list_archs
from repro.core.rules import get_policy
from repro.core.spec import QuantSpec
from repro.data.synthetic import MarkovLM
from repro.distributed.compress import (GRAD_COMPRESS_MODES,
                                        dp_grad_transform, trainable_pspecs)
from repro.launch.compile_cache import setup_compile_cache
from repro.launch.mesh import make_host_mesh, parse_mesh_arg
from repro.launch import partition
from repro.models import api
from repro.models.reduce import reduced
from repro.optim.optimizers import adamw, cosine_schedule
from repro.optim.train_state import init_train_state, make_train_step, state_flat
from repro.runtime.loop import TrainLoop


def _train_cfg(args):
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    if getattr(args, "quant_policy", None):
        policy = get_policy(args.quant_policy)
        cfg = cfg.replace(quant=policy, act_bits=args.act_bits)
        print(policy.describe())
    elif args.quant_bits > 0:
        cfg = cfg.replace(quant=QuantSpec(bits=args.quant_bits,
                                          constraint=args.quant_constraint,
                                          kmeans_iters=1,
                                          min_size=args.quant_min_size),
                          act_bits=args.act_bits)
    else:
        cfg = cfg.replace(quant=None, act_bits=32)
    if args.vocab:
        cfg = cfg.replace(vocab=args.vocab)
    return cfg


def build(args, mesh=None):
    """(cfg, state, step_fn, shardings) for one training run.

    ``mesh=None`` is the solo path (caller jits the returned step).
    With a mesh the state is placed onto its train NamedShardings and
    the step comes back jitted with explicit in/out shardings; with
    ``args.grad_compress`` the compressed-DP grad_transform is installed
    and the state carries the error-feedback tree.
    """
    cfg = _train_cfg(args)
    compress = getattr(args, "grad_compress", None)

    params, axes = api.init(jax.random.PRNGKey(args.seed), cfg)
    params = api.quantize(params, cfg, axes)
    opt = adamw(cosine_schedule(args.lr, args.warmup, args.steps))
    state = state_flat(init_train_state(params, opt,
                                        grad_compress=bool(compress)))
    shardings = None
    if mesh is not None:
        shardings = partition.train_shardings(
            cfg, mesh, batch=args.batch, seq=args.seq,
            grad_compress=bool(compress))
        state = partition.place_state(state, shardings["state"])
    grad_transform = (dp_grad_transform(
        mesh, mode=compress,
        pspecs=None if shardings is None
        else trainable_pspecs(shardings["state"]))
        if compress else None)
    step_fn = make_train_step(cfg, api.loss_fn, opt,
                              microbatches=args.microbatches,
                              grad_transform=grad_transform,
                              shardings=shardings)
    return cfg, state, step_fn, shardings


def train_report(state, mesh) -> str:
    """Per-device master/static bytes + the resolved pspecs of the
    largest trainable leaves (train-side twin of serve's shard_report)."""
    from repro.launch.partition import device_nbytes
    from repro.nn.tree import tree_paths

    dev = mesh.devices.flat[0]
    t_dev = sum(device_nbytes(l, dev)
                for _, l in tree_paths(state["trainable"])
                if l is not None and hasattr(l, "nbytes"))
    s_dev = sum(device_nbytes(l, dev)
                for _, l in tree_paths(state["static"])
                if l is not None and hasattr(l, "nbytes"))
    rows = sorted(((int(l.nbytes), "/".join(p),
                    getattr(getattr(l, "sharding", None), "spec", None))
                   for p, l in tree_paths(state["trainable"])
                   if l is not None and hasattr(l, "nbytes")), reverse=True)
    mesh_s = "x".join(str(mesh.shape[a]) for a in mesh.axis_names)
    lines = [f"[train] mesh {mesh_s} ({','.join(mesh.axis_names)}): "
             f"per-device masters {t_dev/2**20:.2f} MiB + "
             f"LUT-Q/static {s_dev/2**20:.2f} MiB"]
    for nbytes, path, spec in rows[:3]:
        lines.append(f"[train]   {path}: {nbytes/2**20:.2f} MiB -> "
                     f"{spec if spec is not None else 'unplaced'}")
    return "\n".join(lines)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list_archs())
    ap.add_argument("--reduced", action="store_true",
                    help="CPU-scale reduced config (same structure)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--vocab", type=int, default=256)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--warmup", type=int, default=10)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--quant-policy", default=None,
                    help="mixed-precision policy: preset name "
                         "(paper_default | serving_aggressive | mixed_paper), "
                         "'uniform:<bits>[:<constraint>]', inline JSON, or "
                         "@policy.json; supersedes --quant-bits")
    ap.add_argument("--quant-bits", type=int, default=4,
                    help="legacy uniform knob (ignored when --quant-policy "
                         "is given)")
    ap.add_argument("--quant-constraint", default="pow2",
                    choices=["none", "pow2", "binary", "ternary"])
    ap.add_argument("--quant-min-size", type=int, default=4096)
    ap.add_argument("--act-bits", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--data-seed", type=int, default=0)
    ap.add_argument("--mesh", default=None, metavar="DxM",
                    help="train SPMD on a (data, model) host mesh, e.g. 2x4 "
                         "(FSDP/DP masters+moments, tensor-parallel kernels, "
                         "shard-local k-means; see docs/training.md). On CPU "
                         "set XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=N first")
    ap.add_argument("--grad-compress", default=None,
                    choices=list(GRAD_COMPRESS_MODES),
                    help="compressed data-parallel gradient exchange: 'ef' = "
                         "error-feedback int8 (compressed-collective "
                         "arithmetic), 'ring' = ef + the explicit f16-payload "
                         "ppermute ring over the data axis")
    ap.add_argument("--autotune", default="off",
                    choices=("off", "cache", "search"),
                    help="kernel tile autotuning for the train->serve "
                         "handoff: 'cache' loads tuned tiles from "
                         "--tuning-cache; 'search' tunes this config's "
                         "serve-form kernel shapes before training. Either "
                         "way the cache rides in every checkpoint manifest "
                         "(serve --autotune cache picks it up)")
    ap.add_argument("--tuning-cache", default=None, metavar="PATH",
                    help="tuning-cache JSON: read by --autotune cache, "
                         "written by --autotune search")
    ap.add_argument("--autotune-batch", type=int, default=8,
                    help="decode batch M the --autotune search tunes for "
                         "(match the serve engine's --max-batch)")
    args = ap.parse_args(argv)
    setup_compile_cache()

    mesh = None
    if args.mesh:
        dsz, msz = parse_mesh_arg(args.mesh)
        mesh = make_host_mesh(dsz, msz)

    cfg, state, step_fn, shardings = build(args, mesh)
    if mesh is None:
        step_fn = jax.jit(step_fn)
    else:
        print(train_report(state, mesh))

    tuning = None
    if args.autotune != "off":
        from repro.core.policy import serve_view
        from repro.kernels import autotune, ops

        tuning = ops.tuning_cache()
        if args.autotune == "cache":
            if args.tuning_cache:
                tuning.update(autotune.TuningCache.load(args.tuning_cache))
                print(f"[train] autotune: loaded {len(tuning)} tuned tiles "
                      f"from {args.tuning_cache}")
            else:
                print("[train] autotune cache: --tuning-cache required")
        else:  # search the serve-form shapes this run will deploy as
            from repro.core.policy import merge_trainable

            sv = serve_view(merge_trainable(state["trainable"],
                                            state["static"]),
                            policy=api.resolved_policy(cfg))
            autotune.tune_tree(sv, batch_m=args.autotune_batch,
                               dtype=cfg.dtype, cache=tuning, emit=print)
            if args.tuning_cache:
                tuning.save(args.tuning_cache)
                print(f"[train] autotune: saved {len(tuning)} tuned tiles "
                      f"to {args.tuning_cache}")
            del sv

    lm = MarkovLM(cfg.vocab, seed=args.data_seed)

    def make_batch(step):
        b = lm.batch(args.data_seed, step, args.batch, args.seq)
        batch = {k: jnp.asarray(v) for k, v in b.items()}
        if cfg.family == "encdec":
            key = jax.random.fold_in(jax.random.PRNGKey(7), step)
            batch["frames"] = jax.random.normal(
                key, (args.batch, args.seq, cfg.d_model), cfg.dtype)
        if cfg.family == "vlm":
            key = jax.random.fold_in(jax.random.PRNGKey(8), step)
            batch["prefix_embeds"] = jax.random.normal(
                key, (args.batch, cfg.n_prefix_tokens, cfg.d_model), cfg.dtype)
        return batch

    from repro.models.api import resolved_policy
    loop = TrainLoop(step_fn, make_batch, ckpt_dir=args.ckpt_dir,
                     ckpt_every=args.ckpt_every, log_every=10,
                     quant_policy=resolved_policy(cfg),
                     shardings=None if shardings is None
                     else shardings["state"],
                     mesh=mesh, tuning=tuning)
    state, step = loop.run(state, args.steps)
    losses = [h["loss"] for h in loop.history]
    if losses:
        print(f"[train] {args.arch}: loss {losses[0]:.4f} -> {losses[-1]:.4f} "
              f"(floor ~{lm.entropy_floor():.3f}) in {step} steps")
    return 0


if __name__ == "__main__":
    sys.exit(main())
