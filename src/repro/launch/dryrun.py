import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this:
  1. builds the quantized model state via jax.eval_shape (no allocation),
  2. assigns NamedShardings from the logical-axis rules,
  3. jits the right entry point (train_step / prefill / decode_step),
  4. ``.lower().compile()`` on the production mesh,
  5. records memory_analysis, cost_analysis FLOPs/bytes and the
     per-collective byte counts parsed from the optimized HLO,
  6. writes one JSON artifact per cell for the roofline layer.

Run:  PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all \
          --mesh both --out benchmarks/artifacts/dryrun
"""
import argparse
import json
import re
import sys
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs import get_config, list_archs
from repro.launch.mesh import make_production_mesh
from repro.launch import partition
from repro.models import api
from repro.models.api import SHAPES
from repro.optim.optimizers import adamw, sgd
from repro.optim.train_state import init_train_state, make_train_step, state_flat

HLO_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                   "collective-permute")

_DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
                "u8": 1, "pred": 1, "s64": 8, "u64": 8, "f64": 8, "s16": 2,
                "u16": 2}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _op_bytes(shape_str: str) -> int:
    """Bytes of one HLO shape string like 'bf16[16,4096,128]'."""
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str):
    """Sum output-shape bytes of every collective op in the optimized HLO.

    These are per-device shapes (SPMD), so totals are per-device wire
    bytes — exactly what the ICI roofline term wants.
    """
    out = {k: 0 for k in HLO_COLLECTIVES}
    counts = {k: 0 for k in HLO_COLLECTIVES}
    for line in hlo_text.splitlines():
        ls = line.strip()
        m = re.match(r"(?:ROOT )?%?[\w.-]+ = (\([^)]*\)|[^ ]+) ([\w-]+)", ls)
        if not m:
            continue
        shape_str, opname = m.group(1), m.group(2)
        for c in HLO_COLLECTIVES:
            if opname == c or opname.startswith(c + "-start") or opname == c + "-done":
                if opname.endswith("-done"):
                    continue  # counted at -start
                out[c] += _op_bytes(shape_str)
                counts[c] += 1
    return out, counts


def _pick_optimizer(n_params: int):
    # paper-faithful SGD+momentum for the giants (3 state bytes/param
    # incl. int8 assignments), AdamW for the rest
    if n_params >= 5e10:
        return sgd(1e-2, momentum=0.9), "sgd_momentum"
    return adamw(3e-4), "adamw"


def build_cell(arch: str, shape_name: str, mesh, overrides=None):
    """Returns (lower_fn, meta) or raises Skip.

    overrides: dict of ModelConfig field -> value (plus the special key
    "microbatches") — used by §Perf to lower optimized variants while
    the unsuffixed artifacts stay paper-faithful baselines.
    """
    cfg = get_config(arch)
    overrides = dict(overrides or {})
    micro_override = overrides.pop("microbatches", None)
    if overrides:
        cfg = cfg.replace(**overrides)
    shape = SHAPES[shape_name]
    ok, why = api.supports_shape(cfg, shape)
    if not ok:
        raise SkipCell(why)

    cap = {}

    def initp(k):
        p, a = api.init(k, cfg)
        cap["axes"] = a
        return p

    params_struct = jax.eval_shape(initp, jax.random.PRNGKey(0))
    params_struct = jax.eval_shape(
        lambda p: api.quantize(p, cfg, cap["axes"]), params_struct)
    axes = cap["axes"]
    n_params = sum(l.w.size if hasattr(l, "w") and l.w is not None else
                   (l.size if hasattr(l, "size") else 0)
                   for l in jax.tree.leaves(
                       params_struct,
                       is_leaf=lambda x: hasattr(x, "w") or x is None))

    batch_struct = api.input_specs(cfg, shape)
    dp_total = 1
    for a in ("pod", "data"):
        if a in mesh.axis_names:
            dp_total *= mesh.shape[a]

    if shape.kind == "train":
        opt, opt_name = _pick_optimizer(n_params)
        per_shard = shape.global_batch // dp_total
        microbatches = max(1, min(per_shard, 16 if n_params >= 5e10 else 8))
        if micro_override is not None:
            microbatches = micro_override
        state_struct = jax.eval_shape(
            lambda p: state_flat(init_train_state(p, opt)), params_struct)
        state_sh = partition.train_state_shardings(axes, params_struct,
                                                   state_struct, mesh)
        batch_sh = partition.data_batch_shardings(batch_struct, mesh)
        step_fn = make_train_step(cfg, api.loss_fn, opt,
                                  microbatches=microbatches)
        jf = jax.jit(step_fn, in_shardings=(state_sh, batch_sh),
                     out_shardings=(state_sh, None))
        lower = lambda: jf.lower(state_struct, batch_struct)
        meta = {"kind": "train", "optimizer": opt_name,
                "microbatches": microbatches}
    else:
        from repro.core.policy import serve_view
        sparams_struct = jax.eval_shape(
            lambda p: serve_view(p, pack4=cfg.pack_assignments), params_struct)
        params_sh = partition.params_shardings(axes, sparams_struct, mesh)
        if shape.kind == "prefill":
            batch_sh = partition.data_batch_shardings(batch_struct, mesh)
            jf = jax.jit(
                lambda p, b: api.prefill(p, cfg, b, max_len=shape.seq_len),
                in_shardings=(params_sh, batch_sh))
            lower = lambda: jf.lower(sparams_struct, batch_struct)
            meta = {"kind": "prefill"}
        else:
            token_struct = batch_struct["token"]
            cache_struct = batch_struct["cache"]
            token_sh = partition.token_shardings(token_struct, mesh)
            cache_sh = partition.cache_shardings(cache_struct, mesh)
            jf = jax.jit(
                lambda p, t, c: api.decode_step(p, cfg, t, c),
                in_shardings=(params_sh, token_sh, cache_sh),
                out_shardings=(None, cache_sh))
            lower = lambda: jf.lower(sparams_struct, token_struct, cache_struct)
            meta = {"kind": "decode"}

    meta.update(arch=arch, shape=shape_name, n_params=int(n_params),
                seq_len=shape.seq_len, global_batch=shape.global_batch)
    return lower, meta


class SkipCell(Exception):
    pass


def run_cell(arch: str, shape_name: str, *, multi_pod: bool, out_dir: Path,
             force: bool = False, overrides=None, variant: str = ""):
    mesh_tag = "pod2x16x16" if multi_pod else "pod16x16"
    suffix = f"__{variant}" if variant else ""
    out_path = out_dir / mesh_tag / f"{arch}__{shape_name}{suffix}.json"
    out_path.parent.mkdir(parents=True, exist_ok=True)
    if out_path.exists() and not force:
        print(f"[dryrun] {mesh_tag}/{arch}/{shape_name}{suffix}: cached")
        return json.loads(out_path.read_text())

    mesh = make_production_mesh(multi_pod=multi_pod)
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_tag,
           "devices": int(len(jax.devices())),
           "variant": variant or "baseline", "overrides": overrides or {}}
    try:
        lower_fn, meta = build_cell(arch, shape_name, mesh, overrides)
        rec.update(meta)
        t0 = time.time()
        with mesh:
            lowered = lower_fn()
            rec["lower_s"] = round(time.time() - t0, 2)
            t0 = time.time()
            compiled = lowered.compile()
            rec["compile_s"] = round(time.time() - t0, 2)

            ma = compiled.memory_analysis()
            rec["memory"] = {
                "argument_bytes": int(ma.argument_size_in_bytes),
                "output_bytes": int(ma.output_size_in_bytes),
                "temp_bytes": int(ma.temp_size_in_bytes),
                "alias_bytes": int(ma.alias_size_in_bytes),
            }
            ca = compiled.cost_analysis() or {}
            rec["cost"] = {
                "flops": float(ca.get("flops", -1)),
                "bytes_accessed": float(ca.get("bytes accessed", -1)),
                "transcendentals": float(ca.get("transcendentals", -1)),
            }
            t0 = time.time()
            hlo = compiled.as_text()
            coll, counts = collective_bytes(hlo)
            rec["collectives_bytes"] = coll
            rec["collectives_count"] = counts
            rec["hlo_parse_s"] = round(time.time() - t0, 2)
            rec["status"] = "ok"
            print(f"[dryrun] {mesh_tag}/{arch}/{shape_name}: OK "
                  f"compile={rec['compile_s']}s flops={rec['cost']['flops']:.3e} "
                  f"temp={rec['memory']['temp_bytes']/2**30:.2f}GiB/dev")
    except SkipCell as e:
        rec["status"] = "skipped"
        rec["reason"] = str(e)
        print(f"[dryrun] {mesh_tag}/{arch}/{shape_name}: SKIP ({e})")
    except Exception as e:  # noqa: BLE001 — report, don't crash the sweep
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
        print(f"[dryrun] {mesh_tag}/{arch}/{shape_name}: ERROR {rec['error']}")
    out_path.write_text(json.dumps(rec, indent=1))
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="benchmarks/artifacts/dryrun")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--variant", default="",
                    help="artifact suffix for optimized lowerings")
    ap.add_argument("--override", action="append", default=[],
                    help="cfg field=value (repeatable); special key "
                         "microbatches=N")
    args = ap.parse_args(argv)

    overrides = {}
    for kv in args.override:
        k, v = kv.split("=", 1)
        try:
            v = json.loads(v)
        except json.JSONDecodeError:
            pass
        overrides[k] = v

    archs = list_archs() if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    out_dir = Path(args.out)

    results = []
    for multi_pod in meshes:
        for arch in archs:
            for shape in shapes:
                results.append(run_cell(arch, shape, multi_pod=multi_pod,
                                        out_dir=out_dir, force=args.force,
                                        overrides=overrides or None,
                                        variant=args.variant))
    ok = sum(1 for r in results if r["status"] == "ok")
    sk = sum(1 for r in results if r["status"] == "skipped")
    er = sum(1 for r in results if r["status"] == "error")
    print(f"[dryrun] done: {ok} ok, {sk} skipped, {er} errors / {len(results)}")
    return 1 if er else 0


if __name__ == "__main__":
    sys.exit(main())
