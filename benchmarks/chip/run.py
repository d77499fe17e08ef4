#!/usr/bin/env python3
"""Chip benchmark: run one cell of BENCHMARK.json on the chips of this
machine and print its result as the last line of standard output.

    python3 benchmarks/chip/run.py --workload danube-chat --seed 7 \
        --seconds 51 --trace 0

From the root of a checkout. ``--trace 0`` reports the cell's end-to-end
metrics, ``--trace 1`` its per-layer metrics from a profiled run. Every
run checks what the timed path produced against the plain reference and
prints each compared number beside its limit, last on standard error and
under ``checks`` in the result line; ``--control 1`` puts the control
in the program's place, and must come out not correct. Without as many
TPU chips as the cell asks for, or outside a checkout, it exits non-zero
and prints no result.
"""
from __future__ import annotations

import time

T_PROC = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def prepare(args, allow_cpu: bool = False, conf=None, tf=None) -> dict:
    """Resolve the cell's files by name and claim its chips. Tests pass
    ``allow_cpu`` and small ``conf`` / ``tf`` in place of the files."""
    sys.path.insert(0, str(HERE))
    from chipbench import harness

    bench = harness.manifest(ROOT)
    cell = harness.cell(bench, args.workload)
    if conf is None:
        conf_entry = harness.config_entry(bench, cell["config"])
        conf = harness.load_json(ROOT / conf_entry["file"])
    if tf is None:
        tf = harness.load_json(harness.traffic_file(cell["traffic"]))
    kind = harness.load_module(harness.kind_file(tf["kind"]),
                               "chipbench_kind_" + tf["kind"])
    if not (ROOT / "src" / "repro").is_dir():
        raise harness.BenchError(f"{ROOT / 'src' / 'repro'} not found: run "
                                 "from a checkout of the repository")
    sys.path.insert(0, str(ROOT / "src"))
    devs = harness.setup_jax(int(cell["chips"]), allow_cpu=allow_cpu)
    harness.log(f"{len(devs)} {devs[0].device_kind} ready, "
                f"{time.perf_counter() - T_PROC:.1f} s")
    return {"bench": bench, "cell": cell, "conf": conf,
            "m": harness.model_dims(conf), "tf": tf, "kind": kind,
            "seed": int(args.seed), "seconds": float(args.seconds),
            "trace": bool(args.trace), "control": bool(args.control),
            "devs": devs, "t_proc": T_PROC}


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="judge the control (the reference in float8, in "
                    "the program's place) instead: must come out incorrect")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    sys.path.insert(0, str(HERE))
    from chipbench import harness

    try:
        ctx = prepare(args)
        result, checks = ctx["kind"].run(ctx)
    except harness.BenchError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    harness.emit(result, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
