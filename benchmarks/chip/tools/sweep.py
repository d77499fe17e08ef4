#!/usr/bin/env python3
"""Rate sweep of an open-loop serve cell, to find the highest rate it
sustains without a growing queue. One engine, built once; for each rate
the mix's warm phase, a window and a drain until the engine is idle.

    python3 benchmarks/chip/tools/sweep.py --workload danube-chat \
        --rates 0.8,1.0,1.25 --seconds 51

From the root of a checkout, on the chip. Prints one JSON line per rate
on standard error, and with ``--out`` writes them all to that file. A
rate is sustained where the mean queue wait (scheduled send to
admission) of the window's second half is no longer than that of its
first half, and every request of the window has its first token by the
window's end or shortly after.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import run  # noqa: E402
from chipbench import harness, serve  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=51)
    ap.add_argument("--seed", type=int, default=4242)
    ap.add_argument("--out", help="also write the rows to this JSON file")
    a = ap.parse_args(argv)
    ctx = run.prepare(run.parse(["--workload", a.workload, "--seed",
                                 str(a.seed), "--seconds", str(a.seconds)]))
    params, eng = serve.build(ctx)
    rows = []
    for i, rate in enumerate(float(r) for r in a.rates.split(",")):
        tf = dict(ctx["tf"], rate_rps=rate)
        c = dict(ctx, tf=tf, seed=a.seed + 1 + i)
        loop = ctx["kind"].OpenLoop(tf, a.seconds, c["seed"],
                                    ctx["m"]["vocab"])
        out = serve.serve_loop(c, eng, loop)
        drv, win0, win1 = out["drv"], out["win0"], out["win1"]
        while not eng.idle:
            drv.step()
        e2e, n = serve._lat_metrics(list(drv.by_rid.values()), win0, win1,
                                    a.seconds)
        recs = sorted((r for r in drv.by_rid.values()
                       if r.phase == "window"), key=lambda r: r.sched)
        waits = [r.req.t_admit - r.sched for r in recs]
        h = len(waits) // 2
        steps = [s for s in drv.steps if win0 <= s["t1"] < win1]
        plain = [s["t1"] - s["t0"] for s in steps
                 if s["decode"] and not s["prefill"]]
        pre = [s["t1"] - s["t0"] for s in steps if s["prefill"]]
        row = {"rate_rps": rate, "requests": n,
               **{k: float(v) for k, v in e2e.items()},
               "wait_s_first_half": float(np.mean(waits[:h])) if h else None,
               "wait_s_second_half": float(np.mean(waits[h:])) if h else None,
               "first_token_after_window": sum(
                   1 for r in recs if r.times[0] > win1),
               "step_ms_decode": float(np.median(plain)) * 1e3
               if plain else None,
               "step_ms_prefill": float(np.median(pre)) * 1e3
               if pre else None,
               "live_slots_mean": float(np.mean([len(s["ctxs"])
                                                 for s in steps]))
               if steps else None}
        harness.log("sweep " + json.dumps(row))
        rows.append(row)
    if a.out:
        Path(a.out).parent.mkdir(parents=True, exist_ok=True)
        Path(a.out).write_text(json.dumps(rows, indent=1) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
