"""Per-layer quantities of a traced serve run, shared by the readers in
``metrics/``. Each takes the reader context that ``serve._layer_ctx``
builds and returns a number, or None where the trace or the step log
holds nothing to read."""
from __future__ import annotations

import math

from . import devtrace, harness, work


def idle_share(ctx):
    """Share of the traced window in which no device op ran, in %."""
    t = ctx["trace"]
    if t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def queue_wait_p95_ms(ctx):
    """p95 of scheduled send to admission over the window's requests."""
    waits = [(r.req.t_admit - r.sched) * 1e3 if r.req.t_admit else math.inf
             for r in ctx["requests"]]
    return harness.percentile(waits, 95) if waits else None


def prefix_hit_rate(ctx):
    """Prompt tokens served from cached pages over the prompt tokens
    admitted in the traced steps, in %, as the engine's admission found
    them."""
    adm = [a for s in ctx["steps"] for a in s["admitted"]]
    toks = sum(n for n, _ in adm)
    if not toks:
        return None
    return 100.0 * sum(hit for _, hit in adm) / toks


def mfu(ctx):
    """Model FLOPs of the traced steps over their wall time and peak."""
    steps = ctx["steps"]
    if not steps:
        return None
    flops = sum(work.model_flops_step(ctx["m"], s["ctxs"], s["prefill"])
                for s in steps)
    wall = sum(s["t1"] - s["t0"] for s in steps)
    return 100.0 * flops / (wall * ctx["chips"] * ctx["peaks"]["bf16_flops"])


def lutq_dot_roofline(ctx):
    """Least time of every lutq_dot call of the traced steps over the
    kernel's device time: the live decode rows, and the real prompt
    tokens of each prefill call with one head row per prompt."""
    steps, m, pk = ctx["steps"], ctx["m"], ctx["peaks"]
    t = devtrace.op_seconds(ctx["trace"]["ops"],
                            work.kernel_match("lutq_dot"))
    if t <= 0 or not steps:
        return None
    need = 0.0
    for s in steps:
        if s["decode"]:
            rows = len(s["ctxs"])
            need += work.lutq_dot_min_time(m, rows, rows, pk)
        if s["prefill"]:
            need += work.lutq_dot_min_time(
                m, sum(n for _, n in s["prefill"]), len(s["prefill"]), pk)
    return 100.0 * need / t


def paged_attn_roofline(ctx):
    """Least time of the paged decode attention of the traced steps (KV
    pages read at each live slot's length, plus q and out) over the
    kernel's device time."""
    m, pk = ctx["m"], ctx["peaks"]
    t = devtrace.op_seconds(ctx["trace"]["ops"],
                            work.kernel_match("paged_attn", m,
                                              ctx["capacity"]))
    if t <= 0:
        return None
    need = 0.0
    for s in ctx["steps"]:
        if not s["decode"]:
            continue
        f, b = work.paged_attn_step(m, s["ctxs"], ctx["page_size"])
        need += work.min_time(f, b, pk)
    return 100.0 * need / t
